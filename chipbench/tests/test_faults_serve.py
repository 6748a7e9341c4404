"""A serving run whose decode step alters the token it produces must
come out not correct: the harness's own run at a size a CPU test can
hold, chip check skipped."""
from __future__ import annotations

from chipbench import bench, run
from chipbench.tests import tiny


def _run():
    import jax
    return run.run_cell(tiny.tiny_cell("stablelm3b-serve-batch"),
                        seed=2**37 + 9, seconds=0.2, trace=False,
                        devices=jax.devices()[:1])


def test_sound_serve_is_correct():
    result = _run()
    assert result["correct"], result["checks"]
    assert result["metrics"]["serve_itl_p95_ms"]["value"] > 0


def test_altered_token_is_caught(monkeypatch):
    bench.use_program_sources()
    from repro.serve import engine

    real = engine.make_decode_step

    def altered(cfg, **kw):
        step = real(cfg, **kw)

        def decode(*args):
            logits, pool = step(*args)
            # token 0 wins every decode tick
            return logits.at[..., 0].set(logits.max() + 1.0), pool
        return decode

    monkeypatch.setattr(engine, "make_decode_step", altered)
    result = _run()
    assert not result["correct"], result["checks"]
