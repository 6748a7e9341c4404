"""The expert-layer model (DeepSeek-V2-Lite's block: latent attention
with YaRN, a dense first layer, dropless routing over a share of the
experts, shared experts) against its plain reference
(``configs/deepseek-v2-lite-ep8-sparse-experts.py``), at a size a CPU
test holds: float32, 2 of the router's 8 experts held at top-3, YaRN
kept, ``norm_topk_prob`` false.  Logits, loss and every gradient leaf;
the share test (the disjoint shares' routed outputs, with the shared
experts counted once, give the uncut layer); a router that sends every
token to one expert; and the fused Adam step against the two-pass one
for an expert that gets no rows."""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest

from chipbench import bench
from chipbench.tests import tiny_moe

SEED = 2**35 + 7
bench.use_program_sources()


def _setup(**conf):
    cell = tiny_moe.tiny_cell(**conf)
    ad = bench.load_module(bench.HERE / "adapters" / "moe_lm.py")
    ref = bench.load_module(cell.reference_file)
    pats = ad.patterns(cell.config)
    flat = ad.flat_weights(cell.config, pats, bench.seed_key(SEED))
    toks = ad.TokenBatches(SEED, 2, 32, cell.config["vocab_held"])(0)
    return cell.config, ad, ref, pats, flat, toks


def _arch(ad, conf, engine):
    arch = ad.arch_config(conf, param_dtype="float32", fused_update=False)
    return dataclasses.replace(arch, dtype="float32", engine=engine)


def _ref_pats(pats):
    import jax.numpy as jnp
    return {k: {"idx": jnp.asarray(v["idx"])} for k, v in pats.items()}


@pytest.mark.parametrize("engine", ["jnp", "pallas"])
def test_loss_logits_and_every_gradient_match_the_reference(engine):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    conf, ad, ref, pats, flat, toks = _setup()
    arch = _arch(ad, conf, engine)
    names = set(flat)

    def prog(fl):
        return M.loss_fn(arch, ad.to_program(conf, pats, fl, jnp.float32),
                         {"tokens": jnp.asarray(toks)})
    (loss, metrics), grads = jax.value_and_grad(prog, has_aux=True)(flat)
    want_loss, want = ref.make_batch_grad(conf, pats)(flat, toks)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert int(metrics["moe_dropped_rows"]) == 0
    assert set(grads) == names
    for k in sorted(names):
        g, w = np.asarray(grads[k]), np.asarray(want[k])
        scale = max(np.abs(w).max(), 1e-6)
        np.testing.assert_allclose(g, w, rtol=0, atol=2e-4 * scale,
                                   err_msg=k)
    logits, _, _ = M.forward(arch, ad.to_program(conf, pats, flat,
                                                 jnp.float32),
                             {"tokens": jnp.asarray(toks)})
    with jax.default_matmul_precision("highest"):
        x, _ = ref.final_hidden(conf, _ref_pats(pats), flat,
                                jnp.asarray(toks[1]))
        want_logits = x @ flat["embed/out"]
    np.testing.assert_allclose(np.asarray(logits[1]),
                               np.asarray(want_logits), rtol=0, atol=2e-4)


def _layer_inputs(conf, flat):
    import jax
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 32, conf["hidden_size"]))
    lp = {k[len("layers/"):]: v[0] for k, v in flat.items()
          if k.startswith("layers/")}
    return h, lp


def _program_layer(ad, conf, pats, flat, h, **moe):
    """One expert layer of the program (first expert layer's weights),
    with the held experts sliced from ``flat``'s."""
    import jax.numpy as jnp
    from repro.models import moe as moe_mod
    arch = _arch(ad, conf, "pallas")
    arch = dataclasses.replace(arch, moe=dataclasses.replace(arch.moe, **moe))
    tree = ad.to_program(conf, pats, flat, jnp.float32)["layers"]["moe"]
    p = {k: (v[0] if k not in ("wg", "wi", "wo") else
             v[0, arch.moe.first_held:arch.moe.first_held + arch.moe.held_])
         for k, v in tree.items() if k != "shared"}
    p["shared"] = {j: {k: v[0] for k, v in d.items()}
                   for j, d in tree["shared"].items()}
    return moe_mod.moe_apply(p, h, arch)


def test_shares_add_up_to_the_uncut_layer():
    """Four chips each holding 2 of 8 experts: their routed outputs, the
    shared experts counted once, equal the reference layer holding all 8."""
    import jax
    import jax.numpy as jnp
    from repro.models.layers import mlp_apply
    conf, ad, ref, pats, flat, toks = _setup(n_routed_experts=8)
    h, lp = _layer_inputs(conf, flat)
    total = 0.0
    for first in range(0, 8, 2):
        y, _, st = _program_layer(ad, conf, pats, flat, h, held=2,
                                  first_held=first)
        assert int(st["moe_dropped_rows"]) == 0
        total = total + y
    arch = _arch(ad, conf, "pallas")
    tree = ad.to_program(conf, pats, flat, jnp.float32)["layers"]["moe"]
    shared = mlp_apply({j: {k: v[0] for k, v in d.items()}
                        for j, d in tree["shared"].items()}, h, arch)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(conf, _ref_pats(pats), lp, h[b], None)[0]
                          for b in range(2)])
    np.testing.assert_allclose(np.asarray(total - 3 * shared),
                               np.asarray(want), rtol=0, atol=3e-4)


def test_one_expert_takes_every_token_and_nothing_drops():
    """A router that sends every token to held expert 0: its count is
    the token count, nothing is dropped, and the rows past each count
    (unwritten by the kernels) add nothing to the output."""
    import jax
    import jax.numpy as jnp
    conf, ad, ref, pats, flat, toks = _setup()
    flat = dict(flat)
    flat["layers/moe/router"] = flat["layers/moe/router"].at[..., 0].add(
        10.0)
    h, lp = _layer_inputs(conf, flat)
    h = h + 1.0
    y, _, st = _program_layer(ad, conf, pats, flat, h)
    assert int(st["moe_max_expert_rows"]) == 64      # every token
    assert int(st["moe_dropped_rows"]) == 0
    assert int(st["moe_routed_rows"]) >= 64
    assert np.isfinite(np.asarray(y)).all()
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(conf, _ref_pats(pats), lp, h[b], None)[0]
                          for b in range(2)])
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=0,
                               atol=3e-4 * float(jnp.abs(want).max()))


def test_fused_adam_matches_two_pass_for_an_expert_with_no_rows():
    """Step 1 routes normally (every held expert gets rows and moments);
    before step 2 the router stops sending anything to held expert 1.
    The fused step must still move that expert from its moments, as the
    two-pass Adam does."""
    import jax
    import jax.numpy as jnp
    from repro.optim import constant_schedule, fused_adam
    from repro.train.steps import make_train_step
    conf, ad, ref, pats, flat, toks = _setup()
    arch = dataclasses.replace(_arch(ad, conf, "pallas"), fused_update=True)
    opt = fused_adam(constant_schedule(1e-3))
    fused = make_train_step(arch, opt, donate=False)
    twopass = make_train_step(dataclasses.replace(arch, fused_update=False),
                              opt, donate=False)
    params = ad.to_program(conf, pats, flat, jnp.float32)
    batch = {"tokens": jnp.asarray(toks)}
    out = {}
    for name, step in (("fused", fused), ("twopass", twopass)):
        p, s = params, opt.init(params)
        p, s, _ = step(p, s, batch, jnp.asarray(0))
        moe = dict(p["layers"]["moe"])
        moe["router"] = moe["router"].at[..., 1].add(-1e4)
        p1 = dict(p, layers=dict(p["layers"], moe=moe))
        p, s, m = step(p1, s, batch, jnp.asarray(1))
        out[name] = (p1, p, m)
    p1, pf, mf = out["fused"]
    _, pt, _ = out["twopass"]
    assert int(mf["moe_dropped_rows"]) == 0
    for k in ("wg", "wi", "wo"):
        # expert 1 took no row in step 2 and still moved
        assert float(jnp.abs(pf["layers"]["moe"][k][:, 1]
                             - p1["layers"]["moe"][k][:, 1]).max()) > 0
        np.testing.assert_allclose(np.asarray(pf["layers"]["moe"][k]),
                                   np.asarray(pt["layers"]["moe"][k]),
                                   rtol=2e-4, atol=2e-6, err_msg=k)
