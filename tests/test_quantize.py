"""Quantized inference datapath (ISSUE 8): int8/fxp junction kernels.

Pins the PR's acceptance criteria: int8 forwards sit within the analytic
quantization tolerance of fp32 and the two engines agree to float
rounding; the fxp path is ENGINE-EXACT and bit-exact against the
core/fixed_point.py clipping-tree reference on the paper's Table II
triplets (on data where no intermediate adder clips, so the two
semantics provably coincide); MoE expert junctions quantize per expert;
every train entry point refuses integer-code weights; quantize-at-load
serving decodes greedily like fp32 and its decode jaxpr contains ONLY
the quantized forward kernels; and the ragged-shape padding that
replaced the hard tile asserts in fxp_qmatmul / sigmoid_lut round-trips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ArchConfig, MoEConfig
from repro.core import fixed_point as fp
from repro.core import quantize as qz
from repro.core import sparse_linear as sl
from repro.core.sparsity import SparsityConfig, make_block_pattern
from repro.kernels import ops


def _junction(n_in=256, n_out=128, density=0.5, block=32, bias=True, seed=0):
    sp = SparsityConfig(density=density, block=block)
    p = sl.init_sparse(jax.random.PRNGKey(seed), n_in, n_out, sp, bias=bias)
    if bias:
        p["b"] = jax.random.normal(jax.random.PRNGKey(seed + 1),
                                   (n_out,)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (70, n_in))
    return p, x


# ------------------------------------------------------------ weight codes
@pytest.mark.parametrize("granularity", ["block", "unit"])
def test_int8_codes_dequantize_within_half_step(granularity):
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 32, 32))
    codes, scale = qz.quantize_weights(w, bits=8, granularity=granularity)
    assert codes.dtype == jnp.int8 and scale.shape == (4, 3)
    deq = codes.astype(jnp.float32) * scale[..., None, None]
    # symmetric round-to-nearest: error bounded by half a quantization step
    err = np.abs(np.asarray(deq) - np.asarray(w))
    assert np.all(err <= np.asarray(scale)[..., None, None] / 2 + 1e-7)
    if granularity == "unit":
        assert len(np.unique(np.asarray(scale))) == 1


def test_int8_sub8_bits_clip_tighter():
    w = jax.random.normal(jax.random.PRNGKey(0), (4, 3, 32, 32))
    codes4, _ = qz.quantize_weights(w, bits=4)
    assert int(jnp.max(jnp.abs(codes4.astype(jnp.int32)))) <= 7
    codes2, _ = qz.quantize_weights(w, bits=2)
    assert int(jnp.max(jnp.abs(codes2.astype(jnp.int32)))) <= 1
    for bad in (1, 9):
        with pytest.raises(ValueError):
            qz.QuantConfig(mode="int8", bits=bad)


def test_zero_block_scale_stays_finite():
    w = jnp.zeros((2, 2, 32, 32))
    codes, scale = qz.quantize_weights(w)
    assert np.all(np.asarray(scale) == 1.0)     # no 0/0 in the dequant
    assert np.all(np.asarray(codes) == 0)


# --------------------------------------------------------------- int8 path
def test_int8_fwd_within_analytic_tolerance_of_fp32():
    p, x = _junction()
    pq = qz.quantize_junction(p, qz.QuantConfig(mode="int8"))
    assert "w" not in pq and "wq" in pq          # fp leaf provably gone
    y_fp = sl.apply(p, x, engine="jnp", act="none")
    y_q = sl.apply(pq, x, engine="jnp", act="none")
    err = np.max(np.abs(np.asarray(y_q) - np.asarray(y_fp)))
    # 8-bit symmetric weight+activation quantization over a kb*bs=64 fan-in
    # at unit-scale activations: observed ~0.01, bound generously
    assert 0.0 < err < 0.08


@pytest.mark.parametrize("granularity", ["block", "unit"])
@pytest.mark.parametrize("static_x", [False, True])
def test_int8_engine_parity(granularity, static_x):
    """The jnp sim mirrors the kernel op-for-op (same scale grouping, same
    per-slot accumulation order) — parity is float rounding, not an
    approximation tolerance."""
    p, x = _junction()
    xs = float(jnp.max(jnp.abs(x))) / 127.0 if static_x else None
    pq = qz.quantize_junction(
        p, qz.QuantConfig(mode="int8", granularity=granularity), x_scale=xs)
    assert ("x_scale" in pq) == static_x
    y_jnp = sl.apply(pq, x, engine="jnp", act="sigmoid")
    y_pal = sl.apply(pq, x, engine="pallas", act="sigmoid")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_jnp),
                               atol=1e-5, rtol=1e-5)


def test_gated_int8_matches_two_branch_sim():
    """gated_fwd_int8 (shared activation codes, silu(g)*u epilogue) vs the
    two plain int8 sims composed — same quantization formula, so the only
    difference is float rounding."""
    sp = SparsityConfig(density=0.5, block=32)
    pg = sl.init_sparse(jax.random.PRNGKey(0), 256, 128, sp)
    pi = sl.init_sparse(jax.random.PRNGKey(1), 256, 128, sp)
    x = jax.random.normal(jax.random.PRNGKey(2), (45, 256))
    wgq, wg_s = qz.quantize_weights(pg["w"])
    wiq, wi_s = qz.quantize_weights(pi["w"])
    y = ops.junction_matmul(x, wgq, pg["idx"], pg["rev_ob"], pg["rev_t"],
                            pg["rev_cnt"], wi=wiq, w_scale=wg_s,
                            wi_scale=wi_s)
    g = qz._int8_apply(x, wgq, pg["idx"], wg_s)
    u = qz._int8_apply(x, wiq, pg["idx"], wi_s)
    want = jax.nn.silu(g) * u
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_calibrated_scales_positive_and_layerwise():
    layers = [sl.init_sparse(jax.random.PRNGKey(i), 256, 256,
                             SparsityConfig(density=0.5, block=32))
              for i in range(2)]
    x = jax.random.normal(jax.random.PRNGKey(9), (64, 256))
    scales = qz.calibrate_layer_scales(layers, x, act="sigmoid")
    assert len(scales) == 2 and all(s > 0.0 for s in scales)
    # layer 1 sees sigmoid outputs in (0, 1): its absmax/127 is below
    # the raw-input scale
    assert scales[1] < scales[0]


# ---------------------------------------------------------------- fxp path
def test_fxp_engine_exact():
    """No tolerance: the fxp pipeline is integer end to end, so the Pallas
    kernel and the jnp sim must agree bit for bit."""
    p, x = _junction()
    pq = qz.quantize_junction(p, qz.QuantConfig(mode="fxp", act="sigmoid"))
    assert "qfmt" in pq and pq["qlut"].shape == (fp.PAPER_FMT.n_codes,)
    y_jnp = sl.apply(pq, x, engine="jnp")
    y_pal = sl.apply(pq, x, engine="pallas")
    assert jnp.array_equal(y_jnp, y_pal)
    # and the LUT epilogue actually ran: outputs are sigmoid-range codes
    assert float(jnp.min(y_jnp)) >= 0.0 and float(jnp.max(y_jnp)) <= 1.0


@pytest.mark.parametrize("fmt", fp.PAPER_TRIPLETS,
                         ids=lambda f: f"bw{f.bw}bn{f.bn}bf{f.bf}")
def test_fxp_bitexact_vs_clipping_tree(fmt):
    """Bit-exact against the paper's clipping-tree semantics on data where
    the two provably coincide: activations on the 2^-5 grid in
    [-0.25, 0.25] (exact in every Table II triplet), integer weights in
    {-1, 0, 1} with <= 4 live rows per block (|partial sums| <= 2 plus a
    bias in [-0.5, 0.5] stays under every triplet's max_val, so no adder
    clips and every product lands on the grid)."""
    bs, nib, nob, kb = 8, 8, 2, 2
    pat = make_block_pattern(nib * bs, nob * bs, kb / nib, bs)
    assert pat.fan_in_blocks == kb
    rng = np.random.default_rng(fmt.bw * 100 + fmt.bf)
    M = 24
    x = jnp.asarray(rng.integers(-8, 9, size=(M, nib * bs)) / 32.0,
                    jnp.float32)
    w_int = rng.integers(-1, 2, size=(nob, kb, bs, bs)).astype(np.float32)
    w_int[:, :, 4:, :] = 0.0                      # <= 4 live rows per block
    w = jnp.asarray(w_int)
    b = jnp.asarray(rng.integers(-16, 17, size=(nob * bs,)) / 32.0,
                    jnp.float32)
    p = {"w": w, "b": b, "idx": jnp.asarray(pat.idx),
         "rev_ob": jnp.asarray(pat.rev_ob), "rev_t": jnp.asarray(pat.rev_t),
         "rev_cnt": jnp.asarray(pat.rev_cnt)}

    # the clipping-tree reference: q_mul every edge, tree-sum with clipping
    # at every adder node, q_add the bias, sigmoid LUT on the result code
    xb = x.reshape(M, nib, bs)
    terms = []
    for k in range(kb):
        xk = xb[:, pat.idx[:, k], :]                        # [M, nob, bs]
        terms.append(fp.q_mul(xk[:, :, :, None], w[None, :, k], fmt))
    terms = jnp.concatenate(terms, axis=2)          # [M, nob, kb*bs, bs]
    s = fp.tree_sum_clipped(terms, fmt, axis=2).reshape(M, nob * bs)
    s = fp.q_add(s, fp.quantize(b, fmt), fmt)
    want = fp.lut_sigmoid(s, fmt)[0]

    pq = qz.quantize_junction(p, qz.QuantConfig(mode="fxp", fmt=fmt,
                                                act="sigmoid"))
    for engine in ("jnp", "pallas"):
        got = sl.apply(pq, x, engine=engine)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=f"engine={engine}")


def test_fxp_refuses_gated_and_moe():
    with pytest.raises(ValueError, match="plain junctions only"):
        qz.quantize_junction({"idx_in": jnp.zeros((2, 2), jnp.int32),
                              "wg": jnp.zeros((2, 2, 2, 32, 32))},
                             qz.QuantConfig(mode="fxp"))
    w = jnp.zeros((2, 2, 32, 32), jnp.int32)
    with pytest.raises(ValueError, match="plain junctions"):
        ops.junction_matmul(jnp.zeros((4, 64)), w,
                            jnp.zeros((2, 2), jnp.int32), None, None, None,
                            wi=w, qfmt=jnp.asarray([8, 3], jnp.int32),
                            qlut=jnp.zeros((4096,)))


# ----------------------------------------------------------- MoE junctions
def _moe_cfg(engine="jnp"):
    return ArchConfig(
        name="quant-moe-test", family="moe", n_layers=1, d_model=128,
        n_heads=4, kv_heads=4, head_dim=32, d_ff=256, vocab=128,
        act="silu", max_seq=64, attn_chunk=32, dtype="float32",
        moe=MoEConfig(num_experts=4, top_k=2, d_expert=64),
        sparsity=SparsityConfig(density=0.5, block=32, where="ffn"),
        engine=engine)


def test_moe_expert_int8_parity_and_tolerance():
    """Per-expert [E, nob, kb] scales through both expert junctions: the
    quantized jnp twin tracks fp32 within quantization error, and the
    Pallas expert kernels match the twin to float rounding."""
    from repro.models import moe as moe_mod

    cfg = _moe_cfg("jnp")
    params = moe_mod.moe_init(jax.random.PRNGKey(0), cfg)
    assert "idx_in" in params
    pq = qz.quantize_tree(params, qz.QuantConfig(mode="int8"))
    assert "wgq" in pq and pq["wg_scale"].shape == params["wg"].shape[:3]
    for k in ("wg", "wi", "wo"):
        assert k not in pq
    assert jnp.array_equal(pq["router"], params["router"])  # dense stays fp

    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))
    y_fp, aux_fp, _ = moe_mod.moe_apply(params, x, cfg)
    y_q, aux_q, _ = moe_mod.moe_apply(pq, x, cfg)
    assert float(aux_q) == float(aux_fp)         # routing untouched
    rel = (np.linalg.norm(np.asarray(y_q) - np.asarray(y_fp))
           / np.linalg.norm(np.asarray(y_fp)))
    assert 0.0 < rel < 0.05

    y_pal, _, _ = moe_mod.moe_apply(pq, x, dataclasses.replace(
        cfg, engine="pallas"))
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_q),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------- train refusals
def test_train_update_refuses_integer_codes():
    p, x = _junction(bias=False)
    pq = qz.quantize_junction(p, qz.QuantConfig(mode="int8"))
    hyp = jnp.asarray([0.1, 0.9], jnp.float32)
    with pytest.raises(ValueError, match="inference-only"):
        ops.junction_train_update(x, pq["wq"], pq["idx"], pq["rev_ob"],
                                  pq["rev_t"], pq["rev_cnt"], hyp=hyp)
    # and the fp path refuses bare integer codes without their scales
    with pytest.raises(ValueError, match="quantization leaves"):
        ops.junction_matmul(x, pq["wq"], pq["idx"], pq["rev_ob"],
                            pq["rev_t"], pq["rev_cnt"])


def test_inject_update_ctx_refuses_quantized_junction():
    p, _ = _junction()
    pq = qz.quantize_junction(p, qz.QuantConfig(mode="int8"))
    tree = {"layer0": pq}
    with pytest.raises(ValueError, match="inference-only"):
        sl.inject_update_ctx(tree, None, jnp.asarray([0.1, 0.9]))


def test_apply_refuses_fused_ctx_on_quantized_junction():
    p, x = _junction()
    pq = qz.quantize_junction(p, qz.QuantConfig(mode="int8"))
    pq[sl.UPDATE_HYP_LEAF] = jnp.asarray([0.1, 0.9])
    for engine in ("jnp", "pallas"):
        with pytest.raises(ValueError, match="inference-only"):
            sl.apply(pq, x, engine=engine)


# ------------------------------------------------------------ tree / serve
def test_quantize_tree_scopes_to_junctions_and_is_idempotent():
    tree = {
        "dense": {"w": jnp.ones((8, 8))},                 # no pattern: stays
        "junction": _junction()[0],
        "nested": [{"inner": _junction(seed=3)[0]}],
    }
    out = qz.quantize_tree(tree, qz.QuantConfig(mode="int8"))
    assert "w" in out["dense"] and "wq" not in out["dense"]
    assert "wq" in out["junction"] and "w" not in out["junction"]
    assert "wq" in out["nested"][0]["inner"]
    # second pass: nothing fp left to quantize, tree passes through
    again = qz.quantize_tree(out, qz.QuantConfig(mode="int8"))
    assert jax.tree.structure(again) == jax.tree.structure(out)


def test_serve_quantize_at_load_greedy_stable_and_jaxpr():
    """Acceptance: serving end to end with ServeConfig.quantize — greedy
    decode stays in agreement with fp32, the quantized decode step's
    jaxpr contains the int8 forward kernel and NO fp junction forward,
    and fxp is refused at the serve boundary."""
    from repro.models import model as M
    from repro.serve.engine import Engine, ServeConfig
    from repro.train.steps import make_decode_step

    cfg = registry.get("stablelm-3b").reduced().with_sparsity(
        SparsityConfig(density=0.5, block=32, where="ffn"))
    params = M.init(cfg, jax.random.PRNGKey(0))
    prompts = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (2, 8),
                                            0, cfg.vocab))
    n_new = 6
    tok_fp = Engine(cfg, params,
                    ServeConfig(max_new_tokens=n_new)).generate(prompts)
    tok_q = Engine(cfg, params,
                   ServeConfig(max_new_tokens=n_new,
                               quantize="int8")).generate(prompts)
    agreement = float(np.mean(tok_fp == tok_q))
    assert agreement >= 0.75, (tok_fp, tok_q)

    with pytest.raises(ValueError, match="int8"):
        Engine(cfg, params, ServeConfig(quantize="fxp"))

    # the quantized decode step lowers to the int8 kernels ONLY: no fp
    # junction forward survives in the jaxpr (the fp weight leaf is gone)
    cfg_p = dataclasses.replace(cfg, engine="pallas")
    pq = qz.quantize_tree(params, qz.QuantConfig(mode="int8"))
    step = make_decode_step(cfg_p)
    cache = M.make_cache(cfg_p, 2, 16)
    tok = jnp.zeros((2, 1), jnp.int32)
    txt = str(jax.make_jaxpr(step)(pq, cache, tok,
                                   jnp.asarray(8, jnp.int32)))
    assert "junction_fwd_int8" in txt
    assert "junction_fwd" not in txt.replace("junction_fwd_int8", "")


# ------------------------------------------------- config / cohort plumbing
def test_quant_config_validation_and_structure_keys():
    from repro.search import bucket_quant

    with pytest.raises(ValueError):
        qz.QuantConfig(mode="int4")
    with pytest.raises(ValueError):
        qz.QuantConfig(granularity="tensor")
    with pytest.raises(ValueError):
        qz.QuantConfig(mode="fxp", act="gelu")

    configs = [qz.QuantConfig(mode="int8", bits=b, granularity=g)
               for b in (8, 6, 4) for g in ("block", "unit")]
    configs += [qz.QuantConfig(mode="fxp", fmt=f) for f in fp.PAPER_TRIPLETS]
    cohorts = bucket_quant(configs)
    # all int8 configs share one cohort (codes share the int8 container,
    # scales the [nob, kb] layout); each fxp triplet is structural
    assert len(cohorts) == 1 + len(fp.PAPER_TRIPLETS)
    assert cohorts[0].key == ("int8",) and cohorts[0].size == 6
    assert cohorts[0].member_ids == tuple(range(6))
    for co in cohorts[1:]:
        assert co.key[0] == "fxp" and co.size == 1


# -------------------------------------------- ragged-tile kernel regressions
def test_qmatmul_ragged_shapes_pad_to_tile():
    """fxp_qmatmul used to hard-assert M % bm == 0 — ragged M/K/N must now
    pad to the tile and slice back, bit-exact vs the oracle."""
    from repro.kernels import fxp_qmatmul as fxpk
    from repro.kernels import ref

    lim = 1 << 7
    a = jax.random.randint(jax.random.PRNGKey(0), (75, 33), -lim, lim)
    w = jax.random.randint(jax.random.PRNGKey(1), (33, 50), -lim, lim)
    y = fxpk.qmatmul(a, w, bf=5, bn=2, interpret=True)
    assert y.shape == (75, 50)
    assert jnp.array_equal(y, ref.fxp_qmatmul(a, w, 5, 2))


def test_lut_lookup_ragged_rows_pad_to_tile():
    from repro.kernels import sigmoid_lut as slutk

    table, _ = fp.sigmoid_tables(fp.PAPER_FMT)
    codes = jax.random.randint(jax.random.PRNGKey(0), (37, 77), 0, 4096)
    y = slutk.lut_lookup(codes, jnp.asarray(table), interpret=True)
    assert y.shape == (37, 77)
    assert jnp.array_equal(y, jnp.take(jnp.asarray(table), codes, axis=0))
