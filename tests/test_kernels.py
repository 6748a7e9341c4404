"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sparsity import make_block_pattern
from repro.kernels import ops, ref


@pytest.mark.parametrize("n_in,n_out,density", [
    (512, 256, 0.25), (1024, 512, 0.125), (256, 256, 0.5), (384, 640, 0.34),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_sparse_fwd(n_in, n_out, density, dtype):
    pat = make_block_pattern(n_in, n_out, density, 128)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (130, n_in)).astype(dtype)   # non-multiple rows
    w = (jax.random.normal(jax.random.PRNGKey(1),
                           (pat.n_out_blocks, pat.fan_in_blocks, 128, 128))
         * 0.05).astype(dtype)
    y = ops.junction_matmul(x, w, jnp.asarray(pat.idx),
                            jnp.asarray(pat.rev_ob), jnp.asarray(pat.rev_t),
                            jnp.asarray(pat.rev_cnt))
    yr = ref.block_sparse_matmul(x, w, jnp.asarray(pat.idx))
    tol = 1e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), rtol=tol, atol=tol)


def test_block_sparse_grads_vs_oracle():
    pat = make_block_pattern(512, 384, 0.25, 128)
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (128, 512))
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (pat.n_out_blocks, pat.fan_in_blocks, 128, 128)) * 0.05
    idx = jnp.asarray(pat.idx)
    rob, rt, rc = (jnp.asarray(pat.rev_ob), jnp.asarray(pat.rev_t),
                   jnp.asarray(pat.rev_cnt))
    co = jax.random.normal(jax.random.PRNGKey(2), (128, 384))

    f = lambda x, w: jnp.sum(ops.junction_matmul(x, w, idx, rob, rt, rc) * co)
    g = lambda x, w: jnp.sum(ref.block_sparse_matmul(x, w, idx) * co)
    dx1, dw1 = jax.grad(f, (0, 1))(x, w)
    dx2, dw2 = jax.grad(g, (0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(dx1), np.asarray(dx2), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(dw1), np.asarray(dw2), rtol=1e-3, atol=1e-3)


def test_block_sparse_bias_and_lead_dims():
    pat = make_block_pattern(256, 128, 0.5, 128)
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 256))
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (pat.n_out_blocks, pat.fan_in_blocks, 128, 128)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (128,))
    y = ops.junction_matmul(x, w, jnp.asarray(pat.idx),
                            jnp.asarray(pat.rev_ob), jnp.asarray(pat.rev_t),
                            jnp.asarray(pat.rev_cnt), bias=b)
    yr = ref.block_sparse_matmul(x.reshape(15, 256), w, jnp.asarray(pat.idx)) + b
    np.testing.assert_allclose(np.asarray(y).reshape(15, 128), np.asarray(yr),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("M,K,N", [(128, 128, 128), (100, 200, 96), (257, 130, 50)])
@pytest.mark.parametrize("bf,bn", [(8, 3), (5, 2), (11, 4)])
def test_fxp_qmatmul_sweep(M, K, N, bf, bn):
    key = jax.random.PRNGKey(M * K + N)
    lim = 1 << (bn + bf)
    a = jax.random.randint(key, (M, K), -lim, lim)
    w = jax.random.randint(jax.random.PRNGKey(1), (K, N), -lim, lim)
    y = ops.fxp_qmatmul(a, w, bf=bf, bn=bn)
    yr = ref.fxp_qmatmul(a, w, bf, bn)
    assert jnp.array_equal(y, yr), "fixed-point matmul must be bit-exact"


def test_sigmoid_lut_kernel():
    from repro.core import fixed_point as fxp
    t, _ = fxp.sigmoid_tables(fxp.PAPER_FMT)
    codes = jax.random.randint(jax.random.PRNGKey(0), (300, 77), 0, 4096)
    y = ops.sigmoid_lut(codes, jnp.asarray(t))
    assert jnp.array_equal(y, ref.sigmoid_lut(codes, jnp.asarray(t)))


@pytest.mark.parametrize("B,S,di,N,chunk,bd", [
    (2, 128, 512, 16, 64, 256), (1, 256, 256, 8, 128, 256), (3, 64, 1024, 32, 32, 512),
])
def test_selective_scan_kernel(B, S, di, N, chunk, bd):
    """Fused Mamba-1 scan kernel (§Perf F4) vs sequential oracle."""
    from repro.kernels.selective_scan import selective_scan
    ks = jax.random.split(jax.random.PRNGKey(B * S + di), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di))) * 0.1
    x = jax.random.normal(ks[1], (B, S, di))
    bc = jax.random.normal(ks[2], (B, S, N))
    cc = jax.random.normal(ks[3], (B, S, N))
    a = -jnp.exp(jax.random.normal(ks[4], (di, N)) * 0.3)
    h0 = jax.random.normal(ks[5], (B, di, N)) * 0.1
    y1, h1 = selective_scan(dt, x, bc, cc, a, h0, chunk=chunk, bd=bd,
                            interpret=True)
    y2, h2 = ref.selective_scan(dt, x, bc, cc, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=3e-4, rtol=3e-4)


def test_selective_scan_traffic_model():
    from repro.kernels.selective_scan import hbm_bytes
    # falcon-mamba train_4k per-device slice: B=16, S=4096, di=512, N=16
    per_layer = hbm_bytes(16, 4096, 512, 16)
    assert per_layer < 0.5 * 2**30     # < 0.5 GiB per layer pass


@pytest.mark.parametrize("H,Hkv,Sq,window", [
    (4, 4, 128, 0), (8, 2, 128, 0), (4, 2, 256, 96), (2, 1, 64, 0),
])
def test_flash_attention_kernel(H, Hkv, Sq, window):
    """Pallas flash attention vs naive oracle (causal + sliding window, GQA)."""
    from repro.kernels.flash_attention import mha
    B, D = 2, 32
    ks = jax.random.split(jax.random.PRNGKey(H * Sq), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sq, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sq, Hkv, D), jnp.float32)
    got = mha(q, k, v, causal=True, window=window, interpret=True, bq=64, bk=64)

    rep = H // Hkv
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((Sq, Sq), bool))
    if window:
        qp = jnp.arange(Sq)[:, None]
        kp = jnp.arange(Sq)[None, :]
        mask = mask & (qp - kp < window)
    s = jnp.where(mask[None, None], s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def _attn_oracle(q, k, v, causal, window):
    rep = q.shape[2] // k.shape[2]
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(q.shape[-1])
    qp = jnp.arange(q.shape[1])[:, None]
    kp = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones_like(qp >= kp) if not causal else (qp >= kp)
    if window:
        mask = mask & (qp - kp < window)
    s = jnp.where(mask[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vf)


@pytest.mark.parametrize("Sq,Sk,causal,window", [
    (37, 37, True, 0),    # off-tile square
    (37, 53, False, 0),   # off-tile rectangular non-causal: the padded KV
                          # rows are only excluded by the explicit
                          # kv_len mask, not the causal one
    (100, 100, True, 48), # off-tile windowed
    (1, 64, False, 0),    # single query row
])
def test_flash_attention_ragged_shapes(Sq, Sk, causal, window):
    """flash_attention pads ragged Sq/Sk to the tile internally (used to
    assert) and the pad rows/cols never leak into the output."""
    from repro.kernels.flash_attention import mha
    B, H, D = 2, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(Sq * Sk), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Sk, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Sk, H, D), jnp.float32)
    got = mha(q, k, v, causal=causal, window=window,
              interpret=True, bq=64, bk=64)
    assert got.shape == q.shape
    want = _attn_oracle(q, k, v, causal, window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("H,Hkv,window", [
    (4, 4, 0), (8, 2, 0), (4, 2, 96),
])
def test_mha_matches_chunked_attention(H, Hkv, window):
    """The Pallas kernel and the models/attention.chunked_attention
    reference (the path the model actually serves through on jnp) agree
    — causal, GQA and windowed variants."""
    from repro.kernels.flash_attention import mha
    from repro.models.attention import chunked_attention
    B, S, D = 2, 128, 32
    ks = jax.random.split(jax.random.PRNGKey(H * 7 + window), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)
    got = mha(q, k, v, causal=True, window=window, interpret=True,
              bq=64, bk=64)
    want = chunked_attention(q, k, v, causal=True, window=window, chunk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


# ---------------------------------------------------- fused engine autodiff
def _ragged_pattern(n_in, n_out, density, bs):
    """Pattern whose fan-out is ragged (+-1) — exercises the rev_cnt mask."""
    pat = make_block_pattern(n_in, n_out, density, bs)
    assert pat.rev_cnt.min() != pat.rev_cnt.max(), \
        "shape choice no longer ragged — rev_cnt mask untested"
    return pat


@pytest.mark.parametrize("bs", [8, 128])
@pytest.mark.parametrize("act", ["none", "relu", "sigmoid", "silu", "gelu"])
def test_block_sparse_vjp_fused_epilogue(bs, act):
    """custom_vjp (dx/dw/db through the fused kernels, activation grad
    recomputed in the backward prologue) vs jax.grad of apply_jnp + the
    same epilogue — ragged fan-out, non-multiple-of-bm row count."""
    from repro.core import sparse_linear as sl

    n_in, n_out = 10 * bs, 6 * bs          # nib=10, nob=6
    pat = _ragged_pattern(n_in, n_out, 0.34, bs)   # kb=3 over nib=10: ragged
    key = jax.random.PRNGKey(bs)
    M = 45                                  # non-multiple of any bm
    x = jax.random.normal(key, (M, n_in))
    w = jax.random.normal(jax.random.PRNGKey(1),
                          (pat.n_out_blocks, pat.fan_in_blocks, bs, bs)) * 0.1
    b = jax.random.normal(jax.random.PRNGKey(2), (n_out,)) * 0.3
    co = jax.random.normal(jax.random.PRNGKey(3), (M, n_out))
    idx, rob, rt, rc = (jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
                        jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt))

    def f_pallas(x, w, b):
        y = ops.junction_matmul(x, w, idx, rob, rt, rc, bias=b, act=act)
        return jnp.sum(y * co)

    def f_jnp(x, w, b):
        p = {"w": w, "idx": idx, "b": b}
        return jnp.sum(sl._with_act(sl.apply_jnp(p, x), act) * co)

    l1, g1 = jax.value_and_grad(f_pallas, (0, 1, 2))(x, w, b)
    l2, g2 = jax.value_and_grad(f_jnp, (0, 1, 2))(x, w, b)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    for got, want, name in zip(g1, g2, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


@pytest.mark.parametrize("act", ["none", "sigmoid", "silu"])
def test_expert_block_sparse_matmul_vs_vmap_oracle(act):
    """Expert-batched custom_vjp (grid (E, M/bm, nob/bn), shared pattern,
    per-expert weights + bias) vs a vmap of the jnp reference — ragged
    fan-out, non-multiple-of-bm rows."""
    from repro.core import sparse_linear as sl

    E, bs = 3, 32
    pat = _ragged_pattern(10 * bs, 6 * bs, 0.34, bs)
    idx, rob, rt, rc = (jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
                        jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    M = 45
    x = jax.random.normal(ks[0], (E, M, 10 * bs))
    w = jax.random.normal(ks[1], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                  bs, bs)) * 0.1
    b = jax.random.normal(ks[2], (E, 6 * bs)) * 0.3
    co = jax.random.normal(ks[3], (E, M, 6 * bs))

    def f_pallas(x, w, b):
        y = ops.junction_matmul(x, w, idx, rob, rt, rc, bias=b, act=act)
        return jnp.sum(y * co)

    def f_jnp(x, w, b):
        one = lambda x1, w1, b1: sl._with_act(
            sl.apply_jnp({"w": w1, "idx": idx, "b": b1}, x1), act)
        return jnp.sum(jax.vmap(one)(x, w, b) * co)

    l1, g1 = jax.value_and_grad(f_pallas, (0, 1, 2))(x, w, b)
    l2, g2 = jax.value_and_grad(f_jnp, (0, 1, 2))(x, w, b)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    for got, want, name in zip(g1, g2, ("dx", "dw", "db")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_expert_gated_matmul_vs_vmap_oracle():
    """Fused SwiGLU expert kernel — silu(x@wg) * (x@wi) in one pass, both
    branch grads through the fused two-branch dx/dw kernels — vs a vmap
    of the two-matmul jnp formula."""
    from repro.core import sparse_linear as sl

    E, bs = 3, 32
    pat = _ragged_pattern(10 * bs, 6 * bs, 0.34, bs)
    idx, rob, rt, rc = (jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
                        jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    M = 45
    x = jax.random.normal(ks[0], (E, M, 10 * bs))
    wg = jax.random.normal(ks[1], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                   bs, bs)) * 0.1
    wi = jax.random.normal(ks[2], (E, pat.n_out_blocks, pat.fan_in_blocks,
                                   bs, bs)) * 0.1
    co = jax.random.normal(ks[3], (E, M, 6 * bs))

    def f_pallas(x, wg, wi):
        h = ops.junction_matmul(x, wg, idx, rob, rt, rc, wi=wi)
        return jnp.sum(h * co)

    def f_jnp(x, wg, wi):
        def one(x1, g1, i1):
            g = sl.apply_jnp({"w": g1, "idx": idx}, x1)
            u = sl.apply_jnp({"w": i1, "idx": idx}, x1)
            return jax.nn.silu(g) * u
        return jnp.sum(jax.vmap(one)(x, wg, wi) * co)

    l1, g1 = jax.value_and_grad(f_pallas, (0, 1, 2))(x, wg, wi)
    l2, g2 = jax.value_and_grad(f_jnp, (0, 1, 2))(x, wg, wi)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    for got, want, name in zip(g1, g2, ("dx", "dwg", "dwi")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_single_junction_is_e1_wrapper_no_expert_family():
    """Acceptance: exactly one kernel family — no expert_-prefixed
    duplicate bodies survive in the kernel module; the E-generic kernels
    take the leading expert dim; ops exposes the one junction_matmul."""
    from repro.kernels import block_sparse_matmul as bsm

    dupes = [n for n in dir(bsm) if n.startswith("expert_")]
    assert not dupes, f"expert_* duplicate kernel family resurfaced: {dupes}"
    assert not hasattr(bsm, "EXPERT_TUNE_TABLE"), "second tune table resurfaced"
    assert callable(ops.junction_matmul)
    # no pre-unification custom_vjp core survives beside junction_matmul
    for n in ("_bsm_core", "_ebsm_core", "_egated_core"):
        assert not hasattr(ops, n), f"pre-unification custom_vjp {n} survives"


def test_gated_single_junction_e1_parity():
    """The fused SwiGLU gate through the E=1 squeeze path (a configuration
    the pre-unification engine could not express: gated was expert-only)
    matches the two-matmul jnp formula fwd + bwd."""
    from repro.core import sparse_linear as sl

    bs = 32
    pat = _ragged_pattern(10 * bs, 6 * bs, 0.34, bs)
    idx, rob, rt, rc = (jnp.asarray(pat.idx), jnp.asarray(pat.rev_ob),
                        jnp.asarray(pat.rev_t), jnp.asarray(pat.rev_cnt))
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    M = 45
    x = jax.random.normal(ks[0], (M, 10 * bs))
    wg = jax.random.normal(ks[1], (pat.n_out_blocks, pat.fan_in_blocks,
                                   bs, bs)) * 0.1
    wi = jax.random.normal(ks[2], (pat.n_out_blocks, pat.fan_in_blocks,
                                   bs, bs)) * 0.1
    co = jax.random.normal(ks[3], (M, 6 * bs))

    def f_pallas(x, wg, wi):
        return jnp.sum(ops.junction_matmul(x, wg, idx, rob, rt, rc, wi=wi) * co)

    def f_jnp(x, wg, wi):
        g = sl.apply_jnp({"w": wg, "idx": idx}, x)
        u = sl.apply_jnp({"w": wi, "idx": idx}, x)
        return jnp.sum(jax.nn.silu(g) * u * co)

    l1, g1 = jax.value_and_grad(f_pallas, (0, 1, 2))(x, wg, wi)
    l2, g2 = jax.value_and_grad(f_jnp, (0, 1, 2))(x, wg, wi)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-4)
    for got, want, name in zip(g1, g2, ("dx", "dwg", "dwi")):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=name)


def test_tune_table_key_migration():
    """Every TUNE_TABLE entry is keyed in the one schema (E, M, nob, kb,
    bs, n_weight_operands) and is what choose_tiles returns for that
    configuration."""
    from repro.kernels import block_sparse_matmul as bsm

    assert bsm.TUNE_TABLE
    for key, want in bsm.TUNE_TABLE.items():
        E, M, nob, kb, bs, n_w = key
        assert bsm.choose_tiles(M, nob, kb, bs, 8, 4, E=E,
                                n_weight_operands=n_w) == want, key


def test_dx_zero_fanout_rows_exact_zero():
    """A row block with rev_cnt == 0 (input block with zero fan-out under
    the reverse pattern) must produce exact-zero dx rows — even when the
    upstream gradient is non-finite (inf/nan) — rather than garbage from
    the (0, 0) sentinel bundles the padded reverse slots point at."""
    from repro.core.interleaver import reverse_block_pattern

    bs, nib, nob, kb = 8, 6, 2, 2
    # blocks 4 and 5 are referenced by no output block -> rev_cnt == 0
    idx_np = np.array([[0, 1], [2, 3]], np.int32)
    rev_ob, rev_t, rev_cnt = reverse_block_pattern(idx_np, nib)
    assert (rev_cnt == 0).sum() == 2
    idx, rob, rt, rc = (jnp.asarray(idx_np), jnp.asarray(rev_ob),
                        jnp.asarray(rev_t), jnp.asarray(rev_cnt))
    M = 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (M, nib * bs))
    w = jax.random.normal(ks[1], (nob, kb, bs, bs)) * 0.1
    b = jax.random.normal(ks[2], (nob * bs,))

    for act in ("none", "sigmoid", "silu"):
        f = lambda x: ops.junction_matmul(x, w, idx, rob, rt, rc,
                                          bias=b, act=act)
        _, vjp = jax.vjp(f, x)
        # non-finite upstream grad: 0 * inf = nan would leak through a
        # multiply-style mask — the where-mask must keep structural zeros
        dy_bad = jnp.full((M, nob * bs), jnp.inf)
        (dxv,) = vjp(dy_bad)
        dead = np.asarray(dxv).reshape(M, nib, bs)[:, rev_cnt == 0, :]
        np.testing.assert_array_equal(dead, 0.0, err_msg=f"act={act}")

    # gated configuration masks the same way
    wi = jax.random.normal(jax.random.PRNGKey(9), (nob, kb, bs, bs)) * 0.1
    _, vjp = jax.vjp(
        lambda x: ops.junction_matmul(x, w, idx, rob, rt, rc, wi=wi), x)
    (dxv,) = vjp(jnp.full((M, nob * bs), jnp.nan))
    dead = np.asarray(dxv).reshape(M, nib, bs)[:, rev_cnt == 0, :]
    np.testing.assert_array_equal(dead, 0.0)


def test_fused_forward_grid_bound():
    """Acceptance bound: the fused forward runs in exactly
    (M/bm) * ceil(nob/bn) grid steps — the kb reduction never appears as a
    grid dimension (the seed kernel's grid was (M/bm, nob, kb))."""
    from repro.kernels import block_sparse_matmul as bsm

    for (M, nob, kb, bs, nib) in [(256, 4, 2, 128, 8), (12544, 4, 2, 128, 8),
                                  (64, 10, 3, 32, 10), (4096, 32, 2, 128, 8)]:
        bm, bn = bsm.choose_tiles(M, nob, kb, bs, nib, 4)
        gm, gn = bsm.fwd_grid(M, nob, kb, bs, nib, 4)
        Mp = -(-M // bm) * bm
        assert gm * gn <= (Mp // bm) * (-(-nob // bn)), (M, nob, kb)
        assert nob % bn == 0 and gn == nob // bn
        assert bm % 16 == 0 and bm >= 16
