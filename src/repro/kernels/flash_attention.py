"""Pallas flash attention (beyond-paper) — the TPU drop-in for
models/attention.chunked_attention, plus the paged single-query decode
kernel the continuous-batching serve engine ticks through.

``flash_attention`` — online-softmax attention with the (m, l, acc)
running state in VMEM scratch: grid (B*H, Sq/bq, Sk/bk), KV blocks
innermost so one q-tile's state never leaves VMEM; scores/probability
tiles [bq, bk] are never written to HBM (the lax.scan version
materializes them per chunk — the same stage-materialization cost
structure the selective-scan kernel removes for SSMs).  GQA: the kv head
for grid row h is h // rep via the BlockSpec index maps — no repeated
K/V in memory.  Ragged Sq/Sk are padded to the tile internally (padded
query rows are sliced off, padded KV rows masked by an explicit
kpos < Sk term), mirroring the fxp_qmatmul pad-to-tile contract.

``flash_decode`` — the serve-path variant: one query per slot against a
block-paged KV pool.  The per-slot page table and sequence lengths ride
scalar prefetch exactly like the junction kernels' pattern indices; the
KV pool stays in HBM (memory_space=ANY).  A grid step covers a group of
G page-table entries (``decode_pages_per_step``, from the shapes), each
page gathered HBM→VMEM by its own ``make_async_copy`` into one
double-buffered group — the idiom of the reverse-weight DMA in
block_sparse_matmul.dx: while group g is reduced into the online-softmax
state, group g+1 is in flight.  Pages past a slot's length are never
read (matching-predicate start/wait per page), so a ragged batch does no
DMA for dead tail pages and a group wholly past the length does
nothing; a zero-length slot produces exact zeros.  Fixed shapes
throughout: slot refill and page-table swaps change only the prefetched
integers, never the traced graph.

Causal masking from absolute block offsets; fully-masked tiles contribute
exp(-inf)=0 naturally.  Validated against naive oracles over
(heads, GQA ratio, seq, window, ragged lengths) sweeps in interpret mode.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(nk: int, scale: float, causal: bool, window: int, kv_len: int,
            q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0].astype(jnp.float32)                  # [bq, D]
    k = k_ref[0].astype(jnp.float32)                  # [bk, D]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [bq, bk]

    bq, bk = s.shape
    qpos = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = kb * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # ragged Sk: tile-padded key rows carry garbage — mask them for every
    # mode (the causal term only covers them when qpos < kpos)
    mask = kpos < kv_len
    if causal:
        mask = mask & (qpos >= kpos)
    if window:
        mask = mask & (qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_s[...], l_s[...], acc_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot(p, v)
    m_s[...], l_s[...], acc_s[...] = m_new, l_new, acc_new

    @pl.when(kb == nk - 1)
    def _finish():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def _pad_dim(x, axis, to):
    if x.shape[axis] == to:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, to - x.shape[axis])
    return jnp.pad(x, pad)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q [BH, Sq, D]; k, v [BHkv, Sk, D] with BH % BHkv == 0 (GQA).
    Returns [BH, Sq, D].  Ragged Sq/Sk are padded to the tile internally:
    padded query rows are computed and sliced off, padded key rows are
    masked inside the kernel (kpos < Sk), so callers never need
    tile-multiple sequence lengths."""
    BH, Sq, D = q.shape
    BHkv, Sk, _ = k.shape
    assert BH % BHkv == 0
    rep = BH // BHkv
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    sq_p = pl.cdiv(Sq, bq) * bq
    sk_p = pl.cdiv(Sk, bk) * bk
    q = _pad_dim(q, 1, sq_p)
    k = _pad_dim(k, 1, sk_p)
    v = _pad_dim(v, 1, sk_p)
    grid = (BH, sq_p // bq, sk_p // bk)
    scale = float(1.0 / (D ** 0.5))
    out = pl.pallas_call(
        functools.partial(_kernel, sk_p // bk, scale, causal, window, Sk),
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h // rep, j, 0)),
            pl.BlockSpec((1, bk, D), lambda h, i, j: (h // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, sq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max
            pltpu.VMEM((bq,), jnp.float32),       # running denominator
            pltpu.VMEM((bq, D), jnp.float32),     # weighted accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq] if sq_p != Sq else out


def mha(q, k, v, *, causal: bool = True, window: int = 0,
        interpret: bool = False, **kw):
    """Convenience wrapper: q [B,Sq,H,D], k/v [B,Sk,Hkv,D] -> [B,Sq,H,D]."""
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Sk, D)
    o = flash_attention(qf, kf, vf, causal=causal, window=window,
                        interpret=interpret, **kw)
    return o.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)


# ===================================================== paged decode kernel
DECODE_GROUP_BYTES = 1 << 20    # K bytes a decode grid step reads, at most


def decode_pages_per_step(ps: int, hd: int, itemsize: int, maxp: int) -> int:
    """Pages of the slot's page table ``flash_decode`` reads per grid step:
    the largest power of two whose K block stays within
    ``DECODE_GROUP_BYTES`` (so a group holds 0.5-1 MiB of K, and the
    double-buffered K and V at most 4 MiB of VMEM), clamped to
    [1, maxp].  Pages of 16 rows of stablelm-3b's 32 x 80 bf16 heads
    (80 KiB) read 8 a step; narrower rows or smaller pages read more."""
    page = ps * hd * itemsize
    g = 1
    while 2 * g * page <= DECODE_GROUP_BYTES:
        g *= 2
    return min(g, maxp)


def _head_mask(hkv: int, hd: int):
    """[Hkv, Hkv*D] bool: row h marks the lanes of kv head h in the merged
    heads x head_dim row."""
    d = hd // hkv
    head = jax.lax.broadcasted_iota(jnp.int32, (hkv, hd), 0) * d
    lane = jax.lax.broadcasted_iota(jnp.int32, (hkv, hd), 1)
    return jnp.logical_and(lane >= head, lane < head + d)


def _decode_kernel(maxp: int, ps: int, G: int, hkv: int, scale: float,
                   pt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, qbd_s, m_s, l_s, acc_s):
    """One slot (grid axis 0) against its page groups (axis 1): group g
    is the G page-table entries from g*G, each page DMA'd into its ps
    rows of one [G*ps, Hkv*D] buffer.  Every query row r and kv head h
    is one row r*Hkv + h of the softmax state; the queries are held
    block-diagonally (row r*Hkv + h keeps query r's head-h lanes and
    zeros elsewhere), so a group's scores for all heads are one matmul
    against the merged rows and no lane slice at a head boundary (80
    lanes for stablelm) ever happens."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    ng = pl.num_programs(1)
    n = len_ref[b]
    rep, hd = q_ref.shape[1], q_ref.shape[2]
    cd = qbd_s.dtype
    # bf16 operands: one MXU pass multiplies exactly and accumulates in
    # f32; f32 operands ask for f32 contractions
    prec = (jax.lax.Precision.HIGHEST if cd == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def live(page):
        # a page is read only if it holds one of the slot's tokens: no
        # DMA past the length, and never the sentinel page of a tail
        return jnp.logical_and(page < maxp, page * ps < n)

    def copies(buf, page, i):
        pid = pt_ref[b, page]
        rows = pl.ds(i * ps, ps)
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[buf, rows],
                                      sems.at[buf, 0]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[buf, rows],
                                      sems.at[buf, 1]))

    def start(buf, group):
        for i in range(G):
            page = group * G + i

            @pl.when(live(page))
            def _():
                for c in copies(buf, page, i):
                    c.start()

    @pl.when(g == 0)
    def _init():
        q = q_ref[0].astype(jnp.float32)                       # [rep, hd]
        qbd = jnp.where(_head_mask(hkv, hd)[None], q[:, None, :], 0.0)
        qbd_s[...] = qbd.reshape(rep * hkv, hd).astype(cd)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)
        start(0, 0)

    # prefetch group g+1 while group g is reduced; each page's predicate
    # matches its wait below, so a page that is not read touches no
    # semaphore
    @pl.when(g + 1 < ng)
    def _next():
        start((g + 1) % 2, g + 1)

    @pl.when(g * (G * ps) < n)
    def _compute():
        buf = g % 2
        for i in range(G):
            page = g * G + i

            @pl.when(live(page))
            def _():
                for c in copies(buf, page, i):
                    c.wait()

            # a page not read leaves stale rows (another slot's, or
            # uninitialised VMEM) in the buffer: its scores are masked
            # below, and its values are zeroed so that 0 * NaN never
            # reaches the accumulator
            @pl.when(jnp.logical_not(live(page)))
            def _():
                vbuf[buf, pl.ds(i * ps, ps)] = jnp.zeros((ps, hd),
                                                         vbuf.dtype)

        kp = kbuf[buf].astype(cd)                              # [G*ps, hd]
        vp = vbuf[buf].astype(cd)
        s = jax.lax.dot_general(qbd_s[...], kp, (((1,), (1,)), ((), ())),
                                precision=prec,
                                preferred_element_type=jnp.float32) * scale
        kpos = g * (G * ps) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < n, s, NEG_INF)                    # [R, G*ps]
        m_prev = m_s[...]                                      # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        m_s[...] = m_new
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot(
            p.astype(cd), vp, precision=prec,
            preferred_element_type=jnp.float32)               # [R, hd]

    @pl.when(g == ng - 1)
    def _finish():
        # row r*Hkv + h holds head h's output on head h's lanes
        out = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)).reshape(
            rep, hkv, hd)
        o_ref[0] = jnp.sum(jnp.where(_head_mask(hkv, hd)[None], out, 0.0),
                           axis=1).astype(o_ref.dtype)


def flash_decode(q, k_pool, v_pool, page_table, seq_lens, *,
                 pages_per_step: int | None = None,
                 interpret: bool | None = None):
    """Single-query decode attention over a block-paged KV pool.

    q [B, Hkv, rep, D] — one query token per slot, grouped by kv head;
    k_pool / v_pool [P, ps, Hkv*D] — the page pool (the model's paged
    paths pass every layer's pages viewed flat, page ids offset to one
    layer's), heads x head_dim merged on the lane axis so a page row is a
    multiple of 128 lanes at any head_dim (stablelm's 80 included);
    page_table [B, maxp] int32 — pool page ids per slot, in token order
    (entry t covers positions [t*ps, (t+1)*ps));
    seq_lens [B] int32 — valid tokens per slot (0 for free slots).

    Returns [B, Hkv, rep, D].  The page table and lengths ride scalar
    prefetch; the grid is (B, ceil(maxp / G)), each step reading a group
    of G pages (``decode_pages_per_step`` from the shapes unless
    ``pages_per_step`` pins it) by one DMA per page HBM→VMEM,
    double-buffered, with pages past a slot's length never read.
    seq_lens == 0 yields exact zeros.
    """
    B, Hkv, rep, D = q.shape
    P, ps, hd = k_pool.shape
    assert hd == Hkv * D, (k_pool.shape, q.shape)
    maxp = page_table.shape[1]
    G = pages_per_step or decode_pages_per_step(
        ps, hd, k_pool.dtype.itemsize, maxp)
    assert 1 <= G <= maxp, (G, maxp)
    if interpret is None:
        from repro.kernels import ops
        interpret = ops._auto_interpret()
    scale = float(1.0 / (D ** 0.5))
    R = rep * Hkv
    cd = jnp.promote_types(q.dtype, k_pool.dtype)
    # the query in the pool's merged layout: [B, rep, Hkv*D]
    qm = q.transpose(0, 2, 1, 3).reshape(B, rep, hd)
    # profiler attribution (same convention as ops._kernel_scope): the
    # decode-tick hot kernel shows up named, not as an anonymous
    # pallas_call, in a --profile trace
    with jax.named_scope(f"flash_decode_B{B}_H{Hkv}x{rep}_ps{ps}"):
        out = pl.pallas_call(
            functools.partial(_decode_kernel, maxp, ps, G, Hkv, scale),
            name="flash_decode",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(B, pl.cdiv(maxp, G)),
                in_specs=[
                    pl.BlockSpec((1, rep, hd), lambda b, g, *_: (b, 0, 0)),
                    pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY),
                ],
                out_specs=pl.BlockSpec((1, rep, hd),
                                       lambda b, g, *_: (b, 0, 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, G * ps, hd), k_pool.dtype),  # k groups
                    pltpu.VMEM((2, G * ps, hd), v_pool.dtype),  # v groups
                    pltpu.SemaphoreType.DMA((2, 2)),
                    pltpu.VMEM((R, hd), cd),                    # queries
                    pltpu.VMEM((R, 1), jnp.float32),            # running max
                    pltpu.VMEM((R, 1), jnp.float32),            # running denom
                    pltpu.VMEM((R, hd), jnp.float32),           # weighted acc
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((B, rep, hd), q.dtype),
            interpret=interpret,
        )(page_table, seq_lens, qm, k_pool, v_pool)
    return out.reshape(B, rep, Hkv, D).transpose(0, 2, 1, 3)


def paged_decode_ref(q, k_pool, v_pool, page_table, seq_lens):
    """jnp oracle for flash_decode (also the serve engine's jnp path):
    gather the slot's pages, monolithic masked softmax in fp32.  Same
    shapes/contract as flash_decode (merged [P, ps, Hkv*D] pools)."""
    B, Hkv, rep, D = q.shape
    ps = k_pool.shape[1]
    maxp = page_table.shape[1]
    kg = k_pool[page_table].reshape(B, maxp * ps, Hkv, D)
    vg = v_pool[page_table].reshape(B, maxp * ps, Hkv, D)
    scale = 1.0 / (D ** 0.5)
    s = jnp.einsum("bgrd,bkgd->bgrk", q.astype(jnp.float32),
                   kg.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * scale
    valid = jnp.arange(maxp * ps)[None, :] < seq_lens[:, None]     # [B, K]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bgrk,bkgd->bgrd", p / jnp.maximum(l, 1e-30),
                     vg.astype(jnp.float32))
    out = jnp.where((seq_lens > 0)[:, None, None, None], out, 0.0)
    return out.astype(q.dtype)
