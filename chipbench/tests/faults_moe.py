"""Faults planted in the expert-layer program, for the tests that see the
MoE training cell's run come out not correct.  Each takes the program
module it patches and returns the replacement for one of its functions;
the test installs it with ``monkeypatch`` before the step is traced."""
from __future__ import annotations


def topk_renormalised(moe_mod):
    """The router renormalises its kept top-k weights (DeepSeek-V2-Lite
    sets ``norm_topk_prob`` false)."""
    real = moe_mod.route

    def route(p, x, cfg):
        probs, w, e = real(p, x, cfg)
        return probs, w / w.sum(axis=-1, keepdims=True), e
    return route


def yarn_mscale_left_out(attention):
    """Latent attention scores scaled by 1/sqrt(qk dim) alone, without
    YaRN's mscale^2."""
    real = attention.mla_rope

    def mla_rope(cfg):
        inv, _ = real(cfg)
        m = cfg.mla
        return inv, (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    return mla_rope


def experts_at_wrong_offset(moe_mod):
    """The layer computes the experts one index past those it holds the
    weights of."""
    real = moe_mod.dispatch_index

    def dispatch_index(top_e, cfg, T_buf):
        return real(top_e - 1, cfg, T_buf)
    return dispatch_index


def _keep_idle_units(new, old, counts):
    import jax.numpy as jnp
    if new is None or old is None:
        return new
    idle = (counts == 0).reshape((-1,) + (1,) * (new.ndim - 1))
    return jnp.where(idle, old.astype(new.dtype), new)


def update_skipped_without_rows(bsm):
    """The fused update kernels leave a unit with no live rows as it was
    (no Adam step from its moments).  Returns the two replacements
    (update_dw, update_gated_dw)."""
    real_dw, real_gated = bsm.update_dw, bsm.update_gated_dw

    def update_dw(x, dy, idx, res, w, b, mom, mom_b, hyp, *, vel=None,
                  counts=None, **kw):
        out = real_dw(x, dy, idx, res, w, b, mom, mom_b, hyp, vel=vel,
                      counts=counts, **kw)
        if counts is None:
            return out
        nw, nb, nm, nmb, nv, nvb, health = out
        return (_keep_idle_units(nw, w, counts), nb,
                _keep_idle_units(nm, mom, counts), nmb,
                _keep_idle_units(nv, vel, counts), nvb, health)

    def update_gated_dw(x, dh, idx, g, u, wg, wi, mg, mi, hyp, *, vg=None,
                        vi=None, counts=None, **kw):
        out = real_gated(x, dh, idx, g, u, wg, wi, mg, mi, hyp, vg=vg, vi=vi,
                         counts=counts, **kw)
        if counts is None:
            return out
        olds = (wg, wi, mg, mi, vg, vi)
        return tuple(_keep_idle_units(n, o, counts)
                     for n, o in zip(out[:6], olds)) + (out[6],)

    return update_dw, update_gated_dw
