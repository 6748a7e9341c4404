"""Model-layer correctness: attention variants, SSM scans, MoE."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import ssm as S
from repro.models import moe as MoE


def naive_attention(q, k, v, causal=True, window=0):
    B, Sq, H, D = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    rep = H // Hkv
    kf = jnp.repeat(k, rep, axis=2)
    vf = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kf) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), bool), k=Sk - Sq)
        s = jnp.where(mask[None, None], s, -1e30)
    if window:
        qp = jnp.arange(Sq)[:, None] + (Sk - Sq)
        kp = jnp.arange(Sk)[None, :]
        s = jnp.where((qp - kp < window)[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, vf)


@pytest.mark.parametrize("H,Hkv,window", [(4, 4, 0), (8, 2, 0), (4, 2, 7)])
def test_chunked_attention_vs_naive(H, Hkv, window):
    key = jax.random.PRNGKey(0)
    B, Sq, D = 2, 33, 16
    q = jax.random.normal(key, (B, Sq, H, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, Sq, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, Sq, Hkv, D))
    got = A.chunked_attention(q, k, v, causal=True, window=window, chunk=8)
    want = naive_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_decode_attention_matches_full_recompute():
    key = jax.random.PRNGKey(0)
    B, S, H, Hkv, D = 2, 16, 4, 2, 8
    k = jax.random.normal(key, (B, S, Hkv, D))
    v = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
    q = jax.random.normal(jax.random.PRNGKey(2), (B, 1, H, D))
    pos = 9   # cache positions 0..9 valid
    got = A.decode_attention(q, k, v, jnp.asarray(pos))
    want = naive_attention(q, k[:, :pos + 1], v[:, :pos + 1], causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_gqa_prefill_then_decode_consistent():
    """Decoding token t with the prefill cache == prefilling t+1 tokens."""
    cfg = registry.get("llava-next-mistral-7b").reduced()
    key = jax.random.PRNGKey(0)
    p = A.attn_init(key, cfg)
    B, S = 2, 12
    x = jax.random.normal(key, (B, S + 1, cfg.d_model), jnp.float32)
    full, _ = A.gqa_forward(p, x, cfg, positions=jnp.arange(S + 1))
    # prefill on first S tokens
    _, (k, v) = A.gqa_forward(p, x[:, :S], cfg, positions=jnp.arange(S))
    W = min(cfg.window, S + 8) if cfg.attn_kind == "sliding" else S + 8
    cache = {"k": jnp.zeros((B, W, cfg.kv_heads, cfg.head_dim)),
             "v": jnp.zeros((B, W, cfg.kv_heads, cfg.head_dim))}
    if cfg.attn_kind == "sliding":
        sl = jnp.arange(S) % W
        cache = {"k": cache["k"].at[:, sl].set(k), "v": cache["v"].at[:, sl].set(v)}
    else:
        cache = {"k": cache["k"].at[:, :S].set(k), "v": cache["v"].at[:, :S].set(v)}
    out, _ = A.gqa_decode(p, x[:, S:S + 1], cfg, cache, jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, S]),
                               rtol=2e-3, atol=2e-3)


def test_mla_decode_absorbed_matches_expanded():
    cfg = registry.get("deepseek-v2-lite-16b").reduced()
    key = jax.random.PRNGKey(0)
    p = A.attn_init(key, cfg)
    B, S = 2, 9
    x = jax.random.normal(key, (B, S + 1, cfg.d_model), jnp.float32)
    full, (latent, k_rope) = A.mla_forward(p, x, cfg, positions=jnp.arange(S + 1))
    m = cfg.mla
    cache = {"latent": jnp.zeros((B, S + 4, m.kv_lora_rank)),
             "k_rope": jnp.zeros((B, S + 4, m.qk_rope_head_dim))}
    cache["latent"] = cache["latent"].at[:, :S].set(latent[:, :S])
    cache["k_rope"] = cache["k_rope"].at[:, :S].set(k_rope[:, :S])
    out, _ = A.mla_decode(p, x[:, S:S + 1], cfg, cache, jnp.asarray(S))
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, S]),
                               rtol=3e-3, atol=3e-3)


def _naive_mamba1(p, x, cfg):
    """Step-by-step recurrence oracle."""
    import repro.core.sparse_linear as sl
    B, S, _ = x.shape
    di, N, R = cfg.d_inner_, cfg.ssm_state, cfg.dt_rank_
    h = jnp.zeros((B, di, N))
    conv = jnp.zeros((B, cfg.conv_width - 1, di))
    ys = []
    for t in range(S):
        y, cache = S_mod_apply_one(p, x[:, t:t+1], cfg, {"conv": conv, "ssm": h})
        conv, h = cache["conv"], cache["ssm"]
        ys.append(y)
    return jnp.concatenate(ys, axis=1)


def S_mod_apply_one(p, xt, cfg, cache):
    return S.mamba1_apply(p, xt, cfg, cache=cache, decode=True)


def test_mamba1_chunked_scan_matches_stepwise():
    cfg = registry.get("falcon-mamba-7b").reduced()
    key = jax.random.PRNGKey(0)
    p = S.mamba1_init(key, cfg)
    B, Sq = 2, 32
    x = jax.random.normal(key, (B, Sq, cfg.d_model), jnp.float32)
    y_chunked, cache = S.mamba1_apply(
        p, x, cfg, cache={"conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_inner_)),
                          "ssm": jnp.zeros((B, cfg.d_inner_, cfg.ssm_state))})
    y_naive = _naive_mamba1(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_chunked), np.asarray(y_naive),
                               rtol=2e-3, atol=2e-3)


def test_mamba2_ssd_matches_stepwise():
    cfg = registry.get("zamba2-2.7b").reduced()
    key = jax.random.PRNGKey(0)
    p = S.mamba2_init(key, cfg)
    B, Sq = 2, 32
    x = jax.random.normal(key, (B, Sq, cfg.d_model), jnp.float32)
    zero = {"conv": jnp.zeros((B, cfg.conv_width - 1, cfg.d_inner_ + 2 * cfg.ssm_state)),
            "ssm": jnp.zeros((B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state))}
    y_ssd, _ = S.mamba2_apply(p, x, cfg, cache=zero)
    conv, h = zero["conv"], zero["ssm"]
    ys = []
    for t in range(Sq):
        y, c2 = S.mamba2_apply(p, x[:, t:t + 1], cfg,
                               cache={"conv": conv, "ssm": h}, decode=True)
        conv, h = c2["conv"], c2["ssm"]
        ys.append(y)
    y_naive = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_ssd), np.asarray(y_naive),
                               rtol=3e-3, atol=3e-3)


def test_moe_routing_properties():
    cfg = registry.get("qwen3-moe-30b-a3b").reduced()
    key = jax.random.PRNGKey(0)
    p = MoE.moe_init(key, cfg)
    x = jax.random.normal(key, (2, 64, cfg.d_model), jnp.float32)
    y, aux, _ = MoE.moe_apply(p, x, cfg)
    assert y.shape == x.shape
    assert jnp.all(jnp.isfinite(y))
    # aux loss near its uniform-routing value (E * sum f*p ~ 1) * weight
    assert 0.0 < float(aux) < 10 * cfg.moe.aux_loss_weight


@pytest.mark.parametrize("skew", [0.0, 4.0, 1e3],
                         ids=["uniform", "skewed", "one-expert"])
def test_moe_drops_nothing(skew):
    """Whatever the routing's skew, every token-slot lands in its expert:
    each token's output is the weighted sum of its k experts computed on
    their own (one expert at a time, on that token alone), and the
    counters say so."""
    cfg = registry.get("deepseek-v2-lite-16b").reduced()
    p = MoE.moe_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 64, cfg.d_model)) + 1.0
    p = dict(p, router=p["router"].at[:, 0].add(skew / cfg.d_model))
    y, _, st = MoE.moe_apply(p, x, cfg)
    xt = x.reshape(-1, cfg.d_model)
    _, w, e = MoE.route(p, xt, cfg)
    want = MoE.mlp_apply(p["shared"], x, cfg).reshape(xt.shape)
    for k in range(cfg.moe.top_k):
        wg, wi, wo = (p[n][e[:, k]] for n in ("wg", "wi", "wo"))
        h = (jax.nn.silu(jnp.einsum("td,tdf->tf", xt, wg))
             * jnp.einsum("td,tdf->tf", xt, wi))
        want = want + w[:, k:k + 1] * jnp.einsum("tf,tfd->td", h, wo)
    np.testing.assert_allclose(np.asarray(y.reshape(xt.shape)),
                               np.asarray(want), rtol=1e-4, atol=1e-5)
    T, K = xt.shape[0], cfg.moe.top_k
    assert int(st["moe_routed_rows"]) == T * K
    assert int(st["moe_dropped_rows"]) == 0
    if skew > 100:   # every token's first choice is expert 0
        assert int(st["moe_max_expert_rows"]) == T
