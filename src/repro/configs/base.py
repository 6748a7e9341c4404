"""Architecture / run configuration dataclasses.

Every assigned architecture gets one file in this package exporting
``CONFIG: ArchConfig``; ``registry.get(name)`` resolves them.  Reduced
variants for CPU smoke tests come from ``ArchConfig.reduced()``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from repro.core.sparsity import SparsityConfig


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """The expert layer (models/moe.py).  The router always scores all
    ``num_experts`` and keeps ``top_k``; this device computes the experts
    ``first_held .. first_held + held - 1`` (``held`` 0: all of them), the
    share one chip of an expert-parallel deployment holds, and drops no
    token."""
    num_experts: int              # router width (experts in the layer)
    top_k: int
    d_expert: int                 # per-expert FFN hidden width
    num_shared: int = 0           # shared (always-on) experts
    d_shared: int = 0             # hidden width of the shared expert block
    aux_loss_weight: float = 1e-2
    # "batch": Switch/GShard E * sum_e f_e * p_e over all tokens;
    # "sequence": DeepSeek-V2's seq_aux, the same per sequence, averaged
    aux_loss: str = "batch"
    norm_topk_prob: bool = True   # renormalise the kept top-k weights
    routed_scale: float = 1.0     # routed_scaling_factor
    held: int = 0                 # experts computed here (0: all)
    first_held: int = 0           # index of the first held expert
    first_dense_layers: int = 0   # deepseek-v2: layer 0 is a dense FFN

    @property
    def held_(self) -> int:
        return self.held or self.num_experts


@dataclasses.dataclass(frozen=True)
class RopeScaling:
    """YaRN rotary scaling (DeepSeek-V2's ``rope_scaling`` of type yarn)."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | ssm | hybrid | moe | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int                    # padded to shardable multiple
    raw_vocab: int = 0
    # attention
    attn_kind: str = "full"       # full | sliding | mla | none
    window: int = 0               # sliding window size
    qkv_bias: bool = False
    partial_rotary: float = 1.0   # fraction of head_dim rotated (stablelm 0.25)
    rope_theta: float = 1e6
    rope_scaling: Optional[RopeScaling] = None   # YaRN (MLA only)
    mla: Optional[MLAConfig] = None
    # ssm
    ssm_kind: str = ""            # mamba1 | mamba2
    ssm_state: int = 0
    d_inner: int = 0
    conv_width: int = 4
    ssm_head_dim: int = 64        # mamba2
    dt_rank: int = 0              # mamba1 (0 -> ceil(d_model/16))
    # hybrid (zamba2): shared attention block every k ssm layers
    hybrid_attn_every: int = 0
    # moe
    moe: Optional[MoEConfig] = None
    # enc-dec (whisper): encoder layers + stub frame count
    enc_layers: int = 0
    enc_frames: int = 0
    # vlm (llava): stub patch count
    num_patches: int = 0
    # misc
    act: str = "silu"
    norm: str = "rmsnorm"         # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    max_seq: int = 8192           # decode position-table bound (pos-emb archs)
    # distribution: "tp" (tensor parallel) or "sp" (sequence parallel —
    # small models whose head counts don't divide the model axis)
    strategy: str = "tp"
    # cast fp32 master params to bf16 once per step (outside the layer scan)
    # so FSDP all-gathers move bf16, not fp32 — perf knob, see §Perf
    cast_params_once: bool = False
    # compute the CE loss in sequence chunks of this many tokens (0 = off):
    # the [tokens, vocab] logits tensor never fully materializes — perf knob
    loss_chunk: int = 0
    # dtype of the selective-scan associative elements ([B,c,d_inner,N]
    # decay/input tensors): bf16 halves the dominant HBM traffic of SSM
    # training; the inter-chunk carry stays fp32 — perf knob, see §Perf
    ssm_scan_dtype: str = "float32"
    # numerics / memory
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    attn_chunk: int = 1024        # online-softmax kv chunk
    ssm_chunk: int = 128          # selective-scan chunk
    # the paper's technique
    sparsity: Optional[SparsityConfig] = None
    # execution engine for pre-defined-sparse linears:
    #   "pallas" — fused edge-bundle Pallas kernels (TPU; interpret off-TPU)
    #   "jnp"    — gather+einsum fallback (dry-run FLOP accounting, CPU)
    #   "auto"   — pallas on TPU backends, jnp elsewhere (default)
    # resolved once at step-build time (train/steps.py, serve/engine.py)
    engine: str = "auto"
    # fused BP+UP: apply the optimizer update to pre-defined-sparse
    # junction weights INSIDE the backward kernels (the paper's concurrent
    # update stage) so weight gradients never materialize in HBM —
    # SGD+momentum or Adam, per the FusedOptimizer's [E, HYP_K] hyp row
    # (grad clipping folds into the gs column via a norm pre-pass;
    # microbatches>1 runs as the full batch).  Takes effect only when
    # train/steps.py resolves the step as eligible (pallas engine, an
    # optim.FusedOptimizer — fused_sgd / fused_adam — and
    # param_dtype == dtype); otherwise — and always for the jnp engine
    # and launch/dryrun.py — the two-pass reference path runs.
    fused_update: bool = False

    # ---------------------------------------------------------------- helpers
    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def dt_rank_(self) -> int:
        return self.dt_rank or -(-self.d_model // 16)

    @property
    def d_inner_(self) -> int:
        return self.d_inner or 2 * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner_ // self.ssm_head_dim

    def with_sparsity(self, sp: SparsityConfig) -> "ArchConfig":
        return dataclasses.replace(self, sparsity=sp)

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw = dict(
            n_layers=min(self.n_layers, 2),
            d_model=128,
            n_heads=4,
            kv_heads=min(self.kv_heads, 4) if self.kv_heads >= self.n_heads else 2,
            head_dim=32,
            d_ff=256,
            vocab=256,
            raw_vocab=256,
            d_inner=256,
            dt_rank=8,
            ssm_head_dim=32,
            enc_layers=min(self.enc_layers, 2),
            enc_frames=16 if self.enc_frames else 0,
            num_patches=8 if self.num_patches else 0,
            window=min(self.window, 64) if self.window else 0,
            max_seq=512,
            attn_chunk=32,
            ssm_chunk=16,
            hybrid_attn_every=2 if self.hybrid_attn_every else 0,
        )
        if self.moe is not None:
            kw["moe"] = dataclasses.replace(
                self.moe, num_experts=8, top_k=2, d_expert=64,
                d_shared=64 if self.moe.num_shared else 0,
                held=min(self.moe.held, 8),
                first_held=min(self.moe.first_held, 8 - min(self.moe.held, 8)))
        if self.mla is not None:
            kw["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=32,
                                  qk_rope_head_dim=16, v_head_dim=32)
        if self.sparsity is not None:
            kw["sparsity"] = dataclasses.replace(self.sparsity, block=32)
        return dataclasses.replace(self, **kw)

    def param_count(self) -> int:
        """Analytic parameter count N for MODEL_FLOPS = 6*N*D."""
        d, f, L, V = self.d_model, self.d_ff, self.n_layers, self.vocab
        emb = V * d * (1 if self.tie_embeddings else 2)
        per_layer = 0
        if self.family in ("dense", "vlm", "moe") or self.attn_kind != "none":
            if self.attn_kind == "mla":
                m = self.mla
                qd = self.n_heads * (m.qk_nope_head_dim + m.qk_rope_head_dim)
                per_attn = (d * qd + d * (m.kv_lora_rank + m.qk_rope_head_dim)
                            + m.kv_lora_rank * self.n_heads * (m.qk_nope_head_dim + m.v_head_dim)
                            + self.n_heads * m.v_head_dim * d)
            else:
                per_attn = (d * self.n_heads * self.head_dim
                            + 2 * d * self.kv_heads * self.head_dim
                            + self.n_heads * self.head_dim * d)
        else:
            per_attn = 0
        gated = 3 if self.act == "silu" else 2
        if self.family == "moe":
            mo = self.moe
            ffn = mo.held_ * gated * d * mo.d_expert
            if mo.num_shared:
                ffn += gated * d * mo.d_shared
            per_layer = per_attn + ffn
        elif self.family in ("ssm", "hybrid"):
            di, N = self.d_inner_, self.ssm_state
            if self.ssm_kind == "mamba1":
                ssm = (d * 2 * di + self.conv_width * di
                       + di * (self.dt_rank_ + 2 * N) + self.dt_rank_ * di
                       + di * N + di + di * d)
            else:  # mamba2
                H = self.ssm_heads
                ssm = (d * (2 * di + 2 * N + H) + self.conv_width * (di + 2 * N)
                       + H + di + di * d)
            per_layer = ssm
            if self.family == "hybrid":
                # shared attention block params amortized once, added below
                pass
        else:
            per_layer = per_attn + gated * d * f
        total = emb + L * per_layer
        if self.family == "hybrid" and self.hybrid_attn_every:
            total += (d * self.n_heads * self.head_dim * 2
                      + 2 * d * self.kv_heads * self.head_dim
                      + gated * d * self.d_ff)
        if self.family == "audio":
            # encoder layers (self-attn + mlp) + decoder cross-attn
            total += self.enc_layers * (4 * d * d + 2 * d * f)
            total += self.n_layers * 4 * d * d  # cross-attn per decoder layer
        if self.family == "moe" and self.moe.first_dense_layers:
            total += self.moe.first_dense_layers * (gated * d * f - self.moe.held_ * gated * d * self.moe.d_expert)
        return int(total)

    def active_param_count(self) -> int:
        """N_active for MoE MODEL_FLOPS."""
        if self.family != "moe":
            return self.param_count()
        mo = self.moe
        d, L = self.d_model, self.n_layers
        gated = 3
        full = self.param_count()
        all_experts = L * mo.held_ * gated * d * mo.d_expert
        active = L * mo.top_k * gated * d * mo.d_expert
        return int(full - all_experts + active)


@dataclasses.dataclass(frozen=True)
class SweepConfig:
    """Population-search run configuration (src/repro/search/): the
    paper's resource-vs-training-time trade as user-facing knobs —
    cohort size E comes from the candidate list, this fixes the rounds
    side (successive halving) and the execution engine.

    rounds: successive-halving rounds; after each, the live population
        is ranked by eval loss and pruned to keep_fraction (pruned slots
        are masked + hyp-zeroed in place — fixed shapes, no recompiles).
    steps_per_round: fused E-batched train steps between prunes.
    batch_size / eval_samples: shared-data minibatch and held-out sizes.
    engine: "pallas" | "jnp" | "auto" (resolved once at step build);
        fused applies only on the pallas engine.
    quarantine: trip-wire fault isolation: a member whose loss or
        in-kernel health flag goes non-finite is masked + hyp-zeroed
        MID-round (the prune mechanism applied immediately) and recorded
        in the ledger, so sweeping lr×density into the divergent regime
        cannot poison the rest of the cohort's run.
    """
    rounds: int = 3
    steps_per_round: int = 20
    batch_size: int = 128
    eval_samples: int = 512
    keep_fraction: float = 0.5
    seed: int = 0
    engine: str = "auto"
    fused: bool = True
    quarantine: bool = True


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                     # train | prefill | decode

    @property
    def tokens(self) -> int:
        return self.seq_len * self.global_batch


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def long_context_ok(cfg: ArchConfig) -> bool:
    """long_500k runs only for sub-quadratic attention (DESIGN.md Sec. 4)."""
    return (cfg.family in ("ssm", "hybrid")
            or cfg.attn_kind == "sliding")


def valid_cells(cfg: ArchConfig):
    for s in SHAPES.values():
        if s.name == "long_500k" and not long_context_ok(cfg):
            continue
        yield s
