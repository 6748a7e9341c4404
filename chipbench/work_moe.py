"""Operations and bytes of a latent-attention decoder with expert layers
(DeepSeek-V2's block), from shapes alone, as ``work.py`` counts them for
the dense decoder: what the algorithm needs at the configured sparsity,
never recomputation (rematerialisation, the grad-clip pre-pass) and
never padding.  Routed-expert work is counted on the rows routed to the
held experts (the program's ``moe_routed_rows`` counter), not on the
rows its kernels cover.

A multiply-add is two operations.
"""
from __future__ import annotations

import dataclasses

from chipbench.work import Junction, junction  # noqa: F401  (re-exported)


@dataclasses.dataclass(frozen=True)
class MoEShape:
    """``layers`` decoder layers, the first ``dense_layers`` with a dense
    SwiGLU of width ``dense_ffn``, the rest expert layers: a router of
    ``router`` outputs (top ``top_k``), the held experts' junctions
    ``expert`` (gate, up, down) and the shared experts' ``shared``.
    Attention is MLA without q-LoRA: q at ``q_dim`` per head (of which
    ``rope_dim`` rotary), a latent of ``kv_lora`` expanded to keys and
    values (``v_dim``) per head."""
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    q_dim: int
    rope_dim: int
    v_dim: int
    kv_lora: int
    dense_ffn: int
    router: int
    top_k: int
    held: int
    expert: tuple[Junction, ...]
    shared: tuple[Junction, ...]
    vocab: int

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    def mla_proj_flops_per_token(self) -> float:
        """q, kv_a (latent and the rotary key), kv_b (keys' no-rope part
        and values from the latent) and the output projection."""
        d, H = self.d_model, self.heads
        nope = self.q_dim - self.rope_dim
        return 2.0 * (d * H * self.q_dim + d * (self.kv_lora + self.rope_dim)
                      + self.kv_lora * H * (nope + self.v_dim)
                      + H * self.v_dim * d)

    def causal_score_flops(self, seq: int) -> float:
        """QK^T at q_dim and PV at v_dim over one causal sequence."""
        pairs = seq * (seq + 1) / 2
        return 2.0 * self.heads * (self.q_dim + self.v_dim) * pairs

    def dense_ffn_flops_per_token(self) -> float:
        return 3 * 2.0 * self.d_model * self.dense_ffn

    def router_flops_per_token(self) -> float:
        return 2.0 * self.d_model * self.router

    def shared_flops_per_token(self) -> float:
        return sum(j.product_flops(1) for j in self.shared)

    def expert_flops_per_row(self) -> float:
        return sum(j.product_flops(1) for j in self.expert)

    def head_flops_per_token(self) -> float:
        return 2.0 * self.d_model * self.vocab

    def forward_parts(self, seq: int, seqs: int, routed_rows: float) -> dict:
        """Forward work by part over ``seqs`` sequences of ``seq`` tokens,
        ``routed_rows`` token-slots routed to held experts (all layers)."""
        t = seq * seqs
        return {
            "mla_proj": self.layers * t * self.mla_proj_flops_per_token(),
            "mla_scores": self.layers * seqs * self.causal_score_flops(seq),
            "dense_ffn": self.dense_layers * t
            * self.dense_ffn_flops_per_token(),
            "router": self.moe_layers * t * self.router_flops_per_token(),
            "shared": self.moe_layers * t * self.shared_flops_per_token(),
            "routed": routed_rows * self.expert_flops_per_row(),
            "head": t * self.head_flops_per_token(),
        }

    def forward_flops(self, seq: int, seqs: int, routed_rows: float) -> float:
        return sum(self.forward_parts(seq, seqs, routed_rows).values())

    def train_flops(self, seq: int, seqs: int, routed_rows: float) -> float:
        """Forward plus backward (twice the forward) of one step."""
        return 3.0 * self.forward_flops(seq, seqs, routed_rows)

    def expert_train_work(self, routed_rows: float, act_bytes: int,
                          w_bytes: int, steps: int = 1) -> tuple[float, float]:
        """(flops, bytes) of the held experts' fwd, dx and weight-update
        products over ``routed_rows`` routed rows in ``steps`` steps:
        each product's operands and result once, the expert weights read
        once per product per layer and step."""
        flops = sum(3 * j.product_flops(routed_rows) for j in self.expert)
        wbytes = sum(3 * j.weights * w_bytes for j in self.expert)
        rows = sum(3 * routed_rows * (j.n_in + j.n_out) * act_bytes
                   for j in self.expert)
        return flops, rows + steps * self.moe_layers * self.held * wbytes
