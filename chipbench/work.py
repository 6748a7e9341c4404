"""Operations and bytes that the benchmark's work needs, computed from
shapes alone.  These are the numerators of every utilisation and
roofline share, so they count what the algorithm requires at the
configured sparsity, never what an implementation happens to compute:
recomputation (rematerialisation, a grad-clip pre-pass) is left out.

A multiply-add is two operations.  Bytes count each operand and result
of a product once, at the item size it is held in.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Junction:
    """A pre-defined block-sparse junction: ``n_out // block`` output
    blocks, each reading ``kb`` input blocks of ``block`` features."""
    n_in: int
    n_out: int
    kb: int
    block: int

    @property
    def weights(self) -> int:
        return (self.n_out // self.block) * self.kb * self.block ** 2

    def product_flops(self, rows: int) -> float:
        """One of the three products (fwd, dx or dw) over ``rows`` rows:
        every kept weight takes part in one multiply-add per row."""
        return 2.0 * rows * self.weights

    def product_bytes(self, rows: int, act_bytes: int, w_bytes: int) -> float:
        """One product's operands and result, each once: fwd reads x
        and W and writes y; dx reads dy and W and writes dx; dw reads x
        and dy and writes dW.  All three move the same bytes."""
        return (rows * (self.n_in + self.n_out) * act_bytes
                + self.weights * w_bytes)


def block_fan_in(n_in_blocks: int, density: float) -> int:
    """Kept input blocks per output block at ``density`` (Python's
    round, half to even), at least one and at most all of them."""
    return min(n_in_blocks, max(1, round(density * n_in_blocks)))


def junction(n_in: int, n_out: int, density: float, block: int) -> Junction:
    return Junction(n_in, n_out, block_fan_in(n_in // block, density), block)


# ------------------------------------------------------------ decoder LM
@dataclasses.dataclass(frozen=True)
class DecoderShape:
    """The widths a dense decoder with sparse (SwiGLU) FFN junctions
    needs for counting: ``ffn`` is (gate, up, down)."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    ffn: tuple[Junction, ...]

    def proj_flops_per_token(self) -> float:
        d, hd = self.d_model, self.head_dim
        qkv = 2.0 * d * (self.heads + 2 * self.kv_heads) * hd
        return qkv + 2.0 * self.heads * hd * d

    def ffn_flops_per_token(self) -> float:
        return sum(j.product_flops(1) for j in self.ffn)

    def causal_score_flops(self, seq: int) -> float:
        """QK^T and PV over one causal sequence of ``seq`` tokens: each
        of the seq(seq+1)/2 query-key pairs costs 2*hd multiply-adds
        per head."""
        pairs = seq * (seq + 1) / 2
        return 4.0 * self.heads * self.head_dim * pairs

    def forward_flops(self, seq: int, seqs: int = 1) -> float:
        """Model work of one forward pass over ``seqs`` causal sequences
        of ``seq`` tokens, the unembedding of every position included."""
        tokens = seq * seqs
        per_layer = (tokens * (self.proj_flops_per_token()
                               + self.ffn_flops_per_token())
                     + seqs * self.causal_score_flops(seq))
        return self.layers * per_layer + tokens * 2.0 * self.d_model * self.vocab

    def train_flops_per_token(self, seq: int) -> float:
        """Forward plus backward (twice the forward: one product for the
        inputs' gradient and one for the weights') per token."""
        return 3.0 * self.forward_flops(seq) / seq

    def junction_train_work(self, rows: int, act_bytes: int,
                            w_bytes: int) -> tuple[float, float]:
        """(flops, bytes) of every FFN junction's fwd, dx and dw products
        in one training step over ``rows`` token rows."""
        flops = sum(3 * j.product_flops(rows) for j in self.ffn)
        bytes_ = sum(3 * j.product_bytes(rows, act_bytes, w_bytes)
                     for j in self.ffn)
        return self.layers * flops, self.layers * bytes_


# ------------------------------------------------------------ population
@dataclasses.dataclass(frozen=True)
class PopulationShape:
    """An MLP of junctions trained as a population: the first junction
    reads the data, so it needs no input gradient."""
    junctions: tuple[Junction, ...]

    def _products(self, i: int) -> int:
        return 2 if i == 0 else 3

    def member_step_flops(self, rows: int) -> float:
        return sum(self._products(i) * j.product_flops(rows)
                   for i, j in enumerate(self.junctions))

    def member_step_bytes(self, rows: int, act_bytes: int,
                          w_bytes: int) -> float:
        return sum(self._products(i) * j.product_bytes(rows, act_bytes,
                                                       w_bytes)
                   for i, j in enumerate(self.junctions))
