"""The expert-layer training cell cut to a size a CPU test run can hold:
the same driver, adapter, reference and limits, at tiny widths (Pallas
kernels in interpret mode), with YaRN kept, ``norm_topk_prob`` false and
2 of the router's 8 experts held at top-3.  Layer 0's FFN width is, as
at full size, no multiple of the block, so it stays dense."""
from __future__ import annotations

import copy

from chipbench import bench

CELL = "dsv2lite-train-fused-s8k"
TINY = {"hidden_size": 128, "intermediate_size": 264,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
        "v_head_dim": 32, "moe_intermediate_size": 64,
        "num_hidden_layers": 3, "router_experts": 8, "n_routed_experts": 2,
        "first_held_expert": 0, "num_experts_per_tok": 3,
        "vocab_held": 256, "attn_chunk": 16}
TRAIN = {"batch": 2, "seq": 32}


def tiny_cell(**conf) -> bench.Cell:
    cell = copy.deepcopy(bench.find_cell(CELL))
    cell.config.update(TINY)
    cell.config.update(conf)
    cell.config["sparse_ffn"]["block"] = 32
    cell.traffic.update(TRAIN)
    return cell
