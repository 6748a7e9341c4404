"""Cells of the benchmark cut to sizes a CPU test run can hold: the same
drivers, adapters, references and limits, at tiny widths (Pallas kernels
in interpret mode)."""
from __future__ import annotations

import copy

from chipbench import bench

TINY_LM = {"hidden_size": 128, "intermediate_size": 256,
           "num_attention_heads": 4, "num_key_value_heads": 4,
           "num_hidden_layers": 2, "vocab_size": 512}
TINY_TRAIN = {"batch": 2, "seq": 32}
TINY_SERVE = {"slots": 2, "page_size": 8, "prefill_chunk": 16,
              "requests_per_call": 4, "check_requests": 3,
              "prompt": {"median": 12, "sigma": 0.8, "min": 4, "max": 24},
              "output": {"median": 4, "sigma": 0.8, "min": 2, "max": 8}}
TINY_POP = {"hidden_size": 256, "intermediate_size": 512,
            "layers": [256, 512, 256], "block": 32}
TINY_SWEEP = {"densities": [0.25, 0.5], "steps_per_round": 2,
              "check_steps_per_round": 2, "batch": 64, "train_samples": 256,
              "eval_samples": 64}


def tiny_cell(name: str) -> bench.Cell:
    cell = bench.find_cell(name)
    cell = copy.deepcopy(cell)
    if cell.traffic["kind"] == "train":
        cell.config.update(TINY_LM)
        cell.config["sparse_ffn"]["block"] = 32
        cell.traffic.update(TINY_TRAIN)
    elif cell.traffic["kind"] == "serve":
        cell.config.update(TINY_LM)
        cell.config["sparse_ffn"]["block"] = 32
        cell.traffic.update(TINY_SERVE)
    elif cell.traffic["kind"] == "sweep":
        cell.config.update(TINY_POP)
        cell.traffic.update(TINY_SWEEP)
    return cell
