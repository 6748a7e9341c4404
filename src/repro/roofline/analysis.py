"""Three-term roofline from a compiled dry-run artifact (TPU v5e constants).

    compute    = dot_FLOPs_per_device / PEAK_FLOPS
    memory     = HBM_bytes_per_device / HBM_BW
    collective = collective_bytes_per_device / ICI_BW

The compiled module is post-SPMD, so all walker numbers are already
per-device — chips divide out.  ``raw cost_analysis`` values are recorded
alongside for cross-checking (they under-count scan bodies; see hlo.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any

from repro.roofline import hlo as hlo_mod

# TPU v5e, per chip
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s per link


@dataclasses.dataclass
class Roofline:
    dot_flops: float
    mem_bytes: float
    coll_bytes: float
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    coll_detail: dict
    raw_cost: dict
    memory_stats: dict
    n_while: int
    trip_counts: list
    spurious_f32_bytes: int = 0   # XLA-CPU loop widening artifact (see below)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def widened_f32_loop_state(text: str) -> int:
    """Bytes of f32 while-loop state that duplicate a bf16 twin.

    The CPU HLO pipeline widens some bf16 saved-carry stacks to f32 inside
    the autodiff loops (verified minimal repro in tests/test_roofline.py:
    the jaxpr stores bf16; the optimized CPU module carries BOTH a bf16 and
    an f32 copy, each slice converted straight back to bf16).  This is a
    backend artifact, not program-required memory — per-device footprints
    are reported raw and corrected (EXPERIMENTS.md §Dry-run note)."""
    import re
    bf16_dims: set[str] = set()
    f32_sizes: dict[str, int] = {}
    for m in re.finditer(r"=\s*\(([^)]*)\)\s*while\(", text):
        for dt, dims in re.findall(r"(\w+)\[([\d,]+)\]", m.group(1)):
            if len(dims.split(",")) < 3:
                continue
            if dt == "bf16":
                bf16_dims.add(dims)
            elif dt == "f32":
                n = 1
                for d in dims.split(","):
                    n *= int(d)
                f32_sizes[dims] = max(f32_sizes.get(dims, 0), 4 * n)
    return sum(b for dims, b in f32_sizes.items() if dims in bf16_dims)


def analyze_compiled(compiled, lowered=None) -> Roofline:
    text = compiled.as_text()
    costs = hlo_mod.analyze(text)
    ca = compiled.cost_analysis()
    raw = {k: float(v) for k, v in ca.items()
           if k in ("flops", "bytes accessed", "transcendentals")} if ca else {}
    try:
        ms = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": int(ms.argument_size_in_bytes),
            "output_bytes": int(ms.output_size_in_bytes),
            "temp_bytes": int(ms.temp_size_in_bytes),
            "alias_bytes": int(ms.alias_size_in_bytes),
        }
    except Exception:  # pragma: no cover
        mem_stats = {}

    t_c = costs.dot_flops / PEAK_FLOPS
    t_m = costs.mem_bytes / HBM_BW
    t_l = costs.coll_bytes / ICI_BW
    dominant = max(("compute", t_c), ("memory", t_m), ("collective", t_l),
                   key=lambda kv: kv[1])[0]
    return Roofline(
        dot_flops=costs.dot_flops, mem_bytes=costs.mem_bytes,
        coll_bytes=costs.coll_bytes, t_compute=t_c, t_memory=t_m,
        t_collective=t_l, dominant=dominant,
        coll_detail={k: {"bytes": b, "count": c}
                     for k, (b, c) in costs.coll_detail.items()},
        raw_cost=raw, memory_stats=mem_stats,
        n_while=costs.n_while, trip_counts=costs.trip_counts,
        spurious_f32_bytes=widened_f32_loop_state(text))


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE), D = tokens processed.

    For decode shapes D = global_batch (one token each); train/prefill
    D = seq*batch.  Training costs 3x the forward pass (fwd + 2x bwd)."""
    n = cfg.active_param_count()
    if shape.kind == "decode":
        toks = shape.global_batch
        return 2.0 * n * toks
    toks = shape.tokens
    mult = 3.0 if shape.kind == "train" else 1.0
    return 2.0 * n * toks * mult


def useful_fraction(cfg, shape, per_device_dot_flops: float, n_chips: int) -> float:
    """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is 'useful'."""
    total_hlo = per_device_dot_flops * n_chips
    mf = model_flops(cfg, shape)
    return mf / total_hlo if total_hlo else 0.0
