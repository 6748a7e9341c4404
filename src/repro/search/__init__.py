"""Population engine: on-device hyperparameter & structure exploration.

The paper closes on "complexity reduction and easy reconfigurability
enable significantly greater exploration of network hyperparameters and
structures on-chip" — this package is that claim as a subsystem.  It
rides the junction engine's existing expert axis, adding NO new kernels.

The E-axis reuse contract
-------------------------

Every kernel in ``kernels/block_sparse_matmul.py`` is E-generic: grid
``(E, ...)`` over weights ``[E, nob, kb, bs, bs]`` with ONE block
pattern in scalar prefetch shared by all E units.  PRs 2–4 used that
axis for MoE experts (same model, E parallel units); this package
re-addresses it as a *population* (E models, one structure):

* **Members must share structure.**  An E-batched launch fixes every
  static kernel input — layer widths, block size, pattern seed,
  activation, optimizer kind (the accumulator-slot layout is static),
  and the per-junction fan-in ``kb`` the density quantizes to
  (``core/sparsity.block_fan_in``).  ``cohorts.bucket`` groups
  candidates by exactly that key; anything else (lr, momentum/b1, b2,
  eps, weight_decay, init seed) varies within a cohort.
* **Hyperparameters ride the ``[E, HYP_K]`` hyp table.**  The fused
  BP+UP epilogue (``update_dw``/``update_gated_dw``) reads registry row
  ``program_id(0)`` (``kernels/block_sparse_matmul.HYP_COLS``: lr, b1,
  b2, eps, wd, t, gs), so each member updates under its own
  hyperparameters — SGD+momentum or Adam — in the same launch; a plain
  ``(2,)`` pair or ``(HYP_K,)`` row (the single-model and MoE path)
  broadcasts to all rows in ``kernels/ops.junction_train_update``.
* **Members never interact.**  The objective is a live-mask-weighted
  sum of per-member losses over a SHARED batch, so the population
  gradient is the stacked single-model gradients — training E members
  population-parallel is numerically the independent runs (the parity
  contract of tests/test_search.py).
* **Pruning is in place.**  Successive halving (``scheduler.run_sweep``)
  zeroes a pruned member's mask entry and hyp row: gradients become
  exact zeros and the in-kernel update rewrites ``w' = w`` — fixed
  shapes, zero recompiles, the serve engine's finished-slot masking
  applied to training.

Modules: ``population`` (stacking, per-member hyp, E-batched steps),
``cohorts`` (structure bucketing), ``scheduler`` (successive halving;
each structure's step and eval built once per process),
``ledger`` (JSON lineage artifact).  ``launch/sweep.py`` is the CLI;
``configs.base.SweepConfig`` the knob set.
"""
from repro.search.cohorts import Cohort, QuantCohort, bucket, bucket_quant
from repro.search.ledger import Ledger, MemberRecord
from repro.search.population import (CandidateSpec, hyp_table,
                                     init_population, init_slots,
                                     make_population_eval,
                                     make_population_step, member_slice,
                                     structure_key)
from repro.search.scheduler import SweepResult, clear_program_cache, run_sweep

__all__ = ["CandidateSpec", "Cohort", "Ledger", "MemberRecord",
           "QuantCohort", "SweepResult", "bucket", "bucket_quant",
           "clear_program_cache", "hyp_table", "init_population",
           "init_slots", "make_population_eval", "make_population_step",
           "member_slice", "run_sweep", "structure_key"]
