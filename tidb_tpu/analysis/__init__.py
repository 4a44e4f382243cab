"""Static analysis gate: plan-contract verifier + TPU-hygiene linter +
shape/memory cost model.

Three passes, all wired into CI as a zero-findings gate
(``python -m tidb_tpu.analysis``):

- contracts: every physical operator declares a contract (output dtypes,
  row-capacity shape, sharding, traceable-dense vs host locality); the
  verifier walks built plans edge-by-edge and rejects inconsistent ones
  with a structured PlanContractError BEFORE any jit/trace happens.
  Hooked into the session plan path, the sched admission path
  (verify_task), and EXPLAIN (verified plans report ``contract: ok``).
- lint: an AST linter over tidb_tpu/ with repo-specific TPU-hygiene
  rules (tracer leaks, digest instability, host transfers in hot paths,
  broad exception handlers, lock-order hazards, x64-flag-dependent
  dtypes).  Pre-existing accepted findings live in analysis/baseline.txt;
  anything new fails the gate.
- copcost: a static shape/memory abstract interpreter that walks built
  cop DAGs using only contracts (padded device shapes from DENSE
  domain_sizes / SORT capacities, physical dtype widths, per-shard
  extents under the mesh) and rolls up a per-launch LaunchCost
  (peak HBM bytes, transfer bytes, flops, padding waste).  Gate rules
  COST-PAD-WASTE / COST-CAP-BLOWUP / COST-DENSE-BLOWUP /
  COST-UNBOUNDED ride the corpus;
  sched admission enforces peak_hbm_bytes against a per-mesh budget
  (CostError, pre-trace) and EXPLAIN surfaces the estimate.
- copmeter (analysis/calibrate): the closed-loop half of the cost
  model — a bounded per-digest EWMA correction store (clamped to
  [1/8, 8], persisted through the copforge manifest) corrects
  LaunchCost from measured launch times and OOM events; the scheduler
  feeds corrected costs into RU pricing, HBM-budget admission, fusion
  caps, the micro-batch window, and deadline-aware early shedding.
  The gate grows a calibration pass (deterministic drift simulation,
  < 25% corpus pricing error) and the TPU-CALIB-CLAMP lint rule.
- shardflow (analysis/shardflow + parallel/topology): a sharding-layout
  & collective-transfer abstract interpreter — the mesh modeled as
  typed links (intra-chip / same-host ICI / cross-host DCI from the
  declared host view), every collective verified against it pre-trace
  (implicit reshards, unknown axes, coordinator-routed host merges,
  psum limb-fence bounds, DCI blow-ups), and transfer bytes rolled up
  per link class into ``LaunchCost.transfer_breakdown`` so admission,
  RU pricing (a 4x DCI rate), and fusion caps stay honest at pod
  scale.  SHARD-*/COST-DCI-BLOWUP findings ride the corpus plus the
  MULTICHIP dryrun plan shapes under a fake (host=2, device=4) view.
- coplife (analysis/lifetime): a buffer-lifetime pass over the same
  contract DAGs classifying every device-program input slot as
  PERSISTENT (snapshot-cache residents) / LOOP-CARRIED (paging and
  regrow state the client re-feeds) / EPHEMERAL (dead after the
  launch), and emitting the per-program-shape DonationPlan the spmd
  builders derive ``donate_argnums`` from.  DONATE-UNSAFE /
  DONATE-MISSED gate rules ride the corpus; sched admission rejects a
  donating task over a live resident pre-trace, and donated bytes
  tighten LaunchCost.peak_hbm_bytes.

The motivation is the compiler-first failure mode: with XLA-compiled cop
programs a bad plan no longer fails with a type error at build time — it
fails deep inside tracing (shape mismatch, silent dtype promotion,
surprise recompile) or returns wrong rows.  Compiler-first engines
(Flare, LAQP) verify a typed IR before codegen; this package is that
gate between planner/build and jit.
"""

from .calibrate import (BoundedLRU, Correction, CorrectionStore,
                        clamp_factor, correction_store)
from .contracts import (PlanContractError, verify_dag, verify_plan,
                        verify_task)
from .copcost import CostError, LaunchCost, plan_cost, task_cost
from .lifetime import (BufferClass, DonationError, DonationPlan,
                       donation_plan, verify_donation)
from .lint import Finding, lint_source, lint_tree, load_baseline
from .shardflow import (plan_transfer, verify_dag_sharding,
                        verify_plan_sharding, verify_task_sharding)

__all__ = ["PlanContractError", "verify_plan", "verify_dag", "verify_task",
           "CostError", "LaunchCost", "plan_cost", "task_cost",
           "BufferClass", "DonationError", "DonationPlan",
           "donation_plan", "verify_donation",
           "BoundedLRU", "Correction", "CorrectionStore",
           "correction_store", "clamp_factor",
           "plan_transfer", "verify_dag_sharding", "verify_plan_sharding",
           "verify_task_sharding",
           "Finding", "lint_tree", "lint_source", "load_baseline"]
