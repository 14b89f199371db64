"""Adaptive cost-based plan optimizer.

The paper's engine exposes interchangeable physical plans (Section 5.3's
joins x group-bys x connectors) but leaves the choice to the user; this
subsystem makes the runtime pick — and mid-run re-pick — the plan:

* ``stats``     one typed per-superstep record + collector (Section 5.7's
                statistics collector, generalized from the drivers' ad-hoc
                dicts)
* ``cost``      analytical per-superstep cost model over the plan space,
                tied to the dry-run machine model and HLO-calibratable
* ``optimizer`` enumerate + prune + min-cost plan for given statistics
* ``adaptive``  mid-run replanning with hysteresis at superstep boundaries

Entry points: ``run_host(..., plan="auto")``, ``run_jit(..., plan="auto")``,
``run_out_of_core(..., plan="auto")`` and ``launch/pregel_run.py
--auto-plan``.
"""
from repro.planner.adaptive import (AdaptiveConfig, AdaptiveController,
                                    migrate_msgs, resolve_auto_plan)
from repro.planner.cost import (DEFAULT_MACHINE, EMULATED_MACHINE,
                                MACHINES, GraphStats, MachineModel,
                                Observation, machine_for,
                                PlanCost, bucket_cap, calibrate_machine,
                                estimate, hlo_calibrate,
                                refit_frontier_cap)
from repro.planner.optimizer import choose, plan_space, rank
from repro.planner.stats import StatsCollector, SuperstepStats, msg_bytes

__all__ = [
    "AdaptiveConfig", "AdaptiveController", "migrate_msgs",
    "resolve_auto_plan", "DEFAULT_MACHINE", "EMULATED_MACHINE",
    "MACHINES", "machine_for", "GraphStats", "MachineModel",
    "Observation", "PlanCost", "bucket_cap", "calibrate_machine",
    "estimate", "hlo_calibrate",
    "refit_frontier_cap", "choose", "plan_space", "rank", "StatsCollector",
    "SuperstepStats", "msg_bytes",
]
