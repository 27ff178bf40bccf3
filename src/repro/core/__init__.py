"""ENDURE / K-LSM core: the paper's contribution as a composable JAX module.

- lsm_cost:  the unified K-LSM cost model (Eqs. 1-9)
- designs:   Table-3 design-space parameterizations
- nominal:   NOMINAL TUNING (Problem 1) solvers (JAX multistart + SLSQP)
- robust:    ROBUST TUNING (Problem 2) via the KL dual (Eqs. 16-17)
- batch:     single-jit (workload x rho x design) sweep engine backing both
             tuners (tune_nominal_many / tune_robust_many)
- workload:  KL uncertainty regions, exact inner maximizer, rho heuristics
- uncertainty_bench: Table 4 expected workloads + benchmark set B
- metrics:   Delta-throughput and throughput-range (Section 8.1)
- robust_sharding: beyond-paper — same dual applied to mesh/layout selection
"""

from repro.obs import jax_bridge as _jax_bridge

from .batch import (build_results, solve_grid, tune_nominal_many,
                    tune_robust_many)
from .designs import (ENGINE_POLICIES, LAZY_LEVELING_FILL, DesignSpace,
                      describe, policy_effective_phi, to_phi, to_phi_policy)
from .lsm_cost import (LSMSystem, Phi, cost_across_memory, cost_vector,
                       expected_cost, leveling_phi, make_phi, num_levels,
                       throughput, tiering_phi)
from .metrics import delta_throughput, delta_throughput_batch, throughput_range
from .nominal import TuningResult, tune_nominal, tune_nominal_slsqp
from .robust import (dual_solve_cold, dual_solve_warm, primal_worst_case,
                     robust_cost, tune_robust, tune_robust_slsqp)
from .uncertainty_bench import (EXPECTED_WORKLOADS, WORKLOAD_CATEGORY,
                                sample_benchmark, zippydb_like)
from .workload import (kl_divergence, rho_from_history, rho_from_pair,
                       rho_from_ranges, worst_case_workload)

__all__ = [
    "DesignSpace", "LSMSystem", "Phi", "TuningResult",
    "cost_vector", "cost_across_memory", "expected_cost", "throughput",
    "num_levels",
    "make_phi", "leveling_phi", "tiering_phi", "describe", "to_phi",
    "to_phi_policy", "ENGINE_POLICIES", "policy_effective_phi",
    "tune_nominal", "tune_nominal_slsqp", "tune_robust", "tune_robust_slsqp",
    "tune_nominal_many", "tune_robust_many", "solve_grid", "build_results",
    "LAZY_LEVELING_FILL",
    "robust_cost", "dual_solve_cold", "dual_solve_warm",
    "primal_worst_case", "worst_case_workload",
    "kl_divergence", "rho_from_history", "rho_from_pair", "rho_from_ranges",
    "delta_throughput", "delta_throughput_batch", "throughput_range",
    "EXPECTED_WORKLOADS", "WORKLOAD_CATEGORY", "sample_benchmark",
    "zippydb_like",
]

# every obs span is a profiler span too, and compiles are tallied
_jax_bridge.install()
