"""Distributed linear-algebra workload family (ROADMAP item 5).

Blocked, mesh-sharded dense kernels (``blocked``) and iterative
solvers hosted on the Workflow/Unit graph (``solvers``) — the first
non-NN workloads on this platform, instrumented through the same
telemetry/cost/fault planes as training. See docs/workloads.md.
"""

# every counter this package increments — registered in
# telemetry.counters.DESCRIPTIONS (scripts/check_counters.py) and read
# at zero after a non-linalg run by
# tests/test_telemetry.py test_feature_off_counters_stay_zero
LINALG_COUNTERS = (
    "veles_linalg_block_ops_total",
    "veles_linalg_matmuls_total",
    "veles_linalg_factorizations_total",
    "veles_linalg_solves_total",
    "veles_linalg_iterations_total",
    "veles_linalg_residual_checks_total",
    "veles_linalg_residual_failures_total",
)

from .blocked import (DEFAULT_BLOCK, LinalgError, blocked_cholesky,
                      blocked_matmul, blocked_triangular_solve,
                      cholesky_solve, cyclic_permutation,
                      default_tolerance, linalg_mesh, matmul_cost,
                      cholesky_cost, predict_summa_time,
                      residual_tolerance, verify_residual)
from .solvers import (CGDecision, CGSetup, CGState, CGStep, CGWorkflow,
                      TwoLevelPoisson, build_cg_workflow,
                      poisson2d_dense, poisson2d_matvec)

__all__ = [
    "LINALG_COUNTERS",
    "DEFAULT_BLOCK", "LinalgError", "blocked_cholesky",
    "blocked_matmul", "blocked_triangular_solve", "cholesky_solve",
    "cyclic_permutation", "default_tolerance", "linalg_mesh",
    "matmul_cost", "cholesky_cost", "predict_summa_time",
    "residual_tolerance", "verify_residual", "CGDecision", "CGSetup",
    "CGState", "CGStep", "CGWorkflow", "TwoLevelPoisson",
    "build_cg_workflow", "poisson2d_dense", "poisson2d_matvec",
]
