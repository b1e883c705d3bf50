"""Distributed linear-algebra family (veles_tpu/linalg/) — tier-1.

The family's contract, each clause locked here:

- **blocked == dense**: the block-cyclic SUMMA matmul, the
  right-looking blocked Cholesky and the blocked triangular solves
  match ``numpy.linalg`` within the stated 100·eps tolerance — on
  deliberately awkward shapes (odd sizes, blocks that do not divide
  the dims) and on the 8-virtual-device mesh path as well as the
  single-device path.
- **solvers converge and verify**: CG on the SPD Poisson model problem
  reaches < 1e-5; the multigrid-preconditioned run needs fewer
  iterations; a finish claiming convergence is re-verified through the
  trusted dense path, so a corrupt block op can NEVER yield a
  silently-wrong answer (chaos-tested via ``linalg.block_op``).
- **telemetry**: every veles_linalg_* counter is registered and
  counts (that they read zero in a run without linalg is
  ``tests/test_telemetry.py``'s).
- **dtype-correct peaks**: f32 work is graded against the f32 peak
  table (half the bf16 entry), and the stamped source label says so.
"""
import numpy
import pytest

from conftest import import_model
from veles_tpu.linalg import (LINALG_COUNTERS, LinalgError,
                              TwoLevelPoisson, blocked_cholesky,
                              blocked_matmul, blocked_triangular_solve,
                              build_cg_workflow, cholesky_solve,
                              cyclic_permutation, default_tolerance,
                              linalg_mesh, poisson2d_dense,
                              poisson2d_matvec, predict_summa_time,
                              verify_residual)
from veles_tpu.resilience.faults import FaultInjected
from veles_tpu.telemetry.counters import DESCRIPTIONS, counters

F32_TOL = default_tolerance(numpy.float32)


def _spd(n, seed=3, dtype=numpy.float32):
    g = numpy.random.RandomState(seed).standard_normal((n, n))
    return (g @ g.T + n * numpy.eye(n)).astype(dtype)


# -- layout helpers ----------------------------------------------------------

def test_cyclic_permutation_roundtrip():
    for n_pad, slabs, p in ((24, 4, 2), (48, 8, 4), (16, 4, 4)):
        perm, inv = cyclic_permutation(n_pad, slabs, p)
        x = numpy.arange(n_pad)
        assert (x[perm][inv] == x).all()
        assert (x[inv][perm] == x).all()


def test_linalg_mesh_squarest_and_explicit():
    mesh = linalg_mesh()
    assert tuple(mesh.devices.shape) == (2, 4)      # 8 virtual devices
    assert mesh.axis_names == ("rows", "cols")
    sub = linalg_mesh((1, 2))
    assert tuple(sub.devices.shape) == (1, 2)


# -- blocked kernels vs the dense reference ----------------------------------

def test_blocked_matmul_matches_dense_single():
    rng = numpy.random.RandomState(0)
    for m, k, n, block in ((37, 23, 41, 8), (16, 16, 16, 64),
                           (5, 7, 3, 4)):
        a = rng.standard_normal((m, k)).astype(numpy.float32)
        b = rng.standard_normal((k, n)).astype(numpy.float32)
        c = numpy.asarray(blocked_matmul(a, b, block=block, mesh=None))
        ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
        rel = numpy.linalg.norm(c - ref) / numpy.linalg.norm(ref)
        assert rel < F32_TOL, (m, k, n, block, rel)


def test_blocked_matmul_matches_dense_on_mesh():
    """SUMMA over the 2x4 mesh == dense == single-device, on shapes
    the G=4 panel padding must handle (nothing divides evenly)."""
    rng = numpy.random.RandomState(1)
    mesh = linalg_mesh()
    a = rng.standard_normal((50, 30)).astype(numpy.float32)
    b = rng.standard_normal((30, 70)).astype(numpy.float32)
    ref = a.astype(numpy.float64) @ b.astype(numpy.float64)
    single = numpy.asarray(blocked_matmul(a, b, block=16, mesh=None))
    for cyclic in (True, False):
        c = numpy.asarray(blocked_matmul(a, b, block=16, mesh=mesh,
                                         cyclic=cyclic))
        rel = numpy.linalg.norm(c - ref) / numpy.linalg.norm(ref)
        assert rel < F32_TOL, (cyclic, rel)
        drift = (numpy.linalg.norm(c - single)
                 / numpy.linalg.norm(single))
        assert drift < F32_TOL, (cyclic, drift)


def test_blocked_cholesky_matches_dense():
    spd = _spd(45)
    ref = numpy.linalg.cholesky(spd.astype(numpy.float64))
    for mesh in (None, linalg_mesh()):
        l = numpy.asarray(blocked_cholesky(spd, block=16, mesh=mesh,
                                           mesh_min=8))
        rel = numpy.linalg.norm(l - ref) / numpy.linalg.norm(ref)
        assert rel < F32_TOL, rel
        assert numpy.allclose(l, numpy.tril(l))


def test_blocked_cholesky_rejects_indefinite():
    bad = numpy.eye(12, dtype=numpy.float32)
    bad[5, 5] = -1.0
    with pytest.raises(LinalgError):
        blocked_cholesky(bad, block=4)


def test_triangular_and_cholesky_solve():
    rng = numpy.random.RandomState(5)
    spd = _spd(33)
    b = rng.standard_normal((33, 2)).astype(numpy.float32)
    l = numpy.asarray(blocked_cholesky(spd, block=8))
    y = numpy.asarray(blocked_triangular_solve(l, b, lower=True,
                                               block=8))
    assert numpy.linalg.norm(l @ y - b) / numpy.linalg.norm(b) < F32_TOL
    x = numpy.asarray(cholesky_solve(spd, b, block=8, check=True))
    ref = numpy.linalg.solve(spd.astype(numpy.float64),
                             b.astype(numpy.float64))
    assert (numpy.linalg.norm(x - ref)
            / numpy.linalg.norm(ref)) < F32_TOL


def test_verify_residual_fails_loud():
    spd = _spd(16)
    b = numpy.ones((16,), dtype=numpy.float32)
    x = numpy.linalg.solve(spd, b)
    before = counters.snapshot()
    verify_residual(spd, x, b)                       # good x passes
    with pytest.raises(LinalgError):
        verify_residual(spd, x + 1.0, b)             # bad x raises
    delta = counters.delta(before)
    assert delta.get("veles_linalg_residual_checks_total") == 2
    assert delta.get("veles_linalg_residual_failures_total") == 1


# -- solvers on the Workflow graph -------------------------------------------

def test_cg_poisson_converges_and_verifies():
    n = 12
    rhs = numpy.random.RandomState(7).standard_normal(
        n * n).astype(numpy.float32)
    before = counters.snapshot()
    wf = build_cg_workflow(poisson2d_matvec(n), rhs, tol=1e-6,
                           max_iters=400)
    wf.initialize()
    wf.run()
    res = wf.cg_decision.get_metric_values()
    assert res["converged"]
    assert res["residual"] < 1e-5
    assert res["true_residual"] is not None
    assert res["true_residual"] < 1e-4
    # per-iteration telemetry: one history entry per step + seed
    assert len(res["residual_history"]) == res["iterations"] + 1
    delta = counters.delta(before)
    assert delta.get("veles_linalg_iterations_total") == \
        res["iterations"]
    assert delta.get("veles_linalg_solves_total") == 1


def test_cg_dense_operator_routes_through_blocked_matmul():
    n = 8
    dense = poisson2d_dense(n)
    rhs = numpy.random.RandomState(8).standard_normal(
        n * n).astype(numpy.float32)
    before = counters.snapshot()
    wf = build_cg_workflow(dense, rhs, tol=1e-6, max_iters=200,
                           mesh=linalg_mesh((1, 2)), block=16)
    wf.initialize()
    wf.run()
    res = wf.cg_decision.get_metric_values()
    assert res["converged"] and res["residual"] < 1e-5
    # the matvec went through the blocked (faultable) path
    assert counters.delta(before).get("veles_linalg_matmuls_total")


def test_pcg_multigrid_beats_plain_cg():
    n = 12
    rhs = numpy.random.RandomState(9).standard_normal(
        n * n).astype(numpy.float32)
    runs = {}
    for label, precond in (("cg", None),
                           ("pcg", TwoLevelPoisson(n, block=16))):
        wf = build_cg_workflow(poisson2d_matvec(n), rhs, tol=1e-6,
                               max_iters=400, preconditioner=precond)
        wf.initialize()
        wf.run()
        runs[label] = wf.cg_decision.get_metric_values()
        assert runs[label]["converged"]
    assert runs["pcg"]["iterations"] < runs["cg"]["iterations"]


def test_cg_rejects_non_spd_operator():
    n = 4
    rhs = numpy.ones(n, dtype=numpy.float32)
    wf = build_cg_workflow(lambda v: -v, rhs, tol=1e-6, max_iters=10)
    wf.initialize()
    with pytest.raises(LinalgError):
        wf.run()


def test_twolevel_poisson_needs_even_n():
    with pytest.raises(LinalgError):
        TwoLevelPoisson(7)


def test_poisson_solver_model():
    mod = import_model("poisson_solver")
    wf = mod.build_workflow(n=8, tol=1e-6, max_iters=200)
    wf.initialize()
    wf.run()
    res = wf.cg_decision.get_metric_values()
    assert res["converged"] and res["residual"] < 1e-5


# -- chaos: linalg.block_op --------------------------------------------------

def test_chaos_corrupt_block_fails_loud_never_silent(monkeypatch):
    """THE satellite lock: a corrupted block op must surface as a
    LinalgError from the residual check — never as a returned
    silently-wrong x."""
    spd = _spd(24)
    b = numpy.ones((24, 1), dtype=numpy.float32)
    monkeypatch.setenv("VELES_FAULTS", "linalg.block_op:corrupt")
    with pytest.raises(LinalgError):
        cholesky_solve(spd, b, block=8, check=True)
    monkeypatch.setenv("VELES_FAULTS", "")


def test_chaos_corrupt_cg_reports_nonconvergence(monkeypatch):
    """Persistent corruption inside the CG matvec: the solve must end
    in an explicit non-answer (converged=False or a raise) — the
    convergence claim is what the trusted re-verification guards."""
    n = 6
    dense = poisson2d_dense(n)
    rhs = numpy.ones(n * n, dtype=numpy.float32)
    monkeypatch.setenv("VELES_FAULTS", "linalg.block_op:corrupt")
    wf = build_cg_workflow(dense, rhs, tol=1e-8, max_iters=25,
                           block=8)
    wf.initialize()
    try:
        wf.run()
        res = wf.cg_decision.get_metric_values()
        assert not res["converged"] or res["true_residual"] < 1e-6
    except LinalgError:
        pass                     # loud failure is equally acceptable
    finally:
        monkeypatch.setenv("VELES_FAULTS", "")


def test_chaos_raise_propagates(monkeypatch):
    rng = numpy.random.RandomState(2)
    a = rng.standard_normal((8, 8)).astype(numpy.float32)
    monkeypatch.setenv("VELES_FAULTS", "linalg.block_op:raise:times=1")
    with pytest.raises(FaultInjected):
        blocked_matmul(a, a, block=8)
    monkeypatch.setenv("VELES_FAULTS", "")
    numpy.asarray(blocked_matmul(a, a, block=8))     # healed


# -- telemetry + gate plumbing -----------------------------------------------

def test_linalg_counters_registered():
    for name in LINALG_COUNTERS:
        assert name in DESCRIPTIONS, name
    before = counters.snapshot()
    a = numpy.eye(4, dtype=numpy.float32)
    numpy.asarray(blocked_matmul(a, a, block=4))
    delta = counters.delta(before)
    assert delta.get("veles_linalg_matmuls_total") == 1
    assert delta.get("veles_linalg_block_ops_total")


# -- dtype-correct peak table ------------------------------------------------

def test_peak_flops_f32_is_half_bf16():
    from veles_tpu.telemetry.cost import (PEAK_BF16, PEAK_F32,
                                          UnknownDevice,
                                          peak_flops_entry)
    bf16 = dict(PEAK_BF16)
    for kind, peak in PEAK_F32:
        assert peak == bf16[kind] / 2, kind
    v5e = "TPU v5 lite"
    src32, p32 = peak_flops_entry("float32", device_kind=v5e)
    srcbf, pbf = peak_flops_entry("bfloat16", device_kind=v5e)
    assert "PEAK_F32" in src32 and "F32" not in srcbf
    assert pbf == 197e12 and p32 == pbf / 2
    # device-kind substring match routes to the named entry
    src, p = peak_flops_entry(numpy.float32, device_kind="TPU v4")
    assert src == "telemetry.cost.PEAK_F32[v4]" and p == 137.5e12
    # f64 has no separate table: graded against the f32 ceiling
    assert peak_flops_entry("float64", device_kind=v5e)[1] == p32
    # a device with no row is an error, not some other chip's peak —
    # including the CPU these tests run on
    for dtype in ("float32", "bfloat16"):
        with pytest.raises(UnknownDevice, match="mystery"):
            peak_flops_entry(dtype, device_kind="mystery")
        with pytest.raises(UnknownDevice):
            peak_flops_entry(dtype)


def test_predict_summa_time_states_every_input():
    pred = predict_summa_time(384, 384, 384, (2, 4), t1_step_s=1.0)
    inputs = pred["inputs"]
    for field in ("t1_step_s", "grid", "panels",
                  "block_bytes_a_panel", "block_bytes_b_panel",
                  "psum_bytes_per_device",
                  "ici_bw_assumed_bytes_per_s", "ici_bw_source"):
        assert field in inputs, field
    assert pred["predicted_step_s"] == pytest.approx(
        pred["compute_s"] + pred["comm_s"])
    assert pred["comm_s"] > 0 and inputs["psum_bytes_per_device"] > 0
    # a 1x1 grid broadcasts nothing
    solo = predict_summa_time(384, 384, 384, (1, 1), t1_step_s=1.0)
    assert solo["comm_s"] == 0
    assert solo["predicted_step_s"] == pytest.approx(1.0)


