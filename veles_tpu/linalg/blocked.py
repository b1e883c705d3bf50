"""Blocked, mesh-sharded dense linear algebra.

The first non-NN workload family this build hosts (ROADMAP item 5 —
the reference VELES was a general dataflow platform, not an NN
trainer). The kernels follow the TPU linear-algebra literature
(PAPERS.md: "Large Scale Distributed Linear Algebra With Tensor
Processing Units", "JAXMg"): dense matrices are tiled into blocks, the
block grid is laid out block-cyclically over a 2D ("rows", "cols")
device mesh, and every distributed operation decomposes into *local
block dots plus psums* expressed with ``jax.shard_map``.

Three layers, each falsifiable against the layer below:

- ``blocked_matmul`` — SUMMA: for each of the ``G = lcm(pr, pc)``
  k-panels, the owner column broadcasts its A panel along the mesh row
  (a masked psum), the owner row broadcasts its B panel along the mesh
  column, and every device accumulates one local dot. The single-device
  path runs the same panel loop without the mesh; both are asserted
  equal to ``a @ b`` in tests.
- ``blocked_cholesky`` / ``blocked_triangular_solve`` — right-looking
  blocked factorization: small dense potrf on the diagonal block, a
  triangular solve for the panel, and the trailing SYRK update routed
  through ``blocked_matmul`` (which is where the mesh enters).
  Reference: ``np.linalg.cholesky`` / ``scipy``-style substitution.
- ``verify_residual`` — the trusted check every solver must pass
  before an answer is returned. It applies the operator with a PLAIN
  dense dot (never through the faultable block dispatch below), so an
  injected corruption can never vouch for itself: a corrupt block
  makes the solve fail loudly instead of returning a silently-wrong x.

Fault surface: every host-side block dispatch calls
``resilience.faults.fire("linalg.block_op")`` — ``raise`` aborts the
dispatch, ``corrupt`` flips bytes in the dispatched block (the chaos
test proves the residual check catches it). Costs are recorded into
``telemetry.cost.model`` as analytic entries (2mnk matmul flops, n³/3
potrf) keyed ``linalg.*``, with MFU priced against the *computation
dtype's* peak (``peak_flops_entry`` — f32 work is not graded against
the bf16 peak).

Tolerances (stated so the equality claims are falsifiable): blocked
results match the dense reference to ``rtol = 100·eps(dtype)`` of the
result's scale — f32 ≈ 1.2e-5, f64 ≈ 2.2e-14 — and solver residuals
must pass ``verify_residual``'s relative bound (default
``RESIDUAL_TOL`` per dtype below).
"""

from __future__ import annotations

import math
from typing import Callable, Optional, Tuple, Union

import numpy

from ..error import VelesError
from ..telemetry.counters import inc
from ..telemetry.spans import span
from ..telemetry import cost as cost_mod
from ..resilience import faults


class LinalgError(VelesError):
    """A linear-algebra kernel produced (or was asked to produce) an
    answer it cannot stand behind: residual check failure, non-SPD
    input to Cholesky, malformed mesh/shape."""


#: default k-panel width for the single-device blocked paths
DEFAULT_BLOCK = 128

#: default relative residual bound of :func:`verify_residual`, keyed by
#: result dtype itemsize (4 → f32, 8 → f64). Stated here so the
#: "never silently wrong" claim has one number to refute.
RESIDUAL_TOL = {4: 1e-4, 8: 1e-10}


def _jnp():
    import jax.numpy as jnp
    return jnp


def default_tolerance(dtype) -> float:
    """The stated blocked-vs-dense equality tolerance for ``dtype``:
    100·eps, relative to the result's scale."""
    return 100.0 * float(numpy.finfo(numpy.dtype(dtype)).eps)


def residual_tolerance(dtype) -> float:
    """Default :func:`verify_residual` bound for ``dtype``."""
    return RESIDUAL_TOL.get(numpy.dtype(dtype).itemsize, 1e-4)


# ---------------------------------------------------------------------------
# fault surface: one chokepoint every blocked dispatch goes through
# ---------------------------------------------------------------------------

def _dispatch_block(block, **ctx):
    """The ``linalg.block_op`` injection chokepoint: counts the
    dispatch, then lets the fault plane raise, or corrupt the block's
    bytes. The payload is framed big-endian and padded so
    ``Fault.corrupt``'s middle-byte flip lands on the sign/exponent
    byte of one element — real damage the residual check MUST catch
    (a little-endian middle byte would be a mantissa LSB: a 1-ulp
    perturbation inside every stated tolerance, proving nothing)."""
    inc("veles_linalg_block_ops_total")
    fault = faults.fire("linalg.block_op", **ctx)
    if fault is None:
        return block
    arr = numpy.asarray(block)
    if arr.size == 0:                 # nothing to damage
        return block
    be = arr.dtype.newbyteorder(">")
    raw = arr.astype(be).tobytes()
    item = arr.dtype.itemsize
    pad = next(q for q in range(0, 2 * item + 1)
               if ((len(raw) + q) // 2 - q) % item == 0
               and (len(raw) + q) // 2 >= q)
    damaged = fault.corrupt(b"\x00" * pad + raw)[pad:]
    return _jnp().asarray(numpy.frombuffer(damaged, dtype=be)
                          .reshape(arr.shape).astype(arr.dtype))


# ---------------------------------------------------------------------------
# mesh + block-cyclic layout helpers
# ---------------------------------------------------------------------------

def linalg_mesh(grid: Optional[Tuple[int, int]] = None, devices=None):
    """A 2D ``("rows", "cols")`` device mesh for the blocked kernels.

    ``grid=None`` picks the squarest (pr, pc) factorization of the
    visible device count (8 devices → 2×4). A submesh (grid smaller
    than the device count) is allowed, mirroring ``backends.make_mesh``.
    """
    import jax
    from jax.sharding import Mesh
    devices = list(jax.devices() if devices is None else devices)
    if grid is None:
        n = len(devices)
        pr = int(math.sqrt(n))
        while pr > 1 and n % pr:
            pr -= 1
        grid = (pr, n // pr)
    pr, pc = int(grid[0]), int(grid[1])
    if pr < 1 or pc < 1:
        raise LinalgError("linalg mesh grid must be positive, got %r"
                          % (grid,))
    need = pr * pc
    if need > len(devices):
        raise LinalgError("linalg mesh %dx%d needs %d devices, have %d"
                          % (pr, pc, need, len(devices)))
    arr = numpy.asarray(devices[:need]).reshape(pr, pc)
    return Mesh(arr, ("rows", "cols"))


def _pad_to(a, rows: int, cols: int):
    """Zero-pad a 2D array up to (rows, cols)."""
    jnp = _jnp()
    r, c = a.shape
    if r == rows and c == cols:
        return a
    return jnp.pad(a, ((0, rows - r), (0, cols - c)))


def cyclic_permutation(n_pad: int, slabs: int, p: int):
    """Block-cyclic layout as a row permutation.

    Splitting the (padded) axis into ``slabs`` equal slabs and dealing
    them round-robin over ``p`` shards is the classic block-cyclic
    distribution; with shard_map's *contiguous* sharding the same
    layout is obtained by permuting slab ``s`` into the contiguous
    range of shard ``s mod p`` first. Returns ``(perm, inv)`` index
    vectors (``a[perm][inv] == a``).
    """
    if n_pad % slabs:
        raise LinalgError("cyclic layout: %d not divisible into %d slabs"
                          % (n_pad, slabs))
    w = n_pad // slabs
    order = [s for d in range(p) for s in range(d, slabs, p)]
    perm = numpy.concatenate(
        [numpy.arange(s * w, (s + 1) * w) for s in order])
    inv = numpy.empty_like(perm)
    inv[perm] = numpy.arange(n_pad)
    return perm, inv


# ---------------------------------------------------------------------------
# SUMMA matmul
# ---------------------------------------------------------------------------

def _summa_local(ax_r: str, ax_c: str, pr: int, pc: int, G: int, w: int):
    """The per-device SUMMA body: G panel steps, each one masked-psum
    broadcast of the A panel along the mesh row and of the B panel
    along the mesh column, then a local dot accumulate."""
    import jax

    def local(a_loc, b_loc):
        jnp = _jnp()
        row = jax.lax.axis_index(ax_r)
        col = jax.lax.axis_index(ax_c)
        acc = jnp.zeros((a_loc.shape[0], b_loc.shape[1]), a_loc.dtype)
        for g in range(G):
            # A's k-axis is sharded over cols: each col shard holds
            # G/pc consecutive panels; panel g lives in col g//(G/pc)
            oc, la = divmod(g, G // pc)
            orow, lb = divmod(g, G // pr)
            a_sub = a_loc[:, la * w:(la + 1) * w]
            b_sub = b_loc[lb * w:(lb + 1) * w, :]
            a_g = jax.lax.psum(
                jnp.where(col == oc, a_sub, jnp.zeros_like(a_sub)), ax_c)
            b_g = jax.lax.psum(
                jnp.where(row == orow, b_sub, jnp.zeros_like(b_sub)), ax_r)
            acc = acc + a_g @ b_g
        return acc

    return local


def blocked_matmul(a, b, block: int = DEFAULT_BLOCK, mesh=None,
                   cyclic: bool = True):
    """``a @ b`` by blocked panels — SUMMA over a 2D mesh, or the same
    panel loop on one device when ``mesh is None``.

    ``cyclic=True`` (the default, mesh path only) lays the block grid
    out block-cyclically: the matrix axes are slab-permuted before
    sharding and the result is un-permuted, so device (i, j) holds a
    round-robin set of blocks instead of one contiguous tile —
    mathematically identical (matmul commutes with a shared row/column
    permutation), better balanced for the triangular updates built on
    top. Records an analytic 2mnk-FLOP cost under ``linalg.matmul``.
    """
    jnp = _jnp()
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
        raise LinalgError("blocked_matmul shapes %r @ %r"
                          % (tuple(a.shape), tuple(b.shape)))
    m, k = a.shape
    n = b.shape[1]
    with span("linalg.matmul", m=m, k=k, n=n,
              mesh=(tuple(mesh.devices.shape) if mesh is not None
                    else None)):
        if mesh is None:
            out = _matmul_single(a, b, block)
        else:
            out = _matmul_summa(a, b, mesh, cyclic)
    cost_mod.model.record("linalg.matmul", matmul_cost(m, k, n, a.dtype))
    inc("veles_linalg_matmuls_total")
    return out


def _matmul_single(a, b, block: int):
    """Single-device reference path: the identical k-panel loop, one
    block dispatch per panel."""
    jnp = _jnp()
    m, k = a.shape
    n = b.shape[1]
    acc = jnp.zeros((m, n), a.dtype)
    for s in range(0, k, block):
        e = min(k, s + block)
        a_sub = _dispatch_block(a[:, s:e], op="matmul", panel=s // block)
        acc = acc + a_sub @ b[s:e, :]
    return acc


def _matmul_summa(a, b, mesh, cyclic: bool):
    import jax
    from jax.sharding import PartitionSpec as P

    jnp = _jnp()
    if len(mesh.devices.shape) != 2:
        raise LinalgError("linalg needs a 2D mesh, got shape %r"
                          % (tuple(mesh.devices.shape),))
    pr, pc = mesh.devices.shape
    ax_r, ax_c = mesh.axis_names
    G = pr * pc // math.gcd(pr, pc)          # lcm: k-panel count
    m, k = a.shape
    n = b.shape[1]
    mp = G * -(-m // G)
    kp = G * -(-k // G)
    np_ = G * -(-n // G)
    a_p = _pad_to(a, mp, kp)
    b_p = _pad_to(b, kp, np_)
    if cyclic:
        pm, pm_inv = cyclic_permutation(mp, G, pr)
        pk, _ = cyclic_permutation(kp, G, pc)
        pn, pn_inv = cyclic_permutation(np_, G, pc)
        # the SAME k-permutation on A's columns and B's rows cancels in
        # the contraction; row/col permutations are undone on C
        a_p = a_p[pm][:, pk]
        b_p = b_p[pk][:, pn]
    a_p = _dispatch_block(a_p, op="summa", grid=(int(pr), int(pc)))
    spec = P(ax_r, ax_c)
    fn = jax.shard_map(
        _summa_local(ax_r, ax_c, int(pr), int(pc), G, kp // G),
        mesh=mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False)
    with mesh:
        c_p = jax.jit(fn)(a_p, b_p)
    if cyclic:
        c_p = c_p[pm_inv][:, pn_inv]
    return c_p[:m, :n]


def matmul_cost(m: int, k: int, n: int, dtype) -> "cost_mod.Cost":
    """Analytic matmul cost: 2mnk FLOPs, one read of each operand and
    one write of the result."""
    itemsize = numpy.dtype(dtype).itemsize
    return cost_mod.Cost(
        flops=2.0 * m * n * k,
        bytes_accessed=float((m * k + k * n + m * n) * itemsize),
        source="analytic")


# ---------------------------------------------------------------------------
# right-looking blocked Cholesky + blocked triangular solve
# ---------------------------------------------------------------------------

def blocked_cholesky(a, block: int = DEFAULT_BLOCK, mesh=None,
                     mesh_min: int = 64):
    """Lower-triangular L with ``L @ L.T == a`` by right-looking blocked
    panels.

    Per panel k: dense potrf of the diagonal block, a triangular solve
    for the sub-diagonal panel, then the trailing SYRK update
    ``A22 -= L21 @ L21.T`` — routed through :func:`blocked_matmul`
    (and hence over ``mesh`` whenever the trailing size is at least
    ``mesh_min``, which is where the distribution enters; the panel
    factorization itself is small and stays on one device, the standard
    distributed-Cholesky split). Raises :class:`LinalgError` if ``a``
    is not positive definite. Records n³/3 FLOPs under
    ``linalg.cholesky``.
    """
    import jax
    jnp = _jnp()
    a = jnp.asarray(a)
    if a.ndim != 2 or a.shape[0] != a.shape[1]:
        raise LinalgError("cholesky needs a square matrix, got %r"
                          % (tuple(a.shape),))
    n = a.shape[0]
    with span("linalg.cholesky", n=n, block=block,
              mesh=(tuple(mesh.devices.shape) if mesh is not None
                    else None)):
        work = a
        for s in range(0, n, block):
            e = min(n, s + block)
            diag = _dispatch_block(work[s:e, s:e], op="potrf",
                                   panel=s // block)
            l_kk = jnp.linalg.cholesky(diag)
            work = work.at[s:e, s:e].set(l_kk)
            if e < n:
                # L21 = A21 @ L11^{-T}: one triangular solve per panel
                panel = jax.scipy.linalg.solve_triangular(
                    l_kk, jnp.swapaxes(work[e:, s:e], 0, 1),
                    lower=True).T
                work = work.at[e:, s:e].set(panel)
                upd = blocked_matmul(
                    panel, panel.T, block=block,
                    mesh=(mesh if mesh is not None and (n - e) >= mesh_min
                          else None))
                work = work.at[e:, e:].add(-upd)
        out = jnp.tril(work)
        if bool(jnp.any(jnp.isnan(out))):
            inc("veles_linalg_residual_failures_total")
            raise LinalgError(
                "cholesky: matrix is not positive definite (NaN panel)")
    cost_mod.model.record("linalg.cholesky", cholesky_cost(n, a.dtype))
    inc("veles_linalg_factorizations_total")
    return out


def cholesky_cost(n: int, dtype) -> "cost_mod.Cost":
    """Analytic potrf cost: n³/3 FLOPs, read+write of the matrix."""
    itemsize = numpy.dtype(dtype).itemsize
    return cost_mod.Cost(flops=n ** 3 / 3.0,
                         bytes_accessed=float(2 * n * n * itemsize),
                         source="analytic")


def blocked_triangular_solve(l, b, lower: bool = True,
                             block: int = DEFAULT_BLOCK):
    """Solve ``l @ x = b`` (or upper-triangular back-substitution when
    ``lower=False``) by blocked forward/backward substitution: per
    block row, subtract the already-solved block dots, then one small
    dense triangular solve."""
    import jax
    jnp = _jnp()
    l = jnp.asarray(l)
    b = jnp.asarray(b)
    vector = b.ndim == 1
    if vector:
        b = b[:, None]
    n = l.shape[0]
    x = jnp.zeros_like(b)
    ranges = list(range(0, n, block))
    if not lower:
        ranges = ranges[::-1]
    for s in ranges:
        e = min(n, s + block)
        if lower:
            rhs = b[s:e] - _dispatch_block(l[s:e, :s], op="trsm") @ x[:s]
        else:
            rhs = b[s:e] - _dispatch_block(l[s:e, e:], op="trsm") @ x[e:]
        x = x.at[s:e].set(jax.scipy.linalg.solve_triangular(
            l[s:e, s:e], rhs, lower=lower))
    return x[:, 0] if vector else x


def cholesky_solve(a, b, block: int = DEFAULT_BLOCK, mesh=None,
                   check: bool = True, tol: Optional[float] = None):
    """Solve SPD ``a @ x = b`` via blocked Cholesky + two blocked
    triangular solves. With ``check=True`` (default) the answer must
    pass :func:`verify_residual` before it is returned — a corrupted
    block op can therefore never produce a silently-wrong x."""
    l = blocked_cholesky(a, block=block, mesh=mesh)
    y = blocked_triangular_solve(l, b, lower=True, block=block)
    x = blocked_triangular_solve(l.T, y, lower=False, block=block)
    if check:
        verify_residual(a, x, b, tol=tol, what="linalg.cholesky_solve")
    inc("veles_linalg_solves_total")
    return x


# ---------------------------------------------------------------------------
# the trusted residual check
# ---------------------------------------------------------------------------

def verify_residual(operator: Union[Callable, object], x, b,
                    tol: Optional[float] = None,
                    what: str = "linalg.solve") -> float:
    """Relative residual ``|b - A x| / |b|`` of a proposed solution,
    raising :class:`LinalgError` when it exceeds ``tol``.

    THE trusted path of the family: a matrix operator is applied with a
    plain dense dot on the host — never through the faultable
    ``linalg.block_op`` dispatch — so an injected corruption in the
    solve cannot also corrupt its own acceptance check. Callable
    operators are applied as given (they are the caller's trusted
    definition of the problem). Returns the residual; every call is
    counted (``veles_linalg_residual_checks_total`` /
    ``_failures_total``).
    """
    xv = numpy.asarray(x, dtype=numpy.float64)
    bv = numpy.asarray(b, dtype=numpy.float64)
    if callable(operator):
        ax = numpy.asarray(operator(x), dtype=numpy.float64)
        dtype = numpy.asarray(x).dtype
    else:
        av = numpy.asarray(operator, dtype=numpy.float64)
        ax = av @ xv
        dtype = numpy.asarray(operator).dtype
    bound = residual_tolerance(dtype) if tol is None else float(tol)
    denom = float(numpy.linalg.norm(bv))
    resid = float(numpy.linalg.norm(bv - ax)) / (denom or 1.0)
    inc("veles_linalg_residual_checks_total")
    with span("linalg.residual_check", what=what, resid=resid,
              tol=bound):
        if not numpy.isfinite(resid) or resid > bound:
            inc("veles_linalg_residual_failures_total")
            raise LinalgError(
                "%s: residual check FAILED: |b-Ax|/|b| = %.3e > %.3e "
                "(corrupt block or ill-posed system; refusing to "
                "return x)" % (what, resid, bound))
    return resid


# ---------------------------------------------------------------------------
# the falsifiable SUMMA step-time model
# ---------------------------------------------------------------------------

def predict_summa_time(m: int, k: int, n: int, grid: Tuple[int, int],
                       t1_step_s: float, dtype=numpy.float32,
                       ici_bw: Optional[float] = None,
                       device_kind: Optional[str] = None) -> dict:
    """Predicted SUMMA step time on a (pr, pc) mesh, every input stated
    (the same falsifiability contract as
    ``resilience.elastic.predict_step_time`` / the PR 9 elastic row):

    ``t_pred = t1_step/N + psum_bytes/ici_bw`` where per-device psum
    traffic sums, over the G = lcm(pr, pc) panel steps, one ring
    all-reduce of the A panel along the row (2·(pc-1)/pc of its bytes)
    and one of the B panel along the column.
    """
    pr, pc = int(grid[0]), int(grid[1])
    n_dev = pr * pc
    G = pr * pc // math.gcd(pr, pc)
    itemsize = numpy.dtype(dtype).itemsize
    mp = G * -(-m // G)
    kp = G * -(-k // G)
    np_ = G * -(-n // G)
    w = kp // G
    a_panel_bytes = (mp // pr) * w * itemsize
    b_panel_bytes = w * (np_ // pc) * itemsize
    if ici_bw is None:
        ici_bw_source, ici_bw = cost_mod.ici_bandwidth_entry(device_kind)
    else:
        ici_bw_source = "caller"
    psum_bytes = G * (2.0 * (pc - 1) / pc * a_panel_bytes
                      + 2.0 * (pr - 1) / pr * b_panel_bytes)
    compute_s = t1_step_s / n_dev
    comm_s = psum_bytes / ici_bw
    return {
        "predicted_step_s": compute_s + comm_s,
        "compute_s": compute_s,
        "comm_s": comm_s,
        "inputs": {
            "t1_step_s": t1_step_s,
            "grid": [pr, pc],
            "panels": G,
            "block_bytes_a_panel": a_panel_bytes,
            "block_bytes_b_panel": b_panel_bytes,
            "psum_bytes_per_device": psum_bytes,
            "ici_bw_assumed_bytes_per_s": ici_bw,
            "ici_bw_source": ici_bw_source,
        },
    }
