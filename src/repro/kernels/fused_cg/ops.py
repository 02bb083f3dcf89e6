"""Planning and driver for the fused Pallas CG-step kernel.

``fused_cg_plan`` does the host-side work once per topology: a reverse
Cuthill-McKee node reordering (bounds the per-tile column window the
kernel gathers from), a row sort of the edges in the permuted space,
per-tile window measurement, and the ELL (padded row-major) arrays the
XLA form uses for a gather-only matvec.

``fused_cg_solve`` is the solver: batched Jacobi-preconditioned CG on
``A = diag(diag) - offdiag(gvals)`` with per-row live masks and
convergence stats (``CGStats``). It is one masked loop in two forms:

  * backend "pallas"/"interpret" — the outer ``while_loop`` body is ONE
    ``kernel.fused_cg_step_pallas`` launch;
  * backend "xla" — ``pcg_loop`` with the gather-only ELL matvec (no
    scatter, no segment-sum) and the Jacobi apply ``r / diag``; the CPU
    form and every f64 solve.

``pcg_loop`` is that masked PCG loop with a callable matvec and
preconditioner; the family dense tier (template Cholesky) and the FVM
stencil solve call it too.

NOTE: both forms are built on ``lax.while_loop``, so reverse-mode
AD cannot unroll them directly. STEADY solves are differentiable anyway
via the implicit-function-theorem wrapper in ``adjoint.py``
(:func:`repro.kernels.fused_cg.adjoint.make_implicit_steady`): the
backward pass is ONE extra fused CG solve of the self-adjoint system
plus an O(E) residual VJP — this is what takes ``peak_steady`` gradients
off the dense tier. Transient steppers still do not differentiate
through their inner CG; gradient transients ride the ROM rung's r x r
``scan`` instead (``core/optimize.py``).
"""
from __future__ import annotations

import dataclasses
import threading
import warnings
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import resolve_backend
from ..coo_matvec.ops import _round_up
from .kernel import LANE, SUBLANE, fused_cg_step_pallas

__all__ = [
    "CGStats", "FusedCGPlan", "all_finite", "fallback_counts",
    "fused_cg_block", "fused_cg_plan", "fused_cg_solve",
    "fused_cg_vmem_bytes", "pcg_loop", "record_fallback",
    "warn_unconverged", "unconverged_counts", "reset_unconverged_counts",
]


class CGStats(NamedTuple):
    """Per-solve convergence record (leading shape matches the rhs batch).

    iterations: int32, CG iterations each row spent live;
    residual: final RELATIVE residual ||r|| / ||b||;
    converged: bool, whether the row met tol before maxiter.
    """
    iterations: Any
    residual: Any
    converged: Any


def _rcm_order(rows: np.ndarray, cols: np.ndarray, n: int) -> np.ndarray:
    """Reverse Cuthill-McKee ordering (new -> old); identity if scipy is
    unavailable or the graph is empty. RCM keeps every edge tile's column
    footprint inside a narrow band, which is what makes the kernel's
    static gather window small."""
    if rows.size == 0:
        return np.arange(n, dtype=np.int32)
    try:
        from scipy.sparse import coo_matrix
        from scipy.sparse.csgraph import reverse_cuthill_mckee
    except Exception:  # pragma: no cover - scipy is a baked-in dep
        return np.arange(n, dtype=np.int32)
    adj = coo_matrix((np.ones(rows.size, np.float32), (rows, cols)),
                     shape=(n, n)).tocsr()
    perm = np.asarray(reverse_cuthill_mckee(adj, symmetric_mode=True),
                      dtype=np.int32)
    return perm


@dataclasses.dataclass(frozen=True, eq=False)
class FusedCGPlan:
    """Static per-topology plan for the fused CG kernel.

    Everything lives in the RCM-PERMUTED node space: ``node_perm`` maps
    new -> old (``x_p = x[..., node_perm]``) and ``node_inv`` undoes it
    (``x = x_p[..., node_inv]``). Edges are row-sorted in that space;
    ``edge_perm`` gathers original-order edge values into sorted order.
    ``rows2d`` holds ABSOLUTE sorted rows, ``cols2d`` holds columns
    RELATIVE to the owning tile's lane-aligned ``col_base``. The ELL
    arrays give the scatter-free matvec of the XLA form:
    ``offdiag(x) = sum_k (gvals[..., ell_src] * ell_mask) * x[..., ell_cols]``.
    """
    n: int
    n_edges: int
    block_edges: int
    row_span: int
    col_span: int
    n_pad: int
    e_pad: int
    n_tiles: int
    ell_k: int
    node_perm: jnp.ndarray   # (n,) int32, new -> old
    node_inv: jnp.ndarray    # (n,) int32, old -> new gather
    edge_perm: jnp.ndarray   # (E,) int32, original -> sorted gather
    rows_sorted: jnp.ndarray  # (E,) int32, absolute, permuted space
    cols_sorted: jnp.ndarray  # (E,) int32, absolute, permuted space
    rows2d: jnp.ndarray      # (e_pad, 1) int32
    cols2d: jnp.ndarray      # (e_pad, 1) int32, tile-relative
    col_base: jnp.ndarray    # (n_tiles,) int32, lane-aligned
    ell_cols: jnp.ndarray    # (n, ell_k) int32
    ell_src: jnp.ndarray     # (n, ell_k) int32 into ORIGINAL edge order
    ell_mask: jnp.ndarray    # (n, ell_k) bool


def fused_cg_plan(rows, cols, num_segments: int,
                  block_edges: int = 512) -> FusedCGPlan:
    """Build the fused-CG plan for one off-diagonal sparsity pattern."""
    rows = np.asarray(rows, dtype=np.int32).ravel()
    cols = np.asarray(cols, dtype=np.int32).ravel()
    if rows.shape != cols.shape:
        raise ValueError(f"rows/cols mismatch: {rows.shape} vs {cols.shape}")
    n = int(num_segments)
    e = int(rows.size)
    if e and (rows.min() < 0 or rows.max() >= n
              or cols.min() < 0 or cols.max() >= n):
        raise ValueError("edge endpoints out of range")

    perm = _rcm_order(rows, cols, n)                  # new -> old
    inv = np.argsort(perm).astype(np.int32)           # old -> new
    rp = inv[rows] if e else rows
    cp = inv[cols] if e else cols
    order = np.argsort(rp, kind="stable").astype(np.int32)
    rows_s = rp[order]
    cols_s = cp[order]

    e_pad = max(_round_up(e, block_edges), block_edges)
    n_tiles = e_pad // block_edges
    rows_p = np.concatenate(
        [rows_s, np.full(e_pad - e, rows_s[-1] if e else 0, np.int32)])
    cols_p = np.concatenate(
        [cols_s, np.full(e_pad - e, cols_s[-1] if e else 0, np.int32)])
    tiles_r = rows_p.reshape(n_tiles, block_edges)
    tiles_c = cols_p.reshape(n_tiles, block_edges)
    # row window: distance from the tile's lane-aligned first row to its
    # last row (rows are sorted, so min/max are the tile ends)
    r_width = tiles_r[:, -1] - (tiles_r[:, 0] // LANE) * LANE + 1
    row_span = int(_round_up(int(r_width.max()), LANE))
    # column window: lane-aligned floor of the tile's min column
    col_base = ((tiles_c.min(axis=1) // LANE) * LANE).astype(np.int32)
    c_width = tiles_c.max(axis=1) - col_base + 1
    col_span = int(_round_up(int(c_width.max()), LANE))
    cols_rel = (tiles_c - col_base[:, None]).reshape(e_pad).astype(np.int32)
    n_pad = _round_up(n, LANE) + max(row_span, col_span)

    # ELL arrays (permuted node space, gathers into ORIGINAL edge order)
    ell_k = 1
    ell_cols = np.zeros((n, 1), np.int32)
    ell_src = np.zeros((n, 1), np.int32)
    ell_mask = np.zeros((n, 1), bool)
    if e:
        deg = np.bincount(rows_s, minlength=n)
        ell_k = int(deg.max())
        starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
        pos = np.arange(e) - starts[rows_s]
        ell_cols = np.zeros((n, ell_k), np.int32)
        ell_src = np.zeros((n, ell_k), np.int32)
        ell_mask = np.zeros((n, ell_k), bool)
        ell_cols[rows_s, pos] = cols_s
        ell_src[rows_s, pos] = order
        ell_mask[rows_s, pos] = True

    # The plan is host-built but CACHED by callers (lazy `_fused_plan`
    # properties), and first touch routinely happens inside a jit trace:
    # force the device conversions to compile-time constants, or the
    # cached plan would hold that trace's device_put tracers and leak
    # them into every later trace (bit us when the implicit-adjoint
    # backward pass first ran under grad-of-jit).
    with jax.ensure_compile_time_eval():
        return _freeze_plan(n, e, block_edges, row_span, col_span, n_pad,
                            e_pad, n_tiles, ell_k, perm, inv, order,
                            rows_s, cols_s, rows_p, cols_rel, col_base,
                            ell_cols, ell_src, ell_mask)


def _freeze_plan(n, e, block_edges, row_span, col_span, n_pad, e_pad,
                 n_tiles, ell_k, perm, inv, order, rows_s, cols_s, rows_p,
                 cols_rel, col_base, ell_cols, ell_src, ell_mask):
    as_i32 = lambda a: jnp.asarray(a, jnp.int32)
    return FusedCGPlan(
        n=n, n_edges=e, block_edges=block_edges, row_span=row_span,
        col_span=col_span, n_pad=n_pad, e_pad=e_pad, n_tiles=n_tiles,
        ell_k=ell_k,
        node_perm=as_i32(perm), node_inv=as_i32(inv),
        edge_perm=as_i32(order),
        rows_sorted=as_i32(rows_s), cols_sorted=as_i32(cols_s),
        rows2d=as_i32(rows_p[:, None]), cols2d=as_i32(cols_rel[:, None]),
        col_base=as_i32(col_base),
        ell_cols=as_i32(ell_cols), ell_src=as_i32(ell_src),
        ell_mask=jnp.asarray(ell_mask),
    )


# Largest batch block of the fused kernel: two passes of the v5e's
# 128-row MXU, so every one-hot tile built and every weight load serves
# up to 256 rows. On a v5e at B = 2048, 256 ran the kernel 2-5% faster
# than 128 on the Table-6 sweep plans (PERF.md, section 6).
BLOCK_CAP = 256
# VMEM the block's buffers may take (``fused_cg_vmem_bytes``). The limit
# handed to the compiler adds room for the epilogue's temporaries, never
# goes below the compiler's default scoped limit (16 MiB on v5e) and stays
# well inside the v5e core's 128 MiB.
VMEM_BUDGET = 48 << 20
_VMEM_MARGIN = 1.5
_VMEM_FLOOR = 16 << 20
_VMEM_CEIL = 100 << 20


def fused_cg_vmem_bytes(block: int, plan: FusedCGPlan, itemsize: int) -> int:
    """VMEM bytes of one fused-CG launch at batch block ``block``: the
    seven double-buffered (block, n_pad) state blocks (diag, x, r, p in;
    x, r, p out), the f32 ``ap`` scratch, the double-buffered gvals tile
    and the gather and scatter one-hot temporaries."""
    be = plan.block_edges
    state = 7 * 2 * block * plan.n_pad * itemsize
    ap = block * plan.n_pad * max(itemsize, 4)
    gvals = 2 * block * be * itemsize
    onehots = be * (plan.col_span + plan.row_span) * itemsize
    return state + ap + gvals + onehots


def fused_cg_block(b: int, plan: FusedCGPlan, itemsize: int) -> tuple:
    """``(block, vmem_limit_bytes)`` of the fused kernel for a batch of
    ``b`` rows: the largest multiple of SUBLANE that divides
    ``round_up(b, SUBLANE)``, is at most ``BLOCK_CAP`` and whose
    footprint fits ``VMEM_BUDGET`` (SUBLANE at the least). Dividing,
    not padding, computes no extra rows; b <= SUBLANE gives SUBLANE."""
    b_pad = _round_up(b, SUBLANE)
    block = max(
        (r for r in range(SUBLANE, min(b_pad, BLOCK_CAP) + 1, SUBLANE)
         if b_pad % r == 0
         and fused_cg_vmem_bytes(r, plan, itemsize) <= VMEM_BUDGET),
        default=SUBLANE)
    need = int(_VMEM_MARGIN * fused_cg_vmem_bytes(block, plan, itemsize))
    return block, min(max(need, _VMEM_FLOOR), _VMEM_CEIL)


def _ell_matvec(plan: FusedCGPlan, gvals: jnp.ndarray) -> Callable:
    """Gather-only ELL off-diagonal matvec ``x -> offdiag(gvals) x`` in the
    plan's permuted node space: no scatter, no segment-sum. The masked
    (..., N, K) values are formed here, once, outside any loop."""
    gv_ell = ((gvals[..., plan.ell_src] * plan.ell_mask.astype(gvals.dtype))
              if plan.n_edges else
              jnp.zeros(gvals.shape[:-1] + (plan.n, 1), gvals.dtype))
    return lambda x: jnp.sum(gv_ell * x[..., plan.ell_cols], axis=-1)


def _live_threshold(rhs, tol):
    """``(bnorm2g, tol2b)``: ||b||^2 per row (1 where b = 0) and the
    live-row threshold tol^2 ||b||^2 on ||r||^2."""
    bnorm2 = jnp.sum(rhs * rhs, axis=1)
    bnorm2g = jnp.where(bnorm2 == 0, 1.0, bnorm2)
    return bnorm2g, jnp.asarray(tol, rhs.dtype) ** 2 * bnorm2g


def _cg_stats(itr, rn2, bnorm2g, tol2b) -> CGStats:
    return CGStats(iterations=itr, residual=jnp.sqrt(rn2 / bnorm2g),
                   converged=rn2 <= tol2b)


def _solve2d(plan: FusedCGPlan, diag, gvals, rhs, x0, *, tol, maxiter,
             backend):
    """Batched Jacobi PCG on (B, N) operands in permuted space: ``pcg_loop``
    on the ELL matvec for backend "xla", else one fused kernel launch per
    iteration."""
    if backend == "xla":
        offmv = _ell_matvec(plan, gvals)
        return pcg_loop(lambda p: diag * p - offmv(p), lambda r: r / diag,
                        rhs, x0, tol, maxiter)

    dtype = rhs.dtype
    b, n = rhs.shape
    bnorm2g, tol2b = _live_threshold(rhs, tol)
    gv_sorted = gvals[..., plan.edge_perm]
    offmv = _ell_matvec(plan, gvals)
    r0 = rhs - (diag * x0 - offmv(x0))
    z0 = r0 / diag
    rz0 = jnp.sum(r0 * z0, axis=1)
    rn20 = jnp.sum(r0 * r0, axis=1)
    it0 = jnp.zeros((b,), jnp.int32)

    block_b, vmem_limit = fused_cg_block(b, plan, dtype.itemsize)
    b_pad = _round_up(b, block_b)
    n_pad = plan.n_pad

    def padn(a, v=0.0):
        return jnp.pad(a, ((0, b_pad - b), (0, n_pad - n)),
                       constant_values=v)

    def pad1(a, v=0):
        return jnp.pad(a[:, None], ((0, b_pad - b), (0, 0)),
                       constant_values=v)

    gv_p = jnp.pad(jnp.broadcast_to(gv_sorted, (b, plan.n_edges)),
                   ((0, b_pad - b), (0, plan.e_pad - plan.n_edges)))
    diag_p = padn(diag, 1.0)
    tol_p = pad1(tol2b, 1)  # padded rows never live (rn2 = 0 < 1)

    def step(x, r, p, rz, rn2, itr):
        return fused_cg_step_pallas(
            plan.col_base, plan.rows2d, plan.cols2d, gv_p, diag_p,
            x, r, p, rz, rn2, itr, tol_p,
            row_span=plan.row_span, col_span=plan.col_span,
            be=plan.block_edges, block_b=block_b,
            vmem_limit_bytes=vmem_limit,
            interpret=backend == "interpret")

    def cond(s):
        it, _, _, _, _, rn2, _ = s
        return (it < maxiter) & jnp.any(rn2 > tol_p)

    def body(s):
        it, x, r, p, rz, rn2, itr = s
        x, r, p, rz, rn2, itr = step(x, r, p, rz, rn2, itr)
        return it + 1, x, r, p, rz, rn2, itr

    init = (jnp.asarray(0), padn(x0), padn(r0), padn(z0),
            pad1(rz0), pad1(rn20), pad1(it0))
    _, x, _, _, _, rn2, itr = jax.lax.while_loop(cond, body, init)
    x, rn2, itr = x[:b, :n], rn2[:b, 0], itr[:b, 0]
    return x, _cg_stats(itr, rn2, bnorm2g, tol2b)


def fused_cg_solve(plan: FusedCGPlan, diag, gvals, rhs, x0=None, *,
                   tol: float, maxiter: int, backend: str = "auto"):
    """Solve ``(diag(diag) - offdiag(gvals)) x = rhs`` by Jacobi PCG.

    diag (..., N) positive; gvals (..., E) POSITIVE pairwise conductances
    (the off-diagonal magnitude being subtracted); rhs (..., N); leading
    axes broadcast. Returns ``(x, CGStats)`` with x matching the
    broadcast leading shape. ``backend``: "auto" | "pallas" |
    "interpret" | "xla" (see ``kernels/backend.py``).
    """
    n, e = plan.n, plan.n_edges
    diag = jnp.asarray(diag)
    gvals = jnp.asarray(gvals)
    rhs = jnp.asarray(rhs)
    dtype = rhs.dtype
    backend = resolve_backend(backend, dtype)
    if e == 0 and backend in ("pallas", "interpret"):
        backend = "xla"  # no tiles worth launching
    lead = jnp.broadcast_shapes(
        diag.shape[:-1], gvals.shape[:-1], rhs.shape[:-1],
        () if x0 is None else jnp.shape(x0)[:-1])

    def flat(a, last):
        a = jnp.broadcast_to(jnp.asarray(a, dtype), lead + (last,))
        return a.reshape((-1, last))

    d2 = flat(diag, n)[:, plan.node_perm]
    b2 = flat(rhs, n)[:, plan.node_perm]
    x02 = (jnp.zeros_like(b2) if x0 is None
           else flat(x0, n)[:, plan.node_perm])
    # reshape((-1, 0)) is ill-posed, so size the empty-edge case off b2
    g2 = flat(gvals, e) if e else jnp.zeros((b2.shape[0], 0), dtype)
    xp, stats = _solve2d(plan, d2, g2, b2, x02, tol=tol, maxiter=maxiter,
                         backend=backend)
    x = xp[:, plan.node_inv].reshape(lead + (n,))
    return x, CGStats(*(s.reshape(lead) for s in stats))


def pcg_loop(matvec: Callable, prec: Callable, rhs, x0, tol: float,
             maxiter: int):
    """Masked batched PCG with callable matvec/preconditioner.

    Operands are (B, N); a row is live while ``||r||^2 > tol^2 ||b||^2``,
    and frozen rows get alpha = beta = 0 and coast unchanged. Returns
    ``(x, CGStats)`` with (B,)-shaped stats. The one CG loop outside the
    fused kernel; its callers: ``fused_cg_solve``'s XLA form (ELL matvec,
    Jacobi), the family dense tier (``RCFamilyModel._pcg``, template
    Cholesky) and the FVM's ``stencil_pcg``.
    """
    rhs = jnp.asarray(rhs)
    bnorm2g, tol2b = _live_threshold(rhs, tol)

    def cond(s):
        it, _, _, _, _, rn2, _ = s
        return (it < maxiter) & jnp.any(rn2 > tol2b)

    def body(s):
        it, x, r, p, rz, rn2, itr = s
        ap = matvec(p)
        live = rn2 > tol2b
        denom = jnp.sum(p * ap, axis=1)
        alpha = jnp.where(live,
                          rz / jnp.where(denom == 0, 1.0, denom), 0.0)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * ap
        z = prec(r)
        rz_new = jnp.sum(r * z, axis=1)
        beta = jnp.where(live,
                         rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        p = z + beta[:, None] * p
        return (it + 1, x, r, p, rz_new, jnp.sum(r * r, axis=1),
                itr + live.astype(jnp.int32))

    r0 = rhs - matvec(x0)
    z0 = prec(r0)
    init = (jnp.asarray(0), x0, r0, z0, jnp.sum(r0 * z0, axis=1),
            jnp.sum(r0 * r0, axis=1), jnp.zeros(rhs.shape[0], jnp.int32))
    _, x, _, _, _, rn2, itr = jax.lax.while_loop(cond, body, init)
    return x, _cg_stats(itr, rn2, bnorm2g, tol2b)


# Per-solve-site dedup state for warn_unconverged: a high-QPS serving
# loop re-running one unconverged configuration must not emit thousands
# of identical RuntimeWarnings. Each site (the ``where`` string) warns
# ONCE per process; every further hit only bumps its counter, which the
# serving telemetry (``serving/telemetry.py``) surfaces in snapshots.
# All of this state is shared across serving worker / supervisor /
# client threads, so every touch goes through one lock — snapshot and
# reset included (a torn read under concurrent solves would leak into
# BENCH numbers).
_SITE_LOCK = threading.Lock()
_UNCONVERGED_COUNTS: dict = {}
_WARNED_SITES: set = set()
# Numerical-guardrail registry: every NaN/Inf detection that promoted a
# solve to its dense/reference path records the site here (the
# structured ``fallback`` record's process-wide counterpart; surfaced
# by telemetry snapshots next to the unconverged counters).
_FALLBACK_COUNTS: dict = {}


def unconverged_counts() -> dict:
    """Snapshot of ``{solve site: number of unconverged solve CALLS}``
    accumulated since process start (or the last reset). A "call" is one
    ``warn_unconverged`` invocation whose stats contain any
    iteration-cap hit — the rate-limited counterpart of the one-shot
    warning. Thread-safe."""
    with _SITE_LOCK:
        return dict(_UNCONVERGED_COUNTS)


def reset_unconverged_counts() -> None:
    """Clear the per-site counters AND re-arm the one-shot warnings
    (tests of the warning path call this first). Thread-safe; also
    clears the numerical-fallback counters."""
    with _SITE_LOCK:
        _UNCONVERGED_COUNTS.clear()
        _WARNED_SITES.clear()
        _FALLBACK_COUNTS.clear()


def record_fallback(site: str) -> None:
    """Count one guardrail promotion (NaN/Inf solve output replaced by
    the dense/reference path) at ``site``. Thread-safe."""
    with _SITE_LOCK:
        _FALLBACK_COUNTS[site] = _FALLBACK_COUNTS.get(site, 0) + 1


def fallback_counts() -> dict:
    """Snapshot of ``{site: guardrail promotions}`` since process start
    (or the last :func:`reset_unconverged_counts`). Thread-safe."""
    with _SITE_LOCK:
        return dict(_FALLBACK_COUNTS)


def all_finite(x) -> bool:
    """Host-side NaN/Inf guard on a solve output. True for traced
    values (convergence of a tracer is undecidable here — callers
    guard at materialization boundaries instead)."""
    if isinstance(x, jax.core.Tracer):
        return True
    return bool(np.isfinite(np.asarray(x)).all())


def warn_unconverged(stats: Optional[CGStats], where: str) -> None:
    """Host-side post-solve check: warn if any solve hit maxiter.

    Safe to call with traced stats (inside jit/vmap): silently returns,
    since convergence can only be inspected on concrete values.

    Rate-limited: each solve site warns once per process; subsequent
    unconverged calls at the same site are counted silently
    (:func:`unconverged_counts`), keeping serving loops quiet.
    """
    if stats is None or isinstance(stats.converged, jax.core.Tracer):
        return
    conv = np.asarray(stats.converged)
    if conv.all():
        return
    with _SITE_LOCK:
        _UNCONVERGED_COUNTS[where] = _UNCONVERGED_COUNTS.get(where, 0) + 1
        if where in _WARNED_SITES:
            return
        _WARNED_SITES.add(where)
    res = np.asarray(stats.residual)
    its = np.asarray(stats.iterations)
    bad = int(conv.size - conv.sum())
    warnings.warn(
        f"{where}: {bad}/{conv.size} CG solve(s) hit the iteration cap "
        f"(max {int(its.max())} iterations, worst relative residual "
        f"{float(res.max()):.3e}); results may be unconverged — raise "
        "cg_maxiter or loosen cg_tol. (Warned once per site; further "
        "occurrences are counted — see unconverged_counts().)",
        RuntimeWarning, stacklevel=3)
