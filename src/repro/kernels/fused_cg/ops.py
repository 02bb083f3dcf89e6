"""Planning and driver for the fused Pallas CG-step kernel.

``fused_cg_plan`` does the host-side work once per topology: a reverse
Cuthill-McKee node reordering (bounds the per-tile column window the
kernel gathers from), a row sort of the edges in the permuted space,
per-tile window measurement, and the ELL (padded row-major) arrays the
fused-XLA fallback uses for a gather-only matvec on CPU.

``fused_cg_solve`` is the solver: batched Jacobi-preconditioned CG on
``A = diag(diag) - offdiag(gvals)`` with the EXACT masked-row semantics
of the historical ``_batched_pcg`` loop in ``core/rc_model.py``, plus
per-row convergence stats (``CGStats``). Three implementations share it:

  * ``impl="fused"``, backend "pallas"/"interpret" — the outer
    ``while_loop`` body is ONE ``kernel.fused_cg_step_pallas`` launch;
  * ``impl="fused"``, backend "xla" — one fused XLA ``while_loop`` whose
    matvec is the gather-only ELL form (no scatter, no segment-sum);
    this is the CPU/CI default and is itself far faster than the
    historical composition;
  * ``impl="unfused"`` — the historical one-op-per-piece loop
    (``jax.ops.segment_sum`` matvec), kept as the A/B contrast and
    escape hatch.

``pcg_loop`` is the generic masked PCG loop with callable matvec /
preconditioner (used by the dense-tier family solver with its template
Cholesky preconditioner); it returns the same ``CGStats``.

NOTE: the fused paths are built on ``lax.while_loop``, so reverse-mode
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

from ..coo_matvec.kernel import coo_segment_sum_sorted
from ..backend import resolve_backend
from ..coo_matvec.ops import _round_up
from .kernel import LANE, SUBLANE, fused_cg_step_pallas

__all__ = [
    "CGStats", "FusedCGPlan", "all_finite", "fallback_counts",
    "fused_cg_plan", "fused_cg_solve", "pcg_loop", "record_fallback",
    "resolve_cg_impl", "warn_unconverged", "unconverged_counts",
    "reset_unconverged_counts",
]

_CG_IMPLS = ("auto", "fused", "unfused")


class CGStats(NamedTuple):
    """Per-solve convergence record (leading shape matches the rhs batch).

    iterations: int32, CG iterations each row spent live;
    residual: final RELATIVE residual ||r|| / ||b||;
    converged: bool, whether the row met tol before maxiter.
    """
    iterations: Any
    residual: Any
    converged: Any


def resolve_cg_impl(impl: str) -> str:
    """'auto' -> 'fused' (every backend has a fused form: the Pallas
    kernel on TPU, the ELL while_loop on CPU); validate otherwise."""
    if impl not in _CG_IMPLS:
        raise ValueError(f"cg_impl must be one of {_CG_IMPLS}, got {impl!r}")
    return "fused" if impl == "auto" else impl


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
    arrays give the scatter-free matvec for the fused-XLA fallback:
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


# --------------------------------------------------------------------------
# matvec forms (all in the plan's permuted node space)

def _offdiag_ell(plan: FusedCGPlan, gv_ell: jnp.ndarray,
                 x: jnp.ndarray) -> jnp.ndarray:
    """Gather-only ELL matvec: gv_ell (..., N, K) pre-masked values."""
    return jnp.sum(gv_ell * x[..., plan.ell_cols], axis=-1)


def _offdiag_segsum(plan: FusedCGPlan, gv_sorted: jnp.ndarray,
                    x: jnp.ndarray) -> jnp.ndarray:
    """Historical composition: gather + ``jax.ops.segment_sum``."""
    if plan.n_edges == 0:
        return jnp.zeros_like(x)
    contrib = gv_sorted * x[..., plan.cols_sorted]
    flat = jnp.moveaxis(contrib, -1, 0)
    out = jax.ops.segment_sum(flat, plan.rows_sorted,
                              num_segments=plan.n, indices_are_sorted=True)
    return jnp.moveaxis(out, 0, -1)


def _offdiag_coo_kernel(plan: FusedCGPlan, gv_sorted: jnp.ndarray,
                        x: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """Unfused-on-device contrast: one ``coo_matvec`` kernel launch per
    matvec (plus separate XLA ops for everything else in the CG body)."""
    b, n = x.shape
    contrib = gv_sorted * x[:, plan.cols_sorted]
    b_pad = _round_up(b, SUBLANE)
    vals = jnp.pad(contrib, ((0, b_pad - b), (0, plan.e_pad - plan.n_edges)))
    out = coo_segment_sum_sorted(vals, plan.rows2d, n_pad=plan.n_pad,
                                 span=plan.row_span, be=plan.block_edges,
                                 interpret=interpret)
    return out[:b, :n]


def _solve2d(plan: FusedCGPlan, diag, gvals, rhs, x0, *, tol, maxiter,
             impl, backend, block_b):
    """Batched Jacobi PCG on (B, N) operands in permuted space."""
    dtype = rhs.dtype
    b, n = rhs.shape
    bnorm2 = jnp.sum(rhs * rhs, axis=1)
    bnorm2g = jnp.where(bnorm2 == 0, 1.0, bnorm2)
    tol2b = jnp.asarray(tol, dtype) ** 2 * bnorm2g

    gv_sorted = gvals[..., plan.edge_perm]

    use_pallas = impl == "fused" and backend in ("pallas", "interpret")
    if impl == "fused":
        # the ELL gather beats gather+segment_sum at every batch width
        # measured on this container (35-49x at B<=8, ~1.3x at B=256)
        gv_ell = ((gvals[..., plan.ell_src]
                   * plan.ell_mask.astype(dtype)) if plan.n_edges else
                  jnp.zeros(gvals.shape[:-1] + (n, 1), dtype))
        offmv = lambda x: _offdiag_ell(plan, gv_ell, x)
    elif backend in ("pallas", "interpret"):
        offmv = lambda x: _offdiag_coo_kernel(plan, gv_sorted, x,
                                              backend == "interpret")
    else:
        offmv = lambda x: _offdiag_segsum(plan, gv_sorted, x)

    r0 = rhs - (diag * x0 - offmv(x0))
    z0 = r0 / diag
    rz0 = jnp.sum(r0 * z0, axis=1)
    rn20 = jnp.sum(r0 * r0, axis=1)
    it0 = jnp.zeros((b,), jnp.int32)

    if use_pallas:
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
        x = x[:b, :n]
        rn2 = rn2[:b, 0]
        itr = itr[:b, 0]
    else:
        def matvec(p):
            return diag * p - offmv(p)

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
            z = r / diag
            rz_new = jnp.sum(r * z, axis=1)
            beta = jnp.where(live,
                             rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
            p = z + beta[:, None] * p
            return (it + 1, x, r, p, rz_new, jnp.sum(r * r, axis=1),
                    itr + live.astype(jnp.int32))

        init = (jnp.asarray(0), x0, r0, z0, rz0, rn20, it0)
        _, x, _, _, _, rn2, itr = jax.lax.while_loop(cond, body, init)

    stats = CGStats(iterations=itr,
                    residual=jnp.sqrt(rn2 / bnorm2g),
                    converged=rn2 <= tol2b)
    return x, stats


def fused_cg_solve(plan: FusedCGPlan, diag, gvals, rhs, x0=None, *,
                   tol: float, maxiter: int, impl: str = "auto",
                   backend: str = "auto", block_b: int = SUBLANE):
    """Solve ``(diag(diag) - offdiag(gvals)) x = rhs`` by Jacobi PCG.

    diag (..., N) positive; gvals (..., E) POSITIVE pairwise conductances
    (the off-diagonal magnitude being subtracted); rhs (..., N); leading
    axes broadcast. Returns ``(x, CGStats)`` with x matching the
    broadcast leading shape. ``impl``: "auto" | "fused" | "unfused";
    ``backend``: "auto" | "pallas" | "interpret" | "xla" (see
    ``kernels/backend.py``).
    """
    impl = resolve_cg_impl(impl)
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
                         impl=impl, backend=backend, block_b=block_b)
    x = xp[:, plan.node_inv].reshape(lead + (n,))
    return x, CGStats(*(s.reshape(lead) for s in stats))


def pcg_loop(matvec: Callable, prec: Callable, rhs, x0, tol: float,
             maxiter: int):
    """Generic masked batched PCG with callable matvec/preconditioner.

    Operands are (B, N); per-row live masks freeze converged rows exactly
    as the historical ``_batched_pcg``. Returns ``(x, CGStats)`` with
    (B,)-shaped stats. Used where the preconditioner is NOT Jacobi (the
    family dense tier's template Cholesky).
    """
    rhs = jnp.asarray(rhs)
    bnorm2 = jnp.sum(rhs * rhs, axis=1)
    bnorm2g = jnp.where(bnorm2 == 0, 1.0, bnorm2)
    tol2b = jnp.asarray(tol, rhs.dtype) ** 2 * bnorm2g

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
    return x, CGStats(iterations=itr,
                      residual=jnp.sqrt(rn2 / bnorm2g),
                      converged=rn2 <= tol2b)


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
