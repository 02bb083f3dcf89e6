"""Implicit-adjoint steady solve: reverse-mode AD through the fused CG tier.

The fused CG paths (``ops.fused_cg_solve``) run the whole solve inside a
``lax.while_loop`` whose trip count is convergence-dependent — reverse-mode
AD cannot unroll it, which historically pinned every gradient workload to
the dense O(N^3) tier. This module removes that restriction for STEADY
solves using the implicit function theorem instead of differentiating the
iteration:

    A(p) x*(p) = rhs(p),        A = diag(diag) - offdiag(gvals)  (SPD)
    dL/dp = lambda' drhs/dp - lambda' (dA/dp) x*,  A lambda = dL/dx*

``A`` is symmetric, so the adjoint system is solved by the SAME fused CG
kernel as the forward pass — the backward pass costs exactly ONE extra CG
solve (per candidate row), not ``maxiter`` unrolled iterations, and the
remaining cotangents are O(E) elementwise products over the frozen edge
pattern. The O(E) residual ``d(Ax - rhs)/dparams`` then VJPs through the
pure-jax numeric assembly phase like any other jax code.

:func:`make_implicit_steady` builds a ``jax.custom_vjp``-wrapped solver
closure over one :class:`~.ops.FusedCGPlan` + solver configuration; it
composes with ``jax.vmap`` / ``jax.jit`` / ``shard_map`` (the
``FamilyExecutor`` paths), so multi-start gradient batches ride mesh
sharding and chunk streaming like any sweep.

Solve stats: ``CGStats`` cannot ride the custom_vjp output (a stats
cotangent is meaningless), so both directions report through a host-side
registry instead — ``jax.debug.callback`` lands each solve's concrete
stats under its site name (:func:`last_stats`, :func:`solve_counts`) and
runs the same :func:`~.ops.warn_unconverged` iteration-cap discipline as
the forward solvers. ``rows`` in :func:`solve_counts` counts per-candidate
row solves, which is how tests pin "one adjoint solve per backward pass".
"""
from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .ops import CGStats, FusedCGPlan, fused_cg_solve, warn_unconverged

__all__ = [
    "adjoint_offdiag_matvec", "last_stats", "make_implicit_steady",
    "reset_adjoint_stats", "solve_counts",
]

# Host-side stats registry: {site: {"calls", "rows", "stats": CGStats}}.
# Shared by optimizer loops / BENCH / tests across threads, so every
# touch takes the lock (the serving oracle may drive gradient solves
# from its worker thread while a client reads counters).
_ADJ_LOCK = threading.Lock()
_ADJ_STATS: dict = {}


def last_stats(site: str) -> Optional[CGStats]:
    """Most recent concrete :class:`CGStats` recorded at ``site`` (host
    numpy leaves; leading shape = that solve's batch), or None."""
    jax.effects_barrier()  # debug.callback is async: flush pending emits
    with _ADJ_LOCK:
        rec = _ADJ_STATS.get(site)
        return rec["stats"] if rec else None


def solve_counts() -> dict:
    """Snapshot ``{site: {"calls": n, "rows": m}}`` since process start
    (or the last reset): ``calls`` counts recorded solve events, ``rows``
    the per-candidate row solves they contained — backward passes cost
    exactly one adjoint row solve per candidate, which is what BENCH and
    the grad tests assert with this counter."""
    jax.effects_barrier()  # debug.callback is async: flush pending emits
    with _ADJ_LOCK:
        return {k: {"calls": v["calls"], "rows": v["rows"]}
                for k, v in _ADJ_STATS.items()}


def reset_adjoint_stats() -> None:
    """Clear the registry (tests/BENCH call this before a measured run)."""
    with _ADJ_LOCK:
        _ADJ_STATS.clear()


def _record(site: str, iterations, residual, converged) -> None:
    stats = CGStats(iterations=np.asarray(iterations),
                    residual=np.asarray(residual),
                    converged=np.asarray(converged))
    with _ADJ_LOCK:
        rec = _ADJ_STATS.setdefault(site, {"calls": 0, "rows": 0,
                                           "stats": None})
        rec["calls"] += 1
        rec["rows"] += int(stats.converged.size)
        rec["stats"] = stats
    warn_unconverged(stats, site)


def _emit(site: str, stats: CGStats) -> None:
    """Land a traced solve's stats on the host registry. debug.callback
    works under jit/vmap/shard_map and sees concrete values at run time;
    unordered is fine — the registry is an accumulator."""
    jax.debug.callback(functools.partial(_record, site),
                       stats.iterations, stats.residual, stats.converged)


def adjoint_offdiag_matvec(plan: FusedCGPlan, gvals, x):
    """Off-diagonal matvec in the ORIGINAL node/edge order (the numeric
    phase's space): ``out[i] = sum_e gvals[e] x[cols[e]] (rows[e]==i)``.

    Built from differentiable gather/segment-sum pieces (no while_loop),
    so its ``jax.vjp`` yields the O(E) edge cotangent the implicit
    backward pass needs. Leading axes broadcast like the fused solver's.
    """
    if plan.n_edges == 0:
        return jnp.zeros_like(x)
    contrib = (gvals[..., plan.edge_perm]
               * x[..., plan.node_perm][..., plan.cols_sorted])
    out = jax.ops.segment_sum(jnp.moveaxis(contrib, -1, 0),
                              plan.rows_sorted, num_segments=plan.n,
                              indices_are_sorted=True)
    return jnp.moveaxis(out, 0, -1)[..., plan.node_inv]


def make_implicit_steady(plan: FusedCGPlan, *, tol: float, maxiter: int,
                         backend: str = "auto",
                         site: str = "implicit steady adjoint CG"):
    """Build a reverse-differentiable matrix-free steady solver.

    Returns ``solve(diag, gvals, rhs) -> x`` with
    ``(diag(diag) - offdiag(gvals)) x = rhs``: the primal/forward pass is
    the unmodified fused-CG ``while_loop`` (one kernel launch per
    iteration); the backward pass solves the self-adjoint system
    ``A lambda = ct`` with the SAME fused kernel and assembles the input
    cotangents from the O(E) residual —

        ct_rhs   = lambda
        ct_diag  = -lambda * x
        ct_gvals = +lambda[rows] * x[cols]   (via vjp of the edge matvec)

    Leading (batch) axes of ``diag``/``gvals``/``rhs`` must match (no
    implicit broadcast on the differentiable path — cotangent shapes
    equal primal shapes). Stats from both directions land on the host
    registry under ``site`` / ``site + " [forward]"`` with the standard
    ``warn_unconverged`` iteration-cap warning.
    """
    fwd_site = site + " [forward]"

    def _solve(diag, gvals, rhs):
        return fused_cg_solve(plan, diag, gvals, rhs, tol=tol,
                              maxiter=maxiter, backend=backend)

    @jax.custom_vjp
    def solve(diag, gvals, rhs):
        x, stats = _solve(diag, gvals, rhs)
        _emit(fwd_site, stats)
        return x

    def solve_fwd(diag, gvals, rhs):
        x, stats = _solve(diag, gvals, rhs)
        _emit(fwd_site, stats)
        return x, (diag, gvals, x)

    def solve_bwd(res, ct):
        diag, gvals, x = res
        # ONE adjoint solve: A is symmetric, so the transposed system
        # reuses the forward kernel (same plan, same Jacobi diag).
        lam, stats = _solve(diag, gvals, ct)
        _emit(site, stats)

        def apply_a(d, g):  # A(d, g) @ x at FIXED x — pure jax, O(E)
            return d * x - adjoint_offdiag_matvec(plan, g, x)

        _, residual_vjp = jax.vjp(apply_a, diag, gvals)
        ct_diag, ct_gvals = residual_vjp(-lam)
        return ct_diag, ct_gvals, lam

    solve.defvjp(solve_fwd, solve_bwd)
    return solve
