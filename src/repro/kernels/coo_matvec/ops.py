"""Public wrappers for the COO matvec kernel: host-side planning + dispatch.

The edge pattern of an RC network is static per model, so everything the
kernel needs beyond the traced values — the row sort, padding geometry,
and the per-tile row-window bound — is computed ONCE on the host into a
:class:`COOPlan` and captured by the solver's jitted closures. The traced
entry points then work on values only:

    plan = coo_plan(net.rows, net.cols, net.n)
    y = coo_matvec(plan, gvals, x)        # segsum(gvals * x[cols]) by row
    s = coo_segment_sum(plan, vals)       # segsum(vals) by row

Both accept arbitrary leading batch axes ((B, E) edge values against
(B, N) states, or broadcast combinations) — the batch rides the GEMM
sublane dimension of the kernel, so the family solvers need no vmap
around the matvec.

This batch axis also composes with the MESH-SHARDED candidate batch of
``distribution/family_exec.py``: family solvers run inside ``shard_map``
blocks where the :class:`COOPlan` is a closure constant (replicated to
every shard — the plan describes the topology, which is identical for
all candidates) and the local ``B/k`` batch slice rides the leading axes
here exactly as the unsharded batch would. Each shard therefore issues
its own per-shard kernel launches over its own candidates; no edge of
any candidate's network ever crosses a device boundary, and no
re-planning happens per shard (verified by the mesh-parity tests in
``tests/test_family_exec.py``).

Backend selection (same contract as the other kernel packages):
  'pallas'    — real TPU lowering (target hardware)
  'interpret' — Pallas interpret mode (CPU correctness validation)
  'xla'       — ``jax.ops.segment_sum`` on the sorted edges (CPU default)
  'auto'      — ``kernels/backend.resolve_backend`` (pallas on a TPU for
                f32, xla otherwise)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import resolve_backend
from .kernel import LANE, SUBLANE, coo_segment_sum_sorted


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True, eq=False)
class COOPlan:
    """Static per-topology plan for the tiled segment-sum kernel.

    Edges are stored ROW-SORTED; ``perm`` maps original edge order to
    sorted order (``vals_sorted = vals[..., perm]``). ``span`` bounds,
    over every tile of ``block_edges`` sorted edges, the distance from
    the tile's lane-aligned first row to its last row — the static
    output-window width of the kernel.
    """
    n: int                    # number of segments (nodes)
    n_edges: int
    block_edges: int
    span: int                 # static row-window width (lane-aligned)
    n_pad: int                # padded output width
    e_pad: int                # padded edge count
    perm: jnp.ndarray         # (E,) int32, original -> sorted gather map
    rows_sorted: jnp.ndarray  # (E,) int32 ascending
    cols_sorted: jnp.ndarray  # (E,) int32 aligned with rows_sorted
    rows2d_pad: jnp.ndarray   # (e_pad, 1) int32, padding repeats last row


def coo_plan(rows: np.ndarray, cols: np.ndarray, num_segments: int,
             block_edges: int = 512) -> COOPlan:
    """Plan the kernel launch for one COO pattern (host side, one-time)."""
    rows = np.asarray(rows, np.int32)
    cols = np.asarray(cols, np.int32)
    assert rows.shape == cols.shape and rows.ndim == 1, \
        (rows.shape, cols.shape)
    n_edges = int(rows.size)
    if n_edges == 0:
        z = jnp.zeros((0,), jnp.int32)
        return COOPlan(n=num_segments, n_edges=0, block_edges=block_edges,
                       span=LANE, n_pad=_round_up(max(num_segments, 1),
                                                  LANE) + LANE,
                       e_pad=0, perm=z, rows_sorted=z, cols_sorted=z,
                       rows2d_pad=jnp.zeros((0, 1), jnp.int32))
    perm = np.argsort(rows, kind="stable")
    rows_s, cols_s = rows[perm], cols[perm]
    e_pad = _round_up(n_edges, block_edges)
    rows_pad = np.concatenate(
        [rows_s, np.full(e_pad - n_edges, rows_s[-1], np.int32)])
    tiles = rows_pad.reshape(-1, block_edges)
    width = tiles[:, -1] - (tiles[:, 0] // LANE) * LANE + 1
    span = _round_up(int(width.max()), LANE)
    n_pad = _round_up(num_segments, LANE) + span
    return COOPlan(n=num_segments, n_edges=n_edges,
                   block_edges=block_edges, span=span, n_pad=n_pad,
                   e_pad=e_pad,
                   perm=jnp.asarray(perm, jnp.int32),
                   rows_sorted=jnp.asarray(rows_s),
                   cols_sorted=jnp.asarray(cols_s),
                   rows2d_pad=jnp.asarray(rows_pad[:, None]))


def _segment_sum_of_sorted(plan: COOPlan, vals_s: jnp.ndarray,
                           backend: str) -> jnp.ndarray:
    """vals_s (..., E) in SORTED edge order -> (..., N) row sums."""
    backend = resolve_backend(backend, vals_s.dtype)
    lead = vals_s.shape[:-1]
    if plan.n_edges == 0:
        return jnp.zeros(lead + (plan.n,), vals_s.dtype)
    if backend == "xla":
        out = jax.ops.segment_sum(jnp.moveaxis(vals_s, -1, 0),
                                  plan.rows_sorted,
                                  num_segments=plan.n,
                                  indices_are_sorted=True)
        return jnp.moveaxis(out, 0, -1)
    flat = vals_s.reshape((-1, plan.n_edges))
    b = flat.shape[0]
    b_pad = _round_up(max(b, 1), SUBLANE)
    padded = jnp.zeros((b_pad, plan.e_pad), flat.dtype) \
        .at[:b, :plan.n_edges].set(flat)
    out = coo_segment_sum_sorted(padded, plan.rows2d_pad,
                                 n_pad=plan.n_pad, span=plan.span,
                                 be=plan.block_edges,
                                 interpret=(backend == "interpret"))
    return out[:b, :plan.n].reshape(lead + (plan.n,))


def coo_segment_sum(plan: COOPlan, vals: jnp.ndarray,
                    backend: str = "auto") -> jnp.ndarray:
    """Row sums of per-edge values given in ORIGINAL edge order.

    vals (..., E) -> (..., N), equal to ``jax.ops.segment_sum`` over the
    last axis with the plan's original row indices.
    """
    return _segment_sum_of_sorted(plan, vals[..., plan.perm], backend)


def coo_matvec(plan: COOPlan, gvals: jnp.ndarray, x: jnp.ndarray,
               backend: str = "auto") -> jnp.ndarray:
    """Off-diagonal COO matvec: segsum(gvals * x[cols]) by row.

    gvals (..., E) in original edge order, x (..., N); leading axes
    broadcast. This is the matrix-free core of every "cg"-tier solve —
    the caller adds its own diagonal term.
    """
    contrib = gvals[..., plan.perm] * x[..., plan.cols_sorted]
    return _segment_sum_of_sorted(plan, contrib, backend)
