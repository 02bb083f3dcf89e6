"""Pallas TPU kernel: tiled segment-sum over sorted COO edges.

The matrix-free solver tier reduces every RC solve to repeated evaluation
of the off-diagonal COO matvec ``y[r] += gvals[e] * x[cols[e]]``. XLA's
scatter-add lowers poorly on TPU, so the scatter is reformulated as a
sequence of small one-hot GEMMs over ROW-SORTED edges:

  * the batch is tiled into row blocks sized to VMEM (grid dim 0,
    "parallel") and the edge list into blocks of ``be`` edges (grid
    dim 1, "arbitrary" = sequential, so accumulation into the output
    is safe);
  * because the rows are sorted, one tile only touches a narrow window of
    output rows. ``span`` is the host-computed maximum window width over
    all tiles (lane-aligned), so the window is a STATIC shape;
  * inside a tile the partial sums are one (B, be) x (be, span) matmul
    against the tile's one-hot row-selection matrix — MXU work instead of
    a scatter — accumulated into the full output resident in VMEM with a
    dynamic lane-aligned store.

``ops.py`` owns the host-side planning (sort, padding, span measurement)
and the CPU ``segment_sum`` fallback; ``ref.py`` is the dense oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128           # TPU lane width; windows and pads align to this
SUBLANE = 8          # f32 sublane width; the batch dim pads to this
# The MXU's default precision rounds f32 operands to bf16 (8 mantissa
# bits). A one-hot GEMM is a gather or scatter, so the values must pass
# through it unrounded: full-f32 passes.
EXACT = jax.lax.Precision.HIGHEST
# VMEM bytes of one (block_b, n_pad) output block. Pallas double-buffers
# it, so two must fit a v5e core's 16 MiB scoped-VMEM limit beside the
# edge tiles.
_OUT_BLOCK_BYTES = 4 << 20


def _batch_block(b_pad: int, n_pad: int, itemsize: int) -> int:
    """Largest multiple of SUBLANE that divides ``b_pad`` and keeps one
    output block within ``_OUT_BLOCK_BYTES``."""
    cap = max(SUBLANE, _OUT_BLOCK_BYTES // (n_pad * itemsize))
    return max(r for r in range(SUBLANE, min(b_pad, cap) + 1, SUBLANE)
               if b_pad % r == 0)


def _segsum_kernel(rows_ref, vals_ref, o_ref, *, span: int):
    """One edge tile: one-hot GEMM into the [base, base+span) row window.

    rows_ref (be, 1) int32 sorted; vals_ref (bb, be); o_ref (bb, n_pad).
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    be = vals_ref.shape[1]
    # lane-aligned window start; planning guarantees every row of this
    # tile lands inside [base, base + span)
    base = pl.multiple_of((rows_ref[0, 0] // LANE) * LANE, LANE)
    # one-hot row selector: onehot[e, r] = (rows[e] == base + r)
    sel = rows_ref[...] == (
        jax.lax.broadcasted_iota(jnp.int32, (be, span), 1) + base)
    acc_t = vals_ref.dtype if vals_ref.dtype == jnp.float64 \
        else jnp.float32
    local = jnp.dot(vals_ref[...], sel.astype(vals_ref.dtype),
                    precision=EXACT, preferred_element_type=acc_t)
    o_ref[:, pl.ds(base, span)] += local.astype(o_ref.dtype)


def coo_segment_sum_sorted(vals: jnp.ndarray, rows2d: jnp.ndarray,
                           *, n_pad: int, span: int, be: int,
                           interpret: bool = False) -> jnp.ndarray:
    """Tiled segment-sum of pre-sorted, pre-padded edge contributions.

    vals (B_pad, E_pad) with zero padding; rows2d (E_pad, 1) int32 sorted
    ascending (padding repeats the last row). ``span`` must bound, over
    every ``be``-edge tile, the distance from the tile's lane-aligned
    first row to its last row (ops.py measures this). Returns
    (B_pad, n_pad) partial sums; the caller slices off the padding.
    """
    b_pad, e_pad = vals.shape
    assert e_pad % be == 0 and rows2d.shape == (e_pad, 1), \
        (vals.shape, rows2d.shape, be)
    assert n_pad % LANE == 0 and span % LANE == 0, (n_pad, span)
    assert b_pad % SUBLANE == 0, b_pad
    bb = _batch_block(b_pad, n_pad, vals.dtype.itemsize)
    grid = (b_pad // bb, e_pad // be)
    return pl.pallas_call(
        functools.partial(_segsum_kernel, span=span),
        grid=grid,
        in_specs=[
            pl.BlockSpec((be, 1), lambda b, i: (i, 0)),
            pl.BlockSpec((bb, be), lambda b, i: (b, i)),
        ],
        # every tile of a batch block revisits the same output block
        # and accumulates
        out_specs=pl.BlockSpec((bb, n_pad), lambda b, i: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((b_pad, n_pad), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="coo_segment_sum",
    )(rows2d, vals)
