"""Pallas TPU kernel: ONE full preconditioned-CG iteration per launch.

The matrix-free solver tier (``solver="cg"``) spends its life in the CG
body: an off-diagonal COO matvec, a Jacobi preconditioner apply, two
reductions (``p·Ap``, ``r·z``) and three axpys. In the XLA form
(``ops.pcg_loop``) every one of those is a separate op — and on the
target hardware a separate dispatch — per iteration. This kernel executes the WHOLE iteration in a
single launch, flash-attention style (see ``kernels/flash_attn``):

  * grid = (batch blocks, edge tiles); the edge dimension is sequential
    ("arbitrary"), accumulating the off-diagonal matvec ``sum_e g_e *
    p[col_e]`` into a VMEM scratch block exactly like the
    ``kernels/coo_matvec`` segment-sum — a one-hot GEMM per tile against
    the ROW-SORTED edge plan, never a scatter;
  * the GATHER ``p[col_e]`` is also a one-hot GEMM: planning
    (``ops.fused_cg_plan``) reorders the nodes with reverse Cuthill-McKee
    so every edge tile touches a NARROW, host-bounded column window
    [col_base, col_base + col_span) of ``p`` — the window is a static
    shape, its per-tile start is a scalar-prefetched SMEM array (a
    (1, 1) VMEM block per tile is refused by the TPU lowering), and the
    in-tile column indices are stored relative to it;
  * both one-hot GEMMs are exact, in ``ops.fused_cg_passes`` single
    bf16 MXU passes each: the one-hot tile is exact in bf16, and an f32
    value is exactly the sum of three bf16 parts (hi = bf16(v), mid =
    bf16(v - hi), lo = v - hi - mid), so the parts stacked along the
    batch rows make one GEMM that loads each one-hot weight tile once,
    and its three f32 row blocks summed give the gather bit for bit and
    the scatter at f32 accumulation — half the passes of ``EXACT``
    (HIGHEST), which splits the one-hot side too. A bf16 operand is one
    part; f64 (interpret mode only: Mosaic cannot lower it) keeps one
    ``EXACT`` pass in f64;
  * the LAST edge tile runs the epilogue: add the diagonal term, form the
    ``p·Ap`` / ``r·z`` reductions, the masked alpha/beta, the x/r/p
    updates and the new residual norm — all on the full state resident in
    VMEM — and writes the six outputs;
  * the scalar CG state (rho = r·z, ||r||^2, per-row iteration counts)
    rides (B, 1) operands through the launch, so the OUTER ``while_loop``
    body is exactly one kernel call plus a convergence check on ||r||^2;
  * x, r, p and the scalar state are aliased to their outputs: a grid
    step reads and writes only its own batch rows, so the launch updates
    the ``while_loop`` carry in place, and XLA copies no state between
    iterations;
  * the batch axis rides the GEMM sublane dimension as in ``coo_matvec``,
    so the family solvers need no vmap, and per-row live masks replicate
    the masked-batch semantics of ``ops.pcg_loop`` bit for bit. The
    batch block is sized to the batch and to VMEM (``ops.fused_cg_block``):
    the one-hot tiles are built once per grid step and the MXU loads each
    weight tile once per step, so a taller block serves more rows with
    both.

The masking formulas are EXACTLY those of the XLA form's loop
(``ops.pcg_loop``): a row is live while ``||r||^2 > tol^2 ||b||^2``;
frozen rows get alpha = beta = 0 and coast unchanged. Padded lanes carry
``diag = 1`` and zero state so the Jacobi apply never divides 0/0.

``ops.py`` owns planning (RCM ordering, edge sort, window measurement,
ELL arrays for the XLA form) and the solver, ``fused_cg_solve``; ``ref.py``
is the dense oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..coo_matvec.kernel import (  # shared alignment/precision contract
    EXACT, LANE, SUBLANE)

__all__ = ["LANE", "SUBLANE", "fused_cg_step_pallas"]


def _mxu_form(dtype):
    """``(part dtype, precision, accumulator dtype)`` of the one-hot GEMMs
    on ``dtype`` values: bf16 parts at the MXU's single-pass precision
    into f32, or, for f64, one pass in f64 at ``EXACT``."""
    if dtype == jnp.float64:
        return dtype, EXACT, dtype
    return jnp.bfloat16, jax.lax.Precision.DEFAULT, jnp.float32


def _onehot_dot(v, sel, dims, passes: int):
    """``dot_general(v, sel, (dims, ((), ())))`` of a (rows, k) value block
    and a one-hot tile ``sel`` (built in the part dtype), as ``passes``
    parts of ``v`` that sum to it exactly: hi = bf16(v), then the bf16
    of what is left, the last part being the rest. The parts are stacked
    along the rows into ONE GEMM, and its row blocks summed in f32 in
    order (hi + mid) + lo."""
    part_t, precision, acc_t = _mxu_form(v.dtype)
    parts = []
    for _ in range(passes - 1):
        hi = v.astype(part_t).astype(v.dtype)
        parts.append(hi)
        v = v - hi
    parts.append(v)
    stacked = parts[0] if passes == 1 else jnp.concatenate(parts, axis=0)
    y = jax.lax.dot_general(stacked.astype(part_t), sel, (dims, ((), ())),
                            precision=precision, preferred_element_type=acc_t)
    rows = y.shape[0] // passes
    out = y[:rows]
    for k in range(1, passes):
        out = out + y[k * rows:(k + 1) * rows]
    return out


def _cg_step_kernel(colbase_ref, rows_ref, cols_ref, gv_ref, diag_ref,
                    x_ref, r_ref, p_ref, rz_ref, rn2_ref, it_ref, tol2_ref,
                    ox_ref, or_ref, op_ref, orz_ref, orn2_ref, oit_ref,
                    ap_ref, *, n_tiles: int, row_span: int, col_span: int,
                    passes: int):
    """One grid step: accumulate one edge tile of ``offdiag @ p``; on the
    final tile, run the whole CG-iteration epilogue.

    colbase_ref (n_tiles,) int32 in SMEM (scalar prefetch); rows_ref
    (be, 1) int32 sorted ABSOLUTE; cols_ref (be, 1) int32 RELATIVE to
    colbase; gv_ref (bb, be);
    diag/x/r/p (bb, n_pad); rz/rn2/tol2 (bb, 1); it (bb, 1) int32;
    ap_ref (bb, n_pad) VMEM scratch.
    """
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        ap_ref[...] = jnp.zeros_like(ap_ref)

    be = gv_ref.shape[1]
    dtype = gv_ref.dtype
    part_t = _mxu_form(dtype)[0]

    # ---- gather p over the tile's column window (one-hot GEMM) ----------
    cbase = pl.multiple_of(colbase_ref[i], LANE)
    pwin = p_ref[:, pl.ds(cbase, col_span)]              # (bb, col_span)
    selg = (cols_ref[...] == jax.lax.broadcasted_iota(
        jnp.int32, (be, col_span), 1)).astype(part_t)     # (be, col_span)
    # pg[b, e] = pwin[b, cols_rel[e]]
    pg = _onehot_dot(pwin, selg, ((1,), (1,)), passes).astype(dtype)
    contrib = gv_ref[...] * pg                           # (bb, be)

    # ---- scatter into the tile's row window (one-hot GEMM) --------------
    rbase = pl.multiple_of((rows_ref[0, 0] // LANE) * LANE, LANE)
    selr = (rows_ref[...] == (jax.lax.broadcasted_iota(
        jnp.int32, (be, row_span), 1) + rbase)).astype(part_t)
    local = _onehot_dot(contrib, selr, ((1,), (0,)), passes)
    ap_ref[:, pl.ds(rbase, row_span)] += local.astype(ap_ref.dtype)

    # ---- final tile: the rest of the CG iteration -----------------------
    @pl.when(i == n_tiles - 1)
    def _epilogue():
        diag = diag_ref[...]
        p = p_ref[...]
        ap = diag * p - ap_ref[...].astype(dtype)        # A p, full rows
        x = x_ref[...]
        r = r_ref[...]
        rz = rz_ref[...]                                  # (bb, 1)
        live = rn2_ref[...] > tol2_ref[...]               # (bb, 1) bool
        denom = jnp.sum(p * ap, axis=1, keepdims=True)
        alpha = jnp.where(live,
                          rz / jnp.where(denom == 0, 1.0, denom), 0.0)
        x = x + alpha * p
        r = r - alpha * ap
        z = r / diag                                      # Jacobi apply
        rz_new = jnp.sum(r * z, axis=1, keepdims=True)
        beta = jnp.where(live,
                         rz_new / jnp.where(rz == 0, 1.0, rz), 0.0)
        ox_ref[...] = x
        or_ref[...] = r
        op_ref[...] = z + beta * p
        orz_ref[...] = rz_new
        orn2_ref[...] = jnp.sum(r * r, axis=1, keepdims=True)
        oit_ref[...] = it_ref[...] + live.astype(jnp.int32)


def fused_cg_step_pallas(colbase, rows2d, cols2d, gvals, diag, x, r, p,
                         rz, rn2, it, tol2, *, row_span: int,
                         col_span: int, be: int, block_b: int,
                         vmem_limit_bytes: int, interpret: bool = False):
    """One fused Jacobi-PCG iteration on pre-padded operands.

    colbase (n_tiles,) int32 lane-aligned window starts; rows2d /
    cols2d (e_pad, 1) int32 (rows absolute sorted, cols relative);
    gvals (b_pad, e_pad) zero-padded; diag/x/r/p (b_pad, n_pad) with
    ``diag`` one-padded; rz/rn2/tol2 (b_pad, 1); it (b_pad, 1) int32.
    ``block_b`` rows of the batch share one grid step; the caller sizes
    it and the scoped-VMEM limit (``ops.fused_cg_block``). The one-hot
    GEMMs take ``ops.fused_cg_passes(x.dtype)`` MXU passes. Returns
    (x', r', p', rz', rn2', it').
    """
    from .ops import fused_cg_passes  # ops imports this module
    b_pad, e_pad = gvals.shape
    n_pad = x.shape[1]
    assert e_pad % be == 0 and rows2d.shape == (e_pad, 1), \
        (gvals.shape, rows2d.shape, be)
    assert n_pad % LANE == 0 and row_span % LANE == 0 \
        and col_span % LANE == 0, (n_pad, row_span, col_span)
    assert b_pad % block_b == 0, (b_pad, block_b)
    n_tiles = e_pad // be
    grid = (b_pad // block_b, n_tiles)
    dtype = x.dtype
    acc_t = dtype if dtype == jnp.float64 else jnp.float32

    # index maps take the scalar-prefetched colbase ref as a trailing arg
    state_spec = pl.BlockSpec((block_b, n_pad), lambda b, i, cb: (b, 0))
    scalar_spec = pl.BlockSpec((block_b, 1), lambda b, i, cb: (b, 0))
    edge_spec = pl.BlockSpec((be, 1), lambda b, i, cb: (i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                                # colbase
        grid=grid,
        in_specs=[
            edge_spec, edge_spec,                             # rows, cols
            pl.BlockSpec((block_b, be), lambda b, i, cb: (b, i)),  # gvals
            state_spec,                                       # diag
            state_spec, state_spec, state_spec,               # x, r, p
            scalar_spec, scalar_spec,                         # rz, rn2
            scalar_spec, scalar_spec,                         # it, tol2
        ],
        out_specs=[state_spec, state_spec, state_spec,
                   scalar_spec, scalar_spec, scalar_spec],
        scratch_shapes=[pltpu.VMEM((block_b, n_pad), acc_t)],
    )
    return pl.pallas_call(
        functools.partial(_cg_step_kernel, n_tiles=n_tiles,
                          row_span=row_span, col_span=col_span,
                          passes=fused_cg_passes(dtype)),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b_pad, n_pad), dtype),      # x'
            jax.ShapeDtypeStruct((b_pad, n_pad), dtype),      # r'
            jax.ShapeDtypeStruct((b_pad, n_pad), dtype),      # p'
            jax.ShapeDtypeStruct((b_pad, 1), dtype),          # rz'
            jax.ShapeDtypeStruct((b_pad, 1), dtype),          # rn2'
            jax.ShapeDtypeStruct((b_pad, 1), jnp.int32),      # it'
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        # x, r, p, rz, rn2, it (operands 5-10, colbase counted) -> outputs
        input_output_aliases={5: 0, 6: 1, 7: 2, 8: 3, 9: 4, 10: 5},
        interpret=interpret,
        name="fused_cg_step",
    )(colbase, rows2d, cols2d, gvals, diag, x, r, p, rz, rn2, it, tol2)
