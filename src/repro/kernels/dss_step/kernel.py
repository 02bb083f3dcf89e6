"""Pallas TPU kernel for the DSS thermal step (fused blocked GEMM).

The paper's DSS model is a pure multiply-accumulate workload (§4.4, §5.3:
"relying solely on matrix multiplication operations"). On TPU the right
shape for it is a tiled GEMM that (a) keeps A_d/B_d tiles resident in VMEM
and (b) batches many independent thermal traces (DSE candidates / pods) so
the MXU is fed 128x128 tiles.

Grid = (B/bm, N/bn, K/bk); K is the innermost ("arbitrary") dimension and
accumulates into a VMEM fp32 scratch tile; the output tile is written on the
last K step. Tile sizes are MXU-aligned (multiples of 128 in the lane dim,
8 in the sublane dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # full-f32 MXU passes: the default rounds f32 operands to bf16
    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def blocked_matmul(x: jnp.ndarray, w: jnp.ndarray, *, bm: int = 128,
                   bn: int = 128, bk: int = 128,
                   interpret: bool = False) -> jnp.ndarray:
    """X (M,K) @ W (K,N) with explicit VMEM tiling.

    Caller guarantees M % bm == K % bk == N % bn == 0 (ops.py pads).
    """
    m, kdim = x.shape
    k2, n = w.shape
    assert kdim == k2, (x.shape, w.shape)
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0, \
        (x.shape, w.shape, bm, bn, bk)
    nk = kdim // bk
    grid = (m // bm, n // bn, nk)
    return pl.pallas_call(
        functools.partial(_matmul_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dss_fused_gemm",
    )(x, w)
