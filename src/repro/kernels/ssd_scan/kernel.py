"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation of the SSD blocked algorithm (arXiv:2405.21060): the
sequence is processed in chunks of Q tokens; within a chunk the
contribution is an attention-like (Q x Q) masked-decay GEMM (MXU work);
across chunks a small (N x P) state is carried in a VMEM scratch buffer
that persists across the sequential innermost grid dimension.

Grid = (B, H, L/Q); the chunk dimension is 'arbitrary' (sequential) so the
state scratch carries across chunk steps for a fixed (batch, head).
All intermediate math in fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, s_final_ref,
                state_ref, *, nc: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    q = x_ref.shape[1]
    x = x_ref[0, :, 0, :].astype(jnp.float32)        # (Q, P)
    dt = dt_ref[0, :, 0].astype(jnp.float32)         # (Q,)
    a = a_ref[0].astype(jnp.float32)                 # scalar decay rate
    bm = b_ref[0, :, 0, :].astype(jnp.float32)       # (Q, N)
    cm = c_ref[0, :, 0, :].astype(jnp.float32)       # (Q, N)

    xbar = x * dt[:, None]                           # (Q, P)
    alog = dt * a                                    # (Q,)
    acum = jnp.cumsum(alog)                          # (Q,) inclusive
    # decay weights L[i, j] = exp(acum_i - acum_j) for i >= j
    diff = acum[:, None] - acum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lmat = jnp.exp(jnp.where(ii >= jj, diff, -jnp.inf))

    scores = jnp.dot(cm, bm.T, preferred_element_type=jnp.float32) * lmat
    y_diag = jnp.dot(scores, xbar, preferred_element_type=jnp.float32)

    # carried-state contribution: y_off[i] = exp(acum_i) * C_i . S_prev
    s_prev = state_ref[...]                          # (N, P)
    y_off = jnp.exp(acum)[:, None] * jnp.dot(
        cm, s_prev, preferred_element_type=jnp.float32)

    y_ref[0, :, 0, :] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: S = exp(a_tot) S_prev + sum_j exp(a_tot - acum_j) B_j x_j
    a_tot = acum[-1]
    decay = jnp.exp(a_tot - acum)                    # (Q,)
    s_new = jnp.exp(a_tot) * s_prev + jnp.dot(
        (bm * decay[:, None]).T, xbar, preferred_element_type=jnp.float32)
    state_ref[...] = s_new

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        s_final_ref[0, 0, :, :] = s_new.astype(s_final_ref.dtype)


def ssd_scan_pallas(x, dt, a, bm, cm, chunk: int = 64,
                    interpret: bool = False):
    """x (B,L,H,P), dt (B,L,H), a (H,), bm/cm (B,L,G,N).

    Returns y (B,L,H,P) and final state (B,H,P,N) [transposed from the
    kernel's (N,P) scratch]. L must be a multiple of `chunk`.
    """
    b, l, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    hpg = h // g
    grid = (b, h, nc)

    y, s_final = pl.pallas_call(
        functools.partial(_ssd_kernel, nc=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bi, hi, ci: (bi, ci, hi)),
            pl.BlockSpec((1,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, _hpg=hpg: (bi, ci, hi // _hpg, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, _hpg=hpg: (bi, ci, hi // _hpg, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, l, h, p), x.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_chunked_scan",
    )(x, dt, a, bm, cm)
    return y, jnp.swapaxes(s_final, -1, -2)  # -> (B, H, P, N)
