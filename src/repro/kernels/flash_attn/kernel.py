"""Pallas TPU flash-attention forward kernel (online softmax).

Grid = (B*H, Lq/BQ, Lk/BK); the KV dimension is sequential and carries
running max / sum / accumulator in VMEM scratch. Causal blocks entirely
above the diagonal are skipped (no MXU work issued). Diagonals are aligned
to the END of the KV axis so the same kernel serves training (Lq == Lk)
and single-step decode (Lq == 1, Lk == cache length).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               nk: int, causal: bool, scale: float, q_offset: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal skip: whole KV block strictly above the (end-aligned) diagonal
    first_q = qi * bq + q_offset        # global query position of row 0
    run = (not causal) or (ki * bk <= first_q + bq - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # (BQ, D)
        k = k_ref[0].astype(jnp.float32)               # (BK, D)
        v = v_ref[0].astype(jnp.float32)               # (BK, D)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
                + first_q
            kpos = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) \
                + ki * bk
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_prev = m_ref[...]                            # (BQ, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                          # masked -> exp(-inf)=0
        alpha = jnp.exp(m_prev - m_new)                 # (BQ, 1)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _store():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, causal: bool = True, scale=None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q (BH, Lq, D), k/v (BH, Lk, D) -> (BH, Lq, D).

    Lq % block_q == 0 and Lk % block_k == 0 required (ops.py pads).
    """
    bh, lq, d = q.shape
    lk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    assert lq % block_q == 0 and lk % block_k == 0
    nq, nk = lq // block_q, lk // block_k
    grid = (bh, nq, nk)
    return pl.pallas_call(
        functools.partial(_fa_kernel, nk=nk, causal=causal,
                          scale=float(scale), q_offset=lk - lq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
