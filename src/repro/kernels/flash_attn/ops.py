"""Public attention op: GQA-aware, backend-selected, custom-vjp wrapped.

Backends:
  'xla'       — pure-jnp reference math (CPU, dry-run; XLA fuses this well
                on TPU too at moderate sequence lengths)
  'pallas'    — flash-attention forward kernel (TPU target)
  'interpret' — kernel under Pallas interpret mode (CPU validation)

The backward pass recomputes attention with the reference math under
custom_vjp (flash backward kernels are a known follow-up; the dry-run and
CPU training paths use 'xla' end-to-end, so the kernel backward is not on
any critical path in this container).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import resolve_backend
from .kernel import flash_attention_pallas
from .ref import chunked_gqa, gqa_ref, mha_ref  # noqa: F401

# XLA-path threshold: above this Lq the chunked (flash-style) formulation
# is used so the (Lq, Lk) score matrix is never materialized.
_CHUNKED_MIN_LEN = 2048


def _kernel_path(q, k, v, causal, scale, interpret):
    b, hq, lq, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * hq, lq, d)
    kf = k.reshape(b * hq, -1, d)
    vf = v.reshape(b * hq, -1, d)
    out = flash_attention_pallas(qf, kf, vf, causal=causal, scale=scale,
                                 interpret=interpret)
    return out.reshape(b, hq, lq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attn_kernel(q, k, v, causal, scale, interpret):
    return _kernel_path(q, k, v, causal, scale, interpret=interpret)


def _attn_kernel_fwd(q, k, v, causal, scale, interpret):
    return _attn_kernel(q, k, v, causal, scale, interpret), (q, k, v)


def _attn_kernel_bwd(causal, scale, interpret, res, g):
    """Backward for the kernel path: differentiate the memory-efficient
    chunked reference (recompute; flash-bwd kernels are follow-up work)."""
    q, k, v = res
    if causal and q.shape[2] == k.shape[2] \
            and q.shape[2] >= _CHUNKED_MIN_LEN:
        fn = lambda q_, k_, v_: chunked_gqa(q_, k_, v_, scale=scale)
    else:
        fn = lambda q_, k_, v_: gqa_ref(q_, k_, v_, causal=causal,
                                        scale=scale)
    _, vjp = jax.vjp(fn, q, k, v)
    return vjp(g)


_attn_kernel.defvjp(_attn_kernel_fwd, _attn_kernel_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "backend"))
def attention(q, k, v, causal: bool = True, scale=None,
              backend: str = "auto") -> jnp.ndarray:
    """GQA attention. q (B,Hq,L,D), k/v (B,Hkv,Lk,D), Hq % Hkv == 0."""
    backend = resolve_backend(backend, q.dtype)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scale = float(scale)
    if backend == "xla":
        # differentiated directly: chunked_gqa's per-chunk remat gives the
        # flash-style O(L) backward memory without a custom vjp
        if causal and q.shape[2] == k.shape[2] \
                and q.shape[2] >= _CHUNKED_MIN_LEN:
            return chunked_gqa(q, k, v, scale=scale)
        return gqa_ref(q, k, v, causal=causal, scale=scale)
    return _attn_kernel(q, k, v, causal, scale, backend == "interpret")
