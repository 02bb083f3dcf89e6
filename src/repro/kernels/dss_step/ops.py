"""Jitted public wrapper for the DSS step kernel.

Backend selection:
  'pallas'    — real TPU lowering (target hardware)
  'interpret' — Pallas interpret mode (CPU correctness validation)
  'xla'       — pure-jnp reference path (used by CPU benchmarks & dry-run)
  'auto'      — ``kernels/backend.resolve_backend`` (pallas on a TPU for
                f32, xla otherwise)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import resolve_backend
from .kernel import blocked_matmul
from .ref import dss_step_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("backend",))
def dss_step(theta: jnp.ndarray, q: jnp.ndarray, ad_t: jnp.ndarray,
             bd_t: jnp.ndarray, backend: str = "auto") -> jnp.ndarray:
    """Batched DSS step: theta' = theta @ Ad^T + q @ Bd^T.

    theta (B, N), q (B, S), ad_t (N, N), bd_t (S, N) -> (B, N).
    """
    backend = resolve_backend(backend, theta.dtype)
    if backend == "xla":
        return dss_step_ref(theta, q, ad_t, bd_t)
    b, n = theta.shape
    s = q.shape[1]
    # Fused single-GEMM formulation: [theta | q] @ [Ad^T ; Bd^T].
    x = jnp.concatenate([theta, q.astype(theta.dtype)], axis=1)
    w = jnp.concatenate([ad_t, bd_t.astype(ad_t.dtype)], axis=0)
    bm = 8 if b <= 8 else 128
    x = _pad_to(_pad_to(x, 0, bm), 1, 128)
    w = _pad_to(_pad_to(w, 0, 128), 1, 128)
    out = blocked_matmul(x, w, bm=bm, interpret=(backend == "interpret"))
    return out[:b, :n]


@functools.partial(jax.jit, static_argnames=("backend",))
def dss_rollout(theta0: jnp.ndarray, q_traj: jnp.ndarray, ad_t: jnp.ndarray,
                bd_t: jnp.ndarray, backend: str = "auto") -> jnp.ndarray:
    """Roll a batch of DSS traces through time.

    theta0 (B, N), q_traj (T, B, S) -> thetas (T, B, N).
    This is the paper's "milliseconds" runtime model and the batched-DSE
    primitive (B = candidate configurations evaluated simultaneously).
    """

    def body(theta, q):
        th = dss_step(theta, q, ad_t, bd_t, backend=backend)
        return th, th

    _, out = jax.lax.scan(body, theta0, q_traj)
    return out
