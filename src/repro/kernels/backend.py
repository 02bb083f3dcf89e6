"""The one backend rule every kernel package dispatches through.

Each ``kernels/*/ops.py`` entry point takes ``backend``:

  'pallas'    — the Mosaic TPU lowering (target hardware)
  'interpret' — Pallas interpret mode (CPU correctness validation)
  'xla'       — the plain-jnp form of the same computation
  'auto'      — :func:`resolve_backend` below

'auto' picks Pallas only on a TPU and only for 32-bit-or-narrower
floats. Mosaic cannot lower float64, so f64 work (the f64 builds of the
fidelity ladder, the oracle's ``x64=True`` mode) takes the XLA form on
every platform. This is a stated rule, not a fallback: nothing is tried
and caught.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BACKENDS = ("auto", "pallas", "interpret", "xla")


def resolve_backend(backend: str, dtype) -> str:
    """Resolve ``backend`` for work in ``dtype``; 'auto' -> 'pallas' on
    a TPU for dtypes of at most 32 bits, else 'xla'."""
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    if backend != "auto":
        return backend
    if jax.default_backend() == "tpu" and jnp.dtype(dtype).itemsize <= 4:
        return "pallas"
    return "xla"
