"""Public SSD scan op with backend selection (pallas | interpret | xla)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..backend import resolve_backend
from .kernel import ssd_scan_pallas
from .ref import ssd_chunked_jnp, ssd_decode_step, ssd_ref  # noqa: F401


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def ssd_scan(x, dt, a, bm, cm, chunk: int = 64, backend: str = "auto"):
    """Mamba2 SSD scan. Returns (y, final_state).

    x (B,L,H,P), dt (B,L,H), a (H,), bm/cm (B,L,G,N);
    y (B,L,H,P), state (B,H,P,N). Pads L to a chunk multiple internally
    (zero dt/x padding is exact: decay 1, contribution 0).
    """
    backend = resolve_backend(backend, x.dtype)
    b, l, h, p = x.shape
    pad = (-l) % chunk
    if pad:
        zpad = lambda t: jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] *
                                 (t.ndim - 2))
        x, dt, bm, cm = zpad(x), zpad(dt), zpad(bm), zpad(cm)
    if backend == "xla":
        y, s = ssd_chunked_jnp(x, dt, a, bm, cm, chunk=chunk)
    else:
        y, s = ssd_scan_pallas(x, dt, a, bm, cm, chunk=chunk,
                               interpret=(backend == "interpret"))
    return y[:, :l], s
