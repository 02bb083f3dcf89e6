"""Process-wide JAX switches: device float64, the compile cache, spans.

``x64()`` is the one way the program turns on device float64. JAX's flag
is thread-local, so it reaches only the thread that enters the context:
the oracle enters it on its worker thread, tests around their f64
builds. Host f64 math does not need it: it runs in numpy/scipy.

``use_compile_cache()`` turns on JAX's persistent compilation cache for
an entry point (``chip_smoke.py``, the benchmarks). It is never called at
import. ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory and
JAX reads it itself; otherwise the cache lives at the fixed path
``<repo>/.jax_cache``. The path is part of the cache's key, so it is
never temporary or per-process.

``span(name, **args)`` names a stretch of host time on the JAX profiler's
own clock, so it lands in the same trace as the device's operations. The
args are counters the caller already holds (ints, short strings), never
a device value read for the span. With the profiler off a span is a
no-op of under a microsecond.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[2]
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def x64():
    """Context manager: device float64 on for the calling thread."""
    return jax.enable_x64(True)


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    path = os.environ.get(CACHE_ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def span(name: str, **args):
    """Context manager: the profiler span ``mfit.<name>`` with ``args``
    as its event stats."""
    return jax.profiler.TraceAnnotation(f"mfit.{name}", **args)
