"""The benchmark's machinery, shared by ``run.py`` and the tools.

Everything a cell needs is found by name from ``BENCHMARK.json``:

  configs/<config>.json     the deployment (sizes, solver, dtype, power)
  traffic/<traffic>.json    the mix; its ``kind`` names the generator
  drivers/<kind>.py         the generator of that kind of traffic
  limits/<workload>.json    the limit of each number the check compares
  metrics/<metric>.py       one reader per per-layer metric
  peaks.json                the device peaks, keyed by ``device_kind``

so a later cell adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(spec_: dict, workload: str) -> dict:
    """The workload entry with its config, traffic and limits resolved."""
    for w in spec_["workloads"]:
        if w["name"] == workload:
            break
    else:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = next(c for c in spec_["configs"] if c["name"] == w["config"])
    return {"workload": w,
            "config": load_json(ROOT / cfg["file"]),
            "traffic": load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json")}


def metrics_for(spec_: dict, workload: str, trace: bool) -> list:
    """The metric entries this cell reports: end-to-end ones untraced,
    per-layer ones traced."""
    key = "per_layer" if trace else "end_to_end"
    return [m for m in spec_[key]
            if workload in m.get("workloads", [workload])]


def load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def driver(kind: str):
    return load_module(BENCH / "drivers" / f"{kind}.py", f"bench_driver_{kind}")


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py",
                       "bench_metric_" + metric.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json")
    return table[device_kind]


def use_compile_cache() -> str:
    """JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``,
    else at ``<checkout>/.jax_cache``; every program is kept, however
    short its compile, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path   # the program reads it too
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices(chips: int, platform: str = "tpu") -> dict:
    """The devices the cell runs on; raises unless there are ``chips``
    of them on ``platform``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise BenchError(f"no {platform.upper()}: JAX found "
                         f"{devs[0].platform!r} devices")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes():
    import jax
    peaks_ = [(d.memory_stats() or {}).get("peak_bytes_in_use")
              for d in jax.devices()]
    peaks_ = [p for p in peaks_ if p is not None]
    return max(peaks_) if peaks_ else None


class CompileCounter:
    """Counts programs lowered and compiled while ``active``."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.active = False
        self.counts = {e: 0 for e in self.EVENTS}

        def listen(event, duration, **_):
            if self.active and event in self.counts:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    @property
    def lowered(self) -> int:
        return self.counts[self.EVENTS[0]]

    @property
    def compiled(self) -> int:
        return self.counts[self.EVENTS[1]]


def start_trace(path) -> None:
    """Start the JAX profiler: device ops and host annotations, without
    the Python call tracer (it would slow the host path it measures) or
    the HLO protos (size)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(path), profiler_options=opts)


def use_program() -> None:
    """Put the system under test (``src/``) on the import path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def seed_root(seed: int) -> int:
    """A seed as a non-negative integer numpy's generators accept."""
    return int(seed) % (1 << 64)
