"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

Device planes are those named ``/device:TPU:<i>``. On each, the events
of the "XLA Ops" line are the operations that ran (every line of the
plane where it has none). From them:

  * busy: the union of the op intervals inside the traced window,
    averaged over the devices; idle share = 1 - busy / window;
  * per-name device time: summed op durations by event name;
  * idle gaps: stretches of the window in which device 0 ran nothing,
    each named by the benchmark's host annotation (``bench.*``) that
    overlaps it most, or "untraced host" where the stretch outside every
    annotation is longer.

An op is named by its HLO instruction name (``%fused_cg_step.3``, the
text before `` = `` in the event's name). Ops that hold other ops, such
as a ``while`` around its body, count toward busy time but not toward
per-name time, so that no time is counted twice there.

Nothing here knows about a kernel or a cell; metric readers under
``metrics/`` ask for names.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

_OPS_LINE = "XLA Ops"
_ANNOTATION_PREFIX = "bench."


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float                      # mean over devices
    n_devices: int
    op_seconds: Dict[str, float]       # summed over devices
    op_counts: Dict[str, int]
    gaps: List[Tuple[str, float]]      # longest idle gaps, device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, fragment: str) -> Tuple[float, int]:
        """Device time and event count of ops whose name holds
        ``fragment``."""
        t = sum(v for k, v in self.op_seconds.items() if fragment in k)
        n = sum(v for k, v in self.op_counts.items() if fragment in k)
        return t, n

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]


def _union(intervals):
    """Sorted disjoint union of (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_events(device_events, host_events, window, n_gaps: int = 10
                  ) -> TraceSummary:
    """device_events: per device, a list of (name, start_ns, dur_ns);
    host_events: (name, start_ns, dur_ns) annotations; window: (lo, hi)
    in ns on the same clock."""
    lo, hi = window
    busy, op_s, op_n = [], defaultdict(float), defaultdict(int)
    for events in device_events:
        events = sorted((s, s + d, n) for n, s, d in events
                        if s + d > lo and s < hi)
        for k, (start, end, name) in enumerate(events):
            nxt = events[k + 1] if k + 1 < len(events) else None
            if nxt is not None and nxt[0] < end and nxt[1] <= end:
                continue                       # holds the next op
            op_s[name] += (min(end, hi) - max(start, lo)) * 1e-9
            op_n[name] += 1
        busy.append(_union(_clip([(s, e) for s, e, _ in events], lo, hi)))
    busy_s = [sum(e - s for s, e in u) * 1e-9 for u in busy]
    gaps = []
    if busy:
        edges = [lo] + [x for s, e in busy[0] for x in (s, e)] + [hi]
        holes = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        holes.sort(key=lambda g: g[0] - g[1])
        for s, e in holes[:n_gaps]:
            covered = _union(_clip([(hs, hs + hd) for _, hs, hd in host_events],
                                   s, e))
            best = "untraced host"
            best_ov = (e - s) - sum(b - a for a, b in covered)
            for name, hs, hd in host_events:
                ov = min(e, hs + hd) - max(s, hs)
                if ov > best_ov:
                    best, best_ov = name, ov
            gaps.append((best, (e - s) * 1e-9))
    n_dev = max(1, len(device_events))
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=sum(busy_s) / n_dev, n_devices=len(busy_s),
                        op_seconds=dict(op_s), op_counts=dict(op_n),
                        gaps=gaps)


def op_name(event_name: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(...)`` -> ``%fusion.3``."""
    return event_name.split(" = ", 1)[0]


def read_xplane(path: str):
    """(device events per device, bench host annotations) from a file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == _OPS_LINE] or lines
            devices.append((plane.name, [
                (op_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ln in ops for ev in ln.events]))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(_ANNOTATION_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return [ev for _, ev in devices], host


def summarize(path: str, window_annotation: str = "bench.window"
              ) -> TraceSummary:
    """Reduce one trace file. The traced window is the span of the
    ``bench.window`` annotation the harness writes around it."""
    devices, host = read_xplane(path)
    spans = [(s, s + d) for name, s, d in host if name == window_annotation]
    if not spans:
        raise ValueError(f"trace holds no {window_annotation!r} annotation")
    window = (min(s for s, _ in spans), max(e for _, e in spans))
    inner = [h for h in host if h[0] != window_annotation]
    return reduce_events(devices, inner, window)
