"""The program's own spans in a traced run, and the device's idle time
split by them.

The program writes ``mfit.*`` spans into the profiler trace
(``repro.runtime.span``), on the clock of the device's operations, with
small counters as their args (``rows``, ``slots``, ``first``, ...). Per
host thread the spans nest. From one trace this module takes:

  * the traced window, from the harness's ``bench.window`` annotation;
  * the device operations, from ``bench.trace.read_xplane``;
  * every ``mfit.*`` span with its thread, args, parent and the
    outermost span open on its thread (its root);
  * device seconds per XLA program (the "XLA Modules" line).

It then splits the window's device-idle time (no operation running on a
device; per device, averaged over the devices) by the innermost span
open at each instant: a worker thread's first (a thread that opens any
span other than ``oracle.submit`` and ``cache.key``), else a caller's,
else ``outside``. The split is keyed by (root, innermost span). The idle
time a worker's span takes is split again by the caller's innermost span
open beside it (``outside`` where none is): a worker that waits for the
interpreter lock while a caller hashes a package shows there as idle
under its own span beside ``cache.key``.

The metric readers under ``metrics/`` call :func:`load`, which finds the
trace this run wrote under ``.bench_trace/<workload>`` (the workload is
the one whose configuration and traffic are the context's) and reads it
once per process. A trace with no ``mfit.*`` span, as a program that
writes none leaves, gives readers nothing to read: they return None.
"""
from __future__ import annotations

import dataclasses
import re
import sys
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import trace as T

PREFIX = "mfit."
#: spans a client opens on its own thread; a thread that opens any other
#: span is a worker
CALLER_SPANS = ("mfit.oracle.submit", "mfit.cache.key")
OUTSIDE = "outside"
_WINDOW = "bench.window"
_MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Span:
    name: str
    start: float                 # ns, the device trace's clock
    end: float
    thread: int
    args: dict
    parent: Optional[int] = None   # index in ProgramSpans.spans
    root: int = -1                 # outermost span open on the thread

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclasses.dataclass
class ProgramSpans:
    window: Tuple[float, float]
    spans: List[Span]
    n_devices: int
    idle_s: float                            # mean over devices
    idle_by_path: Dict[Tuple[str, str], float]   # (root, innermost)
    #: (worker's innermost, caller's innermost or outside): idle seconds
    idle_beside: Dict[Tuple[str, str], float]
    device_by_program: Dict[str, float]

    @property
    def idle_by_span(self) -> Dict[str, float]:
        """Idle seconds per innermost span, and ``outside``."""
        out: Dict[str, float] = defaultdict(float)
        for (_, name), s in self.idle_by_path.items():
            out[name] += s
        return dict(out)

    @property
    def idle_beside_caller(self) -> Dict[str, Dict[str, float]]:
        """Idle seconds under each worker span, by the caller's innermost
        span open at the same instant (``outside``: none)."""
        out: Dict[str, Dict[str, float]] = defaultdict(dict)
        for (name, caller), s in self.idle_beside.items():
            out[name][caller] = s
        return dict(out)

    def in_window(self, name: str) -> List[Span]:
        """Spans named ``name`` that start inside the window."""
        lo, hi = self.window
        return [s for s in self.spans
                if s.name == name and lo <= s.start < hi]

    def ancestor(self, i: int, name: str) -> Optional[int]:
        """Index of the nearest span named ``name`` around span ``i``."""
        j = self.spans[i].parent
        while j is not None:
            if self.spans[j].name == name:
                return j
            j = self.spans[j].parent
        return None

    def idle_where(self, pick) -> float:
        """Idle seconds whose (root, innermost) satisfies ``pick``."""
        return sum(s for (root, name), s in self.idle_by_path.items()
                   if pick(root, name))


def nest(events) -> List[Span]:
    """Spans from ``(name, start_ns, dur_ns, thread, args)`` events, with
    each one's parent and root on its own thread."""
    order = sorted(range(len(events)),
                   key=lambda k: (events[k][3], events[k][1],
                                  -events[k][2]))
    spans: List[Span] = []
    stack: List[int] = []
    for k in order:
        name, start, dur, thread, args = events[k]
        while stack and (spans[stack[-1]].thread != thread
                         or spans[stack[-1]].end <= start):
            stack.pop()
        i = len(spans)
        parent = stack[-1] if stack else None
        spans.append(Span(name, float(start), float(start) + float(dur),
                          thread, dict(args), parent,
                          spans[parent].root if parent is not None else i))
        stack.append(i)
    return spans


def _segments(spans: List[Span], thread: int):
    """The thread's timeline as disjoint pieces (start, end, innermost
    span index)."""
    bounds = []
    for i, s in enumerate(spans):
        if s.thread == thread and s.end > s.start:
            bounds += [(s.start, 1, i), (s.end, 0, i)]
    bounds.sort()
    segs, stack, t = [], [], None
    for x, opens, i in bounds:
        if stack and x > t:
            segs.append((t, x, stack[-1]))
        if opens:
            stack.append(i)
        else:
            stack.remove(i)
        t = x
    return segs


def _idle(device_events, lo: float, hi: float):
    """Sorted (starts, ends) of the window's stretches in which the device
    ran no operation: the complement of ``bench.trace``'s busy union."""
    busy = T._union(T._clip([(s, s + d) for _, s, d in device_events],
                            lo, hi))
    edges = np.asarray([lo] + [x for a, b in busy for x in (a, b)] + [hi],
                       float)
    starts, ends = edges[0::2], edges[1::2]
    keep = ends > starts
    return starts[keep], ends[keep]


def _cumulative(starts, ends, t):
    """Idle time in [window start, t] for sorted disjoint idle stretches."""
    if len(starts) == 0:
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])
    j = np.searchsorted(starts, t, side="right") - 1
    inside = np.clip(t - starts[np.maximum(j, 0)], 0.0,
                     (ends - starts)[np.maximum(j, 0)])
    return np.where(j >= 0, cum[np.maximum(j, 0)] + inside, 0.0)


def attribute(spans: List[Span], device_events, window):
    """(idle seconds, idle seconds per (root, innermost) span, idle
    seconds per (worker's innermost, caller's innermost) span), each the
    mean over the devices; idle under no span is ``(outside, outside)``,
    and a worker's span with no caller's beside it pairs with
    ``outside``."""
    lo, hi = window
    threads = sorted({s.thread for s in spans})
    worker = {s.thread for s in spans if s.name not in CALLER_SPANS}
    roles = [[t for t in threads if t in worker],
             [t for t in threads if t not in worker]]
    segs = {t: _segments(spans, t) for t in threads}
    edges = [lo, hi] + [x for t in threads for a, b, _ in segs[t]
                        for x in (a, b) if lo < x < hi]
    edges = np.unique(np.asarray(edges, float))
    mid = (edges[:-1] + edges[1:]) / 2
    innermost = []                 # per role: span index, or -1
    for role in roles:
        best = np.full(len(mid), -1)
        key = np.full(len(mid), -np.inf)
        for t in role:
            if not segs[t]:
                continue
            a, b, i = (np.asarray(c) for c in zip(*segs[t]))
            j = np.searchsorted(a, mid, side="right") - 1
            jj = np.maximum(j, 0)
            on = (j >= 0) & (mid < b[jj])
            started = np.asarray([spans[k].start for k in i])[jj]
            better = on & (started > key)
            best[better] = i[jj][better]
            key[better] = started[better]
        innermost.append(best)
    work, call = innermost
    label = np.where(work >= 0, work, call)
    paths = sorted({(spans[s.root].name, s.name) for s in spans})
    paths.append((OUTSIDE, OUTSIDE))
    names = sorted({s.name for s in spans}) + [OUTSIDE]
    # path and name of each span, and of index -1 (no span): the last
    path_of = np.asarray([paths.index((spans[s.root].name, s.name))
                          for s in spans] + [len(paths) - 1], int)
    name_of = np.asarray([names.index(s.name) for s in spans]
                         + [len(names) - 1], int)
    on_worker = work >= 0
    pair = (name_of[work] * len(names) + name_of[call])[on_worker]
    weights = np.zeros(len(paths))
    beside = np.zeros(len(names) ** 2)
    idle_s = 0.0
    for events in device_events or [[]]:
        gs, ge = _idle(events, lo, hi)
        idle_s += float((ge - gs).sum())
        w = np.diff(_cumulative(gs, ge, edges))
        weights += np.bincount(path_of[label], w, minlength=len(paths))
        beside += np.bincount(pair, w[on_worker], minlength=len(beside))
    n_dev = max(1, len(device_events))
    per_path = {path: float(w) * 1e-9 / n_dev
                for path, w in zip(paths, weights) if w > 0}
    per_pair = {(names[k // len(names)], names[k % len(names)]):
                float(w) * 1e-9 / n_dev
                for k, w in enumerate(beside) if w > 0}
    return idle_s * 1e-9 / n_dev, per_path, per_pair


def summarize(device_events, host_events, program_events,
              module_events=()) -> ProgramSpans:
    """device_events: per device, (name, start_ns, dur_ns) operations;
    host_events: the harness's (name, start_ns, dur_ns) annotations;
    program_events: (name, start_ns, dur_ns, thread, args) spans;
    module_events: (program, start_ns, dur_ns) XLA program runs."""
    wins = [(s, s + d) for n, s, d in host_events if n == _WINDOW]
    if not wins:
        raise ValueError(f"trace holds no {_WINDOW!r} annotation")
    window = (min(s for s, _ in wins), max(e for _, e in wins))
    spans = nest([e for e in program_events if e[0].startswith(PREFIX)])
    idle_s, per, beside = attribute(spans, device_events, window)
    lo, hi = window
    programs: Dict[str, float] = defaultdict(float)
    for name, s, d in module_events:
        clipped = min(s + d, hi) - max(s, lo)
        if clipped > 0:
            programs[name] += clipped * 1e-9
    return ProgramSpans(window=window, spans=spans,
                        n_devices=len(device_events), idle_s=idle_s,
                        idle_by_path=per, idle_beside=beside,
                        device_by_program=dict(programs))


_MODULE_ID = re.compile(r"\(\d+\)$")


def read(path: str) -> ProgramSpans:
    """Reduce one ``.xplane.pb``, parsed once: the device operations as
    ``bench.trace.read_xplane`` takes them, the XLA programs, the
    harness's annotations and the program's spans."""
    from jax.profiler import ProfileData
    devices, host, program, modules = [], [], [], []
    thread = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == T._OPS_LINE] or lines
            devices.append((int(plane.name.rsplit(":", 1)[1]), [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ln in ops for ev in ln.events]))
            modules += [(_MODULE_ID.sub("", ev.name), float(ev.start_ns),
                         float(ev.duration_ns))
                        for ln in lines if ln.name == _MODULES_LINE
                        for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                thread += 1
                for ev in ln.events:
                    if ev.name.startswith(PREFIX):
                        program.append((ev.name, float(ev.start_ns),
                                        float(ev.duration_ns), thread,
                                        dict(ev.stats)))
                    elif ev.name.startswith(T._ANNOTATION_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    devices.sort(key=lambda d: d[0])
    return summarize([ev for _, ev in devices], host, program, modules)


_READ: Dict[str, Optional[ProgramSpans]] = {}


def trace_dir(ctx):
    """``.bench_trace/<workload>`` of the cell whose configuration and
    traffic are the context's, or None where the run wrote none. Two cells
    of one configuration and traffic would leave the trace's cell unknown:
    that raises."""
    from bench import harness as H
    cells = [w["name"] for w in H.spec()["workloads"]
             if w["config"] == ctx["config"].get("name")
             and H.load_json(H.BENCH / "traffic" / f"{w['traffic']}.json")
             == ctx["traffic"]]
    if len(cells) > 1:
        raise ValueError(f"cells {cells} share a configuration and "
                         "traffic: the run's trace is not known")
    path = H.ROOT / ".bench_trace" / cells[0] if cells else None
    return path if path is not None and path.is_dir() else None


def load(ctx) -> Optional[ProgramSpans]:
    """This run's spans, read once per process; None where the run left
    no trace or the trace holds no program span."""
    where = trace_dir(ctx)
    if where is None:
        return None
    key = str(where)
    if key not in _READ:
        t = time.monotonic()
        try:
            got = read(T.find_xplane(key))
        except Exception as exc:  # noqa: BLE001 -- a reading, not a result
            print(f"program_spans: {type(exc).__name__}: {exc}",
                  file=sys.stderr, flush=True)
            got = None
        if got is not None and not got.spans:
            got = None
        _READ[key] = got
        print(f"program_spans_read_s {time.monotonic() - t:.3f}",
              file=sys.stderr, flush=True)
    return _READ[key]


def idle_record(ps: ProgramSpans) -> dict:
    """The splits every idle metric carries beside its value."""
    return {"idle_by_span": ps.idle_by_span,
            "device_by_program": ps.device_by_program}
