"""Serving telemetry: a lock-guarded ring buffer of per-request events
plus structured snapshots.

Every completed request (ok / degraded / retried / timeout / overflow /
error / failed / shutdown) lands one event dict in a bounded ring (``collections.deque(maxlen=)``)
recording end-to-end latency, queue wait, queue depth at enqueue, the
batch occupancy it rode in (live slots / capacity), whether its model
came out of the warm cache, and — when the answering solver was the CG
tier — a per-solve :class:`~repro.kernels.fused_cg.ops.CGStats` summary
(max iterations, worst residual, all-converged flag).

:meth:`Telemetry.snapshot` reduces the ring into a structured block:
request counts by status/kind, p50/p99 latency per request kind, mean
queue depth and batch occupancy, the attached cache's hit/miss/byte
stats, and the process-wide per-site unconverged-CG counters that the
rate-limited ``warn_unconverged`` accumulates
(``kernels/fused_cg/ops.unconverged_counts``). The soak scripts read
it; ``benchmarks/exec_time.py`` takes only its cache and status counts.
The timeline of a request's stages is not kept here: the profiler spans
of ``repro.runtime.span`` carry it, on the device trace's clock.

Requests answered through the adaptive fidelity router
(``fidelity="auto"``, ``core/router.py``) additionally land a ``route``
sub-dict on their event — chosen rung, certified observation-error
bound, accuracy target and margin — which ``snapshot()`` reduces into a
``router`` block: answer counts per rung, escalation total, and the
tightest certificate margin in the window.

Edge-case contract (pinned by ``tests/test_telemetry.py``): percentiles
are well-defined at EVERY sample count — an empty ring yields an empty
``latency`` map and NaN depth/occupancy means (never IndexError, never
a misleading 0.0), a single-sample kind reports that sample as both p50
and p99, and two samples interpolate.
"""
from __future__ import annotations

import collections
import threading
from typing import Dict, List, Optional

import numpy as np


def _percentile(values: List[float], q: float) -> float:
    """Percentile that is well-defined at every sample count: NaN for an
    empty list (never IndexError, never a misleading 0.0), the sample
    itself for n=1, linear interpolation for n>=2 — so a p99 over one or
    two samples reports a real latency, not an artifact."""
    if not values:
        return float("nan")
    vals = np.asarray(values, np.float64)
    if vals.size == 1:
        return float(vals[0])
    return float(np.percentile(vals, q))


class Telemetry:
    """Ring buffer of per-request events + counters (thread-safe)."""

    def __init__(self, ring: int = 1024, cache=None):
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self.cache = cache            # ModelCache whose stats() to embed
        self.counts: Dict[str, int] = {}   # by status
        self.submitted = 0
        self._stats_fns: Dict[str, object] = {}

    def register_stats(self, name: str, fn) -> None:
        """Attach a named stats provider (e.g. the oracle's supervisor
        or disk cache): ``fn()`` is called at snapshot time and its
        dict lands under ``snapshot()[name]``; a provider returning
        None is omitted (the subsystem isn't attached)."""
        with self._lock:
            self._stats_fns[name] = fn

    # ------------------------------------------------------------------
    def note_submit(self) -> None:
        with self._lock:
            self.submitted += 1

    def record(self, *, kind: str, status: str, latency_s: float,
               queue_s: float = 0.0, queue_depth: int = 0,
               occupancy: float = 0.0, cache_hit: Optional[bool] = None,
               cg: Optional[dict] = None, **extra) -> None:
        """Append one per-request event (called once per response)."""
        event = {"kind": kind, "status": status,
                 "latency_s": float(latency_s),
                 "queue_s": float(queue_s),
                 "queue_depth": int(queue_depth),
                 "occupancy": float(occupancy),
                 "cache_hit": cache_hit, "cg": cg, **extra}
        with self._lock:
            self._ring.append(event)
            self.counts[status] = self.counts.get(status, 0) + 1

    # ------------------------------------------------------------------
    def snapshot(self) -> dict:
        """Structured reduction of the ring (module docstring)."""
        from ..kernels.fused_cg.ops import fallback_counts, \
            unconverged_counts
        with self._lock:
            events = list(self._ring)
            counts = dict(self.counts)
            submitted = self.submitted
            stats_fns = dict(self._stats_fns)
        by_kind: Dict[str, List[float]] = {}
        depths, occs = [], []
        routed: List[dict] = []
        fallbacks: Dict[str, int] = {}
        answered = 0
        for e in events:
            if e["status"] in ("ok", "degraded", "retried"):
                by_kind.setdefault(e["kind"], []).append(e["latency_s"])
                depths.append(e["queue_depth"])
                occs.append(e["occupancy"])
                answered += 1
            if e.get("route"):
                routed.append(e["route"])
            fb = e.get("fallback")
            if fb:
                fallbacks[fb.get("site", "?")] = \
                    fallbacks.get(fb.get("site", "?"), 0) + 1
        latency = {
            kind: {"p50_s": _percentile(vals, 50),
                   "p99_s": _percentile(vals, 99),
                   "mean_s": float(np.mean(vals)), "n": len(vals)}
            for kind, vals in sorted(by_kind.items())}
        # a window with no answered requests has NO mean depth/occupancy:
        # report NaN (format-safe for the %.2f consumers), never a 0.0
        # that reads as "idle queue, empty batches"
        snap = {
            "submitted": submitted,
            "completed": int(sum(counts.values())),
            "by_status": counts,
            "latency": latency,
            "mean_queue_depth": float(np.mean(depths)) if depths
            else float("nan"),
            "mean_batch_occupancy": float(np.mean(occs)) if occs
            else float("nan"),
            "ring_events": len(events),
            "cg_unconverged_sites": unconverged_counts(),
            "solver_fallbacks": fallback_counts(),
        }
        if fallbacks:
            snap["request_fallbacks"] = fallbacks
        if routed:
            snap["router"] = self._reduce_routes(routed)
        if self.cache is not None:
            snap["cache"] = self.cache.stats()
        for name, fn in sorted(stats_fns.items()):
            sub = fn()
            if sub is not None:
                snap[name] = sub
        return snap

    @staticmethod
    def _reduce_routes(routed: List[dict]) -> dict:
        """Aggregate the adaptive-fidelity route events in the window:
        how often each rung answered, total escalations, and the
        tightest certificate margin (tol - certified; negative would
        mean an accepted answer outside its accuracy target)."""
        by_rung: Dict[str, int] = {}
        rung_failures: Dict[str, int] = {}
        breaker_skips: Dict[str, int] = {}
        margins = []
        escalations = 0
        breaker_trips = 0
        uncertified = 0
        for r in routed:
            by_rung[r["rung"]] = by_rung.get(r["rung"], 0) + 1
            escalations += int(r.get("escalations", 0))
            if r.get("margin") is not None:
                margins.append(float(r["margin"]))
            if r.get("certified_ok") is False:
                uncertified += 1
            for t in r.get("tried") or []:
                rung = t.get("rung", "?")
                if "error" in t:
                    rung_failures[rung] = rung_failures.get(rung, 0) + 1
                if t.get("breaker_tripped"):
                    breaker_trips += 1
                if t.get("breaker") == "open":
                    breaker_skips[rung] = breaker_skips.get(rung, 0) + 1
        out = {"n_routed": len(routed), "by_rung": by_rung,
               "escalations": escalations,
               "min_margin": min(margins) if margins else None,
               "worst_certified": max(
                   (float(r["certified"]) for r in routed
                    if r.get("certified") is not None), default=None)}
        if rung_failures or breaker_trips or breaker_skips:
            out["rung_failures"] = rung_failures
            out["breaker_trips"] = breaker_trips
            out["breaker_skips"] = breaker_skips
        if uncertified:
            out["uncertified_answers"] = uncertified
        return out
