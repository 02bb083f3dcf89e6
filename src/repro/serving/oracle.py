"""The thermal oracle: a persistent in-process query service over the
fidelity ladder.

MFIT's runtime end (DTPM at milliseconds) only pays off when the models
sit behind an always-on service — many concurrent queries against warm
models, not one-shot ``build()`` scripts (cf. 3D-ICE 4.0's server mode).
:class:`ThermalOracle` is that service:

  * **Requests.** Steady (``q -> temps``), transient (``q[T,S] ->
    temps[T,O]``), DTPM control traces (``powers[T,S] -> t_max/throttle
    telemetry`` via :class:`~repro.core.dtpm.ThermalManager`), and their
    design-space forms against a ``PackageFamily`` (per-candidate params
    + q). Clients submit from any thread and get a :class:`PendingResult`
    future; every outcome is a structured :class:`OracleResponse` —
    deadline expiry and queue overflow are *statuses*, never crashes,
    and a CG solve that hits its iteration cap degrades the response
    instead of silently returning garbage.

  * **Continuous batching.** One worker thread drains the queue through
    ``serving/batcher.py``: same-model same-shape requests coalesce into
    fixed-capacity batches answered by ``simulate_batch`` (single
    package) or the ``FamilyExecutor``-routed ``steady_state_batch`` /
    ``simulate_family`` (family), with short batches padded — zero power
    rows on the trace axis, the family's ``base_params()`` on the
    candidate axis, exactly the always-valid padding the PR-5 executor
    uses — so one compiled executable serves the stream and a finishing
    request's slot is refilled without recompilation. Steady queries on
    the single-package path answer per-slot through the model's
    host-prefactored solve (already microseconds on the ROM rung; the
    batch there amortizes dispatch and telemetry, not device work).

  * **Warm cache.** Models are content-addressed
    (``serving/cache.py``): repeat geometries skip discretization,
    symbolic assembly, COO/fused-CG plans and the ROM basis build.
    ``warm()`` pre-builds; the hit/miss counters ride every response.

  * **Telemetry.** Per-request latency, queue depth, batch occupancy,
    cache hit rate and CG stats land in ``serving/telemetry.py``'s ring
    buffer; ``telemetry.snapshot()`` is its structured view, which the
    soak scripts read. Beside it, profiler spans
    (``repro.runtime.span``) time each stage on the device trace's
    clock: ``oracle.submit`` (child ``cache.key``) on the caller's
    thread; ``oracle.batch`` (``rows``, the requests it answers) with
    children ``cache.get``, the model's own spans, ``oracle.land`` and
    ``oracle.respond`` on the worker.

  * **Adaptive routing.** ``fidelity="auto"`` (default or per request)
    answers through the certified router (``core/router.py``): the
    oracle builds one ``RoutedThermalSimulator`` per (geometry, tol)
    cache key — routing knobs fold into ``fidelity.cache_key``, so
    auto-built models never alias hand-picked rungs — and every
    response carries its ``route`` (chosen rung, certified error bound,
    margin), which also lands as a telemetry route event.

  * **Self-healing (PR-9).** A :class:`~repro.serving.supervisor
    .WorkerSupervisor` watches the worker thread: a crash (bug, OOM, or
    an injected ``serving.worker`` fault) restarts it after a jittered
    backoff and re-drives the in-flight group ONCE — answers come back
    ``status="retried"``; a request that kills the worker twice is
    answered ``"failed"``, never hung. ``shutdown()`` drains every
    queued future with terminal ``"shutdown"`` responses. Numerical
    poison (NaN/Inf out of a rung's solver) is caught by the model-level
    guardrails (``core/rom.py`` / ``core/dss.py``) which promote to a
    reference path and attach the structured ``fallback`` record here;
    on the ``"auto"`` rung, repeated solver failures open a per-rung
    circuit breaker (``core/router.py``) and traffic degrades to the
    next certified rung.

  * **Disk tier.** ``disk=DiskCache(path)`` persists the expensive ROM
    Krylov basis across PROCESS restarts (checksummed, atomically
    written — ``serving/diskcache.py``): the next process warm-loads
    the basis and rebuilds the cheap parts, closing the ROADMAP item on
    amortizing the ~98 s 8k-node basis build.

``x64=True`` builds and executes every model under
``repro.runtime.x64()`` *on the worker thread* (the flag is
thread-local — a client-side context manager would not reach the
worker); the f64 parity tests run the service in this mode.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import traceback
from typing import Dict, Optional, Tuple

import numpy as np

from ..core.dtpm import ThermalManager
from ..core.fidelity import build, build_family
from ..core.geometry import Package
from ..runtime import span, x64
from ..testing import faults
from .batcher import ContinuousBatcher
from .cache import ModelCache
from .diskcache import DiskCache
from .supervisor import WorkerSupervisor
from .telemetry import Telemetry

_KINDS = ("steady", "transient", "dtpm", "family_steady",
          "family_transient")


@dataclasses.dataclass
class OracleResponse:
    """Structured outcome of one request (every path returns one).

    status: "ok" | "degraded" (answered, but a CG solve hit its
            iteration cap — see ``cg``) | "retried" (answered, but only
            after the worker died holding it and the supervisor
            re-drove it on a restarted worker) | "timeout" (deadline
            passed — before dispatch, or mid-batch while the solve ran)
            | "overflow" (queue full at submit) | "error" (the solve
            raised; service stays live) | "failed" (the request killed
            the worker past its retry budget) | "shutdown" (the oracle
            shut down before it could be dispatched).
    value:  temps — (n_obs,) steady, (T, n_obs) transient, (T,) max-temp
            trace for DTPM; None unless answered.
    route:  set when the answering model is the adaptive router
            (``fidelity="auto"``): chosen rung, certified error bound,
            accuracy target, margin, escalation count, ``certified_ok``
            (see ``core/router.py``); None for hand-picked rungs.
    fallback: set when the answering model's numerical guardrail fired
            (non-finite solver output promoted to a reference path):
            {"site", "to", "reason"} — an answer that took the slow
            safe path SAYS so.
    """
    status: str
    value: Optional[np.ndarray] = None
    detail: str = ""
    kind: str = ""
    latency_s: float = 0.0
    queue_s: float = 0.0
    cache_hit: Optional[bool] = None
    occupancy: float = 0.0
    cg: Optional[dict] = None
    info: Optional[dict] = None       # DTPM per-request telemetry
    route: Optional[dict] = None      # adaptive-router route event
    retries: int = 0                  # supervisor re-drives it survived
    fallback: Optional[dict] = None   # numerical-guardrail record

    @property
    def ok(self) -> bool:
        return self.status in ("ok", "degraded", "retried")


@dataclasses.dataclass
class _Request:
    kind: str
    key: str                 # content-addressed model key
    target: object           # Package | PackageFamily
    fidelity: str
    opts: dict
    payload: dict            # request arrays (q / q_traj / params / ...)
    group_key: tuple
    control: Optional[tuple] = None   # DTPM controller params


class PendingResult:
    """Client-side future for one submitted request."""

    def __init__(self, req: _Request, deadline: Optional[float]):
        self.req = req
        self.deadline = deadline          # absolute time.monotonic()
        self.enq_t = time.monotonic()
        self.queue_depth = 0              # stamped by the batcher
        self.retries = 0                  # supervisor re-drive count
        self._event = threading.Event()
        self._response: Optional[OracleResponse] = None

    @property
    def group_key(self) -> tuple:
        return self.req.group_key

    def fulfill(self, response: OracleResponse) -> None:
        response.kind = self.req.kind
        self._response = response
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: Optional[float] = None) -> OracleResponse:
        """Block for the response. ``timeout`` bounds the client-side
        WAIT (raises TimeoutError); server-side deadlines are set per
        request at submit and come back as status="timeout"."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"{self.req.kind} request not answered within {timeout}s "
                f"(server-side deadline responses use status='timeout')")
        return self._response


class ThermalOracle:
    """Persistent in-process thermal-query service (see module doc).

    fidelity:  default answering rung ("rom" — microsecond steps,
               node-count independent); per-request override allowed.
    capacity:  fixed batch capacity (the compiled batch shape).
    max_queue: queue bound; submissions past it get overflow responses.
    x64:       build + execute everything in f64 (thread-local jax flag,
               applied on the worker; part of the cache key).
    default_deadline_s: deadline applied when a request names none.
    """

    def __init__(self, fidelity: str = "rom", capacity: int = 8,
                 max_queue: int = 256, cache: Optional[ModelCache] = None,
                 telemetry: Optional[Telemetry] = None, x64: bool = False,
                 default_deadline_s: Optional[float] = None,
                 build_opts: Optional[dict] = None, autostart: bool = True,
                 supervise: bool = True,
                 disk: Optional[DiskCache] = None):
        self.fidelity = fidelity
        self.capacity = int(capacity)
        self.x64 = bool(x64)
        self.default_deadline_s = default_deadline_s
        self.build_opts = dict(build_opts or {})
        self.cache = cache if cache is not None else ModelCache()
        self.disk = disk
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry(cache=self.cache)
        self._managers: Dict[tuple, ThermalManager] = {}
        self._managers_lock = threading.Lock()
        self._shutting_down = False
        self._batcher = ContinuousBatcher(
            self._execute, self._expire, capacity=capacity,
            max_queue=max_queue)
        self._supervisor = WorkerSupervisor(
            self._batcher, on_fail=self._on_fail) if supervise else None
        self.telemetry.register_stats(
            "supervisor", lambda: self._supervisor.stats()
            if self._supervisor else None)
        self.telemetry.register_stats(
            "disk", lambda: self.disk.stats() if self.disk else None)
        if autostart:
            self.start()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------
    def start(self) -> "ThermalOracle":
        self._batcher.start()
        if self._supervisor is not None:
            self._supervisor.start()
        return self

    def shutdown(self) -> None:
        """Stop the service; every still-pending future is answered with
        a terminal ``status="shutdown"`` response — clients blocked in
        ``result()`` are released, never hung."""
        self._shutting_down = True
        if self._supervisor is not None:
            self._supervisor.stop()
        self._batcher.stop()

    def close(self) -> None:
        self.shutdown()

    def __enter__(self) -> "ThermalOracle":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.shutdown()

    # ------------------------------------------------------------------
    # model plumbing
    # ------------------------------------------------------------------
    def _opts(self, fidelity: str, opts: Optional[dict]) -> dict:
        return {**self.build_opts, **(opts or {})}

    def _key(self, target, fidelity: str, opts: dict) -> str:
        with span("cache.key"):
            return self.cache.key_for(target, fidelity, opts,
                                      extra=("x64", self.x64))

    #: build opts that shape the ROM Krylov basis — everything else
    #: (ts, dtype, ...) reuses the same persisted basis.
    _BASIS_OPTS = ("r", "n_moments", "solver")

    def _build(self, target, fidelity: str, opts: dict):
        """Build a model; with a disk tier attached, ROM builds
        warm-load the persisted Krylov basis (checksum-verified;
        corruption -> rebuild) via ``build(..., basis=)`` and publish a
        freshly built basis for the NEXT process. Everything cheap
        (network assembly, projection, jit) always rebuilds live —
        only the build-time-dominant artifact is persisted."""
        fn = build if isinstance(target, Package) else build_family
        persist_key = None
        if self.disk is not None and fidelity == "rom" \
                and "basis" not in opts:
            basis_key = self.cache.key_for(
                target, "rom_basis",
                {k: opts[k] for k in self._BASIS_OPTS if k in opts},
                extra=("x64", self.x64))
            basis = self.disk.get(basis_key)
            if basis is not None:
                opts = {**opts, "basis": np.asarray(basis, np.float64)}
            else:
                persist_key = basis_key
        if self.x64:
            with x64():
                model = fn(target, fidelity, **opts)
        else:
            model = fn(target, fidelity, **opts)
        if persist_key is not None and getattr(model, "V", None) \
                is not None:
            self.disk.put(persist_key, np.asarray(model.V, np.float64))
        return model

    def _model(self, req: _Request) -> Tuple[object, bool, float]:
        with span("cache.get"):
            return self.cache.get_or_build(
                req.key, lambda: self._build(req.target, req.fidelity,
                                             req.opts))

    def warm(self, target, fidelity: Optional[str] = None,
             **opts) -> Tuple[str, bool, float]:
        """Pre-build a model into the warm cache: ``(key, hit,
        build_s)``. The explicit API for amortizing one-time builds
        (e.g. the ~98 s 8k-node ROM basis) before traffic arrives."""
        fidelity = fidelity or self.fidelity
        opts = self._opts(fidelity, opts)
        key = self._key(target, fidelity, opts)
        _, hit, build_s = self.cache.get_or_build(
            key, lambda: self._build(target, fidelity, opts))
        return key, hit, build_s

    def _manager(self, req: _Request, model) -> ThermalManager:
        mkey = (req.key, req.control)
        with self._managers_lock:
            mgr = self._managers.get(mkey)
            if mgr is None:
                mgr = ThermalManager(dss=model, **dict(req.control))
                self._managers[mkey] = mgr
            return mgr

    # ------------------------------------------------------------------
    # submission API (any thread)
    # ------------------------------------------------------------------
    @staticmethod
    def _check_payload(payload: dict) -> None:
        """Reject non-finite request arrays at SUBMIT time, naming the
        offending field — poison must not reach the shared batch (one
        NaN row would contaminate its whole compiled group)."""
        for name, arr in payload.items():
            if isinstance(arr, np.ndarray) \
                    and not np.isfinite(arr).all():
                raise ValueError(
                    f"request array {name!r} contains non-finite "
                    f"values (NaN/Inf); refusing to enqueue")

    def _submit(self, req: _Request,
                deadline_s: Optional[float]) -> PendingResult:
        self._check_payload(req.payload)
        if deadline_s is None:
            deadline_s = self.default_deadline_s
        deadline = None if deadline_s is None \
            else time.monotonic() + deadline_s
        pending = PendingResult(req, deadline)
        self.telemetry.note_submit()
        accepted = self._batcher.submit(pending)
        if accepted is None:           # stopping: terminal, never hangs
            pending.fulfill(OracleResponse(
                status="shutdown",
                detail="oracle is shut down; request rejected at submit"))
            self.telemetry.record(kind=req.kind, status="shutdown",
                                  latency_s=0.0, queue_depth=0)
        elif accepted is False:
            resp = OracleResponse(
                status="overflow",
                detail=f"queue full ({self._batcher.max_queue}); request "
                       f"rejected at submit — retry with backoff")
            pending.fulfill(resp)
            self.telemetry.record(kind=req.kind, status="overflow",
                                  latency_s=0.0,
                                  queue_depth=self._batcher.max_queue)
        return pending

    def submit_steady(self, pkg: Package, q, fidelity: Optional[str] = None,
                      opts: Optional[dict] = None,
                      deadline_s: Optional[float] = None) -> PendingResult:
        with span("oracle.submit"):
            fidelity = fidelity or self.fidelity
            opts = self._opts(fidelity, opts)
            key = self._key(pkg, fidelity, opts)
            req = _Request("steady", key, pkg, fidelity, opts,
                           {"q": np.asarray(q, np.float64)},
                           group_key=(key, "steady"))
            return self._submit(req, deadline_s)

    def submit_transient(self, pkg: Package, q_traj, dt: float,
                         fidelity: Optional[str] = None,
                         opts: Optional[dict] = None,
                         deadline_s: Optional[float] = None
                         ) -> PendingResult:
        with span("oracle.submit"):
            fidelity = fidelity or self.fidelity
            opts = self._opts(fidelity, opts)
            key = self._key(pkg, fidelity, opts)
            q_traj = np.asarray(q_traj, np.float64)
            req = _Request("transient", key, pkg, fidelity, opts,
                           {"q_traj": q_traj, "dt": float(dt)},
                           group_key=(key, "transient", q_traj.shape[0],
                                      round(float(dt), 12)))
            return self._submit(req, deadline_s)

    def submit_dtpm(self, pkg: Package, powers_traj,
                    fidelity: Optional[str] = None,
                    opts: Optional[dict] = None,
                    control: Optional[dict] = None,
                    deadline_s: Optional[float] = None) -> PendingResult:
        """DTPM control-trace request: roll the ThermalManager over a
        (T, S) full-speed power trace; the response's ``value`` is the
        max-temp trace and ``info`` carries the per-request controller
        telemetry (throttle trace, violations, headroom)."""
        fidelity = fidelity or self.fidelity
        if fidelity not in ("dss", "rom"):
            raise ValueError("DTPM requests need a state-space rung "
                             "('dss' or 'rom'), got "
                             f"fidelity={fidelity!r}")
        with span("oracle.submit"):
            opts = self._opts(fidelity, opts)
            key = self._key(pkg, fidelity, opts)
            powers_traj = np.asarray(powers_traj, np.float64)
            ctrl = tuple(sorted((control or {}).items()))
            req = _Request("dtpm", key, pkg, fidelity, opts,
                           {"powers": powers_traj},
                           group_key=(key, "dtpm", powers_traj.shape[0],
                                      ctrl),
                           control=ctrl)
            return self._submit(req, deadline_s)

    def submit_family_steady(self, family, params, q,
                             fidelity: Optional[str] = None,
                             opts: Optional[dict] = None,
                             deadline_s: Optional[float] = None
                             ) -> PendingResult:
        """One design-space candidate: params (P,), q (S,). Concurrent
        candidates against the same family coalesce into one
        ``steady_state_batch`` at the fixed capacity (pad =
        ``base_params()``)."""
        with span("oracle.submit"):
            fidelity = fidelity or self.fidelity
            opts = self._opts(fidelity, opts)
            key = self._key(family, fidelity, opts)
            req = _Request("family_steady", key, family, fidelity, opts,
                           {"params": np.asarray(params, np.float64),
                            "q": np.asarray(q, np.float64)},
                           group_key=(key, "family_steady"))
            return self._submit(req, deadline_s)

    def submit_family_transient(self, family, params, q_traj, dt: float,
                                fidelity: Optional[str] = None,
                                opts: Optional[dict] = None,
                                deadline_s: Optional[float] = None
                                ) -> PendingResult:
        with span("oracle.submit"):
            fidelity = fidelity or self.fidelity
            opts = self._opts(fidelity, opts)
            key = self._key(family, fidelity, opts)
            q_traj = np.asarray(q_traj, np.float64)
            req = _Request("family_transient", key, family, fidelity,
                           opts,
                           {"params": np.asarray(params, np.float64),
                            "q_traj": q_traj, "dt": float(dt)},
                           group_key=(key, "family_transient",
                                      q_traj.shape[0],
                                      round(float(dt), 12)))
            return self._submit(req, deadline_s)

    # blocking conveniences -------------------------------------------------
    def query_steady(self, pkg, q, **kw) -> OracleResponse:
        return self.submit_steady(pkg, q, **kw).result()

    def query_transient(self, pkg, q_traj, dt, **kw) -> OracleResponse:
        return self.submit_transient(pkg, q_traj, dt, **kw).result()

    def query_dtpm(self, pkg, powers_traj, **kw) -> OracleResponse:
        return self.submit_dtpm(pkg, powers_traj, **kw).result()

    # ------------------------------------------------------------------
    # worker-side execution (single thread; jit caches stay single-owner)
    # ------------------------------------------------------------------
    def _expire(self, pending: PendingResult) -> None:
        if pending.done():             # already answered (e.g. failed
            return                     # by the supervisor) — keep it
        now = time.monotonic()
        if self._shutting_down:        # stop() drains the queue here
            resp = OracleResponse(
                status="shutdown", latency_s=now - pending.enq_t,
                queue_s=now - pending.enq_t,
                detail="oracle shut down before the request was "
                       "dispatched")
        else:
            resp = OracleResponse(
                status="timeout", latency_s=now - pending.enq_t,
                queue_s=now - pending.enq_t,
                detail="deadline passed before dispatch (queue wait "
                       f"{now - pending.enq_t:.3f}s)")
        pending.fulfill(resp)
        self.telemetry.record(kind=pending.req.kind, status=resp.status,
                              latency_s=resp.latency_s,
                              queue_s=resp.queue_s,
                              queue_depth=pending.queue_depth)

    def _on_fail(self, pending: PendingResult,
                 exc: BaseException) -> None:
        """Supervisor callback: the request killed the worker past its
        retry budget — terminal structured failure, never a hang."""
        if pending.done():
            return
        now = time.monotonic()
        resp = OracleResponse(
            status="failed", latency_s=now - pending.enq_t,
            retries=pending.retries,
            detail=f"worker crashed while executing this request "
                   f"({type(exc).__name__}: {exc}); retry budget "
                   f"exhausted after {pending.retries} re-drive(s)")
        pending.fulfill(resp)
        self.telemetry.record(kind=pending.req.kind, status="failed",
                              latency_s=resp.latency_s,
                              queue_depth=pending.queue_depth)

    def _execute(self, group_key: tuple, group) -> None:
        try:
            with span("oracle.batch", rows=len(group)):
                if self.x64:
                    with x64():
                        self._answer(group)
                else:
                    self._answer(group)
        except Exception as exc:  # noqa: BLE001 — service must stay live
            now = time.monotonic()
            frame = traceback.extract_tb(exc.__traceback__)[-1]
            detail = (f"{type(exc).__name__}: {exc} "
                      f"[at {frame.filename.rsplit('/', 1)[-1]}:"
                      f"{frame.lineno} in {frame.name}]")
            for p in group:
                if not p.done():
                    p.fulfill(OracleResponse(
                        status="error", latency_s=now - p.enq_t,
                        detail=detail))
                    self.telemetry.record(kind=p.req.kind,
                                          status="error",
                                          latency_s=now - p.enq_t,
                                          queue_depth=p.queue_depth)

    @staticmethod
    def _cg_summary(model) -> Optional[dict]:
        stats = getattr(model, "last_cg_stats", None)
        if stats is None:
            stats = getattr(getattr(model, "rcf", None), "last_cg_stats",
                            None)
        if stats is None:
            return None
        conv = np.asarray(stats.converged)
        return {"max_iterations": int(np.asarray(stats.iterations).max()),
                "worst_residual": float(np.asarray(stats.residual).max()),
                "converged": bool(conv.all())}

    def _answer(self, group) -> None:
        faults.fire("serving.answer")   # chaos hook: batcher-side
        req0 = group[0].req             # exceptions / stalls mid-batch
        start = time.monotonic()
        model, hit, _ = self._model(req0)
        kind = req0.kind
        slot_routes: Optional[list] = None
        slot_fallbacks: Optional[list] = None
        if kind == "steady":
            # per-slot solves: capture the router's route AND any
            # numerical-guardrail fallback per slot (a hand-picked rung
            # has no last_route -> None, no event)
            values, slot_routes, slot_fallbacks = [], [], []
            for p in group:
                values.append(np.asarray(model.observe(
                    model.steady_state(p.req.payload["q"]))))
                slot_routes.append(getattr(model, "last_route", None))
                slot_fallbacks.append(
                    getattr(model, "last_fallback", None))
        elif kind == "transient":
            values = self._answer_transient(model, group)
        elif kind == "dtpm":
            values = self._answer_dtpm(model, group)
        elif kind == "family_steady":
            values = self._answer_family_steady(model, group)
        elif kind == "family_transient":
            values = self._answer_family_transient(model, group)
        else:  # unreachable: submit_* constrain kinds
            raise ValueError(f"unknown request kind {kind!r}")
        if slot_routes is None:
            slot_routes = self._routes_of(model, kind, len(group))
        if slot_fallbacks is None:     # batched kinds fall back (or
            slot_fallbacks = [getattr(model, "last_fallback", None)
                              ] * len(group)    # not) as one batch
        cg = self._cg_summary(model)
        degraded = cg is not None and not cg["converged"]
        done = time.monotonic()
        occupancy = len(group) / self.capacity
        with span("oracle.respond"):
            for i, (p, value) in enumerate(zip(group, values)):
                info = None
                if isinstance(value, tuple):   # dtpm: (trace, telemetry)
                    value, info = value
                route = slot_routes[i] if i < len(slot_routes) else None
                fallback = slot_fallbacks[i] \
                    if i < len(slot_fallbacks) else None
                if degraded:
                    status = "degraded"
                    detail = ("CG hit its iteration cap — results may be "
                              "unconverged (see cg)")
                elif p.deadline is not None and done > p.deadline:
                    # the solve outlived the request's deadline mid-batch:
                    # honest timeout, value still attached for best-effort
                    # consumers
                    status = "timeout"
                    detail = (f"deadline passed mid-batch (answered "
                              f"{done - p.deadline:.3f}s late; value "
                              f"attached best-effort)")
                elif p.retries > 0:
                    status = "retried"
                    detail = (f"answered after {p.retries} worker "
                              f"restart(s) — see telemetry 'supervisor'")
                else:
                    status, detail = "ok", ""
                resp = OracleResponse(
                    status=status, value=value, detail=detail,
                    latency_s=done - p.enq_t, queue_s=start - p.enq_t,
                    cache_hit=hit, occupancy=occupancy, cg=cg, info=info,
                    route=route, retries=p.retries, fallback=fallback)
                p.fulfill(resp)
                self.telemetry.record(
                    kind=kind, status=resp.status, latency_s=resp.latency_s,
                    queue_s=resp.queue_s, queue_depth=p.queue_depth,
                    occupancy=occupancy, cache_hit=hit, cg=cg,
                    **({"route": route} if route else {}),
                    **({"fallback": fallback} if fallback else {}))

    @staticmethod
    def _routes_of(model, kind: str, n_slots: int) -> list:
        """Per-slot route events of an adaptive-router answer: the
        routed batched rollout records one route per slot
        (``last_batch_routes``); family kinds share the one certified
        template-probe route; hand-picked rungs record nothing."""
        if kind == "transient":
            batch = getattr(model, "last_batch_routes", None)
            if batch is not None:
                return list(batch)
        if kind.startswith("family"):
            shared = getattr(model, "last_route", None)
            if shared is not None:
                return [shared] * n_slots
        return [None] * n_slots

    # --- per-kind batch answers (fixed capacity, padded slots) --------
    def _answer_transient(self, model, group) -> list:
        t_len, n_src = group[0].req.payload["q_traj"].shape
        dt = group[0].req.payload["dt"]
        q = np.zeros((t_len, self.capacity, n_src))  # pad: zero power
        for i, p in enumerate(group):
            q[:, i, :] = p.req.payload["q_traj"]
        theta0 = model.zero_state(batch=self.capacity)
        obs = model.simulate_batch(theta0, q, dt)    # (T, capacity, O)
        with span("oracle.land"):
            obs = np.asarray(obs)
        return [obs[:, i, :] for i in range(len(group))]

    def _answer_dtpm(self, model, group) -> list:
        mgr = self._manager(group[0].req, model)
        out = []
        for p in group:
            out.append(mgr.serve_trace(p.req.payload["powers"]))
        return out

    def _family_batch(self, group, with_traj: bool):
        fam = group[0].req.target
        base = fam.base_params()
        params = np.broadcast_to(base, (self.capacity, base.shape[0])) \
            .copy()                                  # pad: base_params
        for i, p in enumerate(group):
            params[i] = p.req.payload["params"]
        if not with_traj:
            n_src = group[0].req.payload["q"].shape[0]
            q = np.zeros((self.capacity, n_src))
            for i, p in enumerate(group):
                q[i] = p.req.payload["q"]
            return params, q
        t_len, n_src = group[0].req.payload["q_traj"].shape
        q = np.zeros((t_len, self.capacity, n_src))
        for i, p in enumerate(group):
            q[:, i, :] = p.req.payload["q_traj"]
        return params, q

    def _answer_family_steady(self, model, group) -> list:
        params, q = self._family_batch(group, with_traj=False)
        theta = model.steady_state_batch(params, q)
        temps = model.observe_batch(theta, params)
        with span("oracle.land"):
            temps = np.asarray(temps)
        return [temps[i] for i in range(len(group))]

    def _answer_family_transient(self, model, group) -> list:
        params, q = self._family_batch(group, with_traj=True)
        dt = group[0].req.payload["dt"]
        obs = model.simulate_family(params, q, dt)
        with span("oracle.land"):
            obs = np.asarray(obs)
        return [obs[:, i, :] for i in range(len(group))]
