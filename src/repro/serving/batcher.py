"""Fixed-capacity continuous batcher: the serving loop that coalesces
concurrent thermal queries into batched solves.

Scope note: the *idiom donor* here is the LM serving scaffold
``launch/serve.py`` — a fixed-capacity batch whose slots are recycled
between requests so ONE compiled executable serves the whole stream
(continuous batching, simplified to a fixed batch shape). This module
productionizes that idiom for thermal queries instead of LM tokens; the
two files cross-reference each other so the serving paths don't drift.
What carries over: fixed batch capacity as the compiled shape, slot
recycling by padding (here with ``base_params``-style neutral rows, the
same always-valid padding the PR-5 ``FamilyExecutor`` uses), one
executable per request shape. What's new here: a deadline- and
overflow-aware queue with structured failure responses, and per-request
telemetry.

Mechanics: client threads ``submit()`` pending requests into a bounded
deque. One worker thread drains it: it takes the queue head, collects
up to ``capacity`` more requests with the SAME group key (model key +
request kind + trace shape — everything that determines the compiled
executable), expires any whose deadline already passed (structured
timeout response, never a crash), and hands the group to the oracle's
execute callback. Because every group executes at the fixed capacity
(short groups are padded by the executor/``simulate_batch`` path), a
finishing request's slot is refilled from the queue on the next drain
without recompilation. A full queue rejects at ``submit()`` time with a
structured overflow response — backpressure, not an exception in the
client thread. Spans: ``batcher.wait`` while the worker blocks on an
empty queue, ``batcher.collect`` while it pops a group.

Crash accounting (PR-9): the worker thread can die — an exception that
escapes the execute callback, or an injected ``serving.worker`` fault.
A dead worker never takes requests down with it silently: the dying
thread parks its exception on ``crashed`` and records which requests it
held mid-flight (``take_inflight``); the :class:`~repro.serving
.supervisor.WorkerSupervisor` detects the death, re-drives the in-flight
work once onto a restarted worker (``requeue_front``), and answers
anything past its retry budget. ``stop()`` also drains whatever is still
queued through the ``expire`` callback so client futures NEVER hang on a
shutdown oracle.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, List, Optional

from ..runtime import span
from ..testing import faults


class ContinuousBatcher:
    """Single-worker continuous batcher over group-keyed requests.

    execute(group_key, pendings): answer 1..capacity same-group requests
        (runs on the worker thread; must fulfill every pending).
    expire(pending): fulfill one whose deadline passed before dispatch
        (also used to flush the queue with terminal answers at stop()).
    capacity:  fixed batch capacity (the compiled batch shape).
    max_queue: bounded queue length; submit() past it reports overflow.
    """

    def __init__(self, execute: Callable, expire: Callable,
                 capacity: int = 8, max_queue: int = 256):
        if capacity < 1 or max_queue < 1:
            raise ValueError("capacity and max_queue must be >= 1")
        self.capacity = int(capacity)
        self.max_queue = int(max_queue)
        self._execute = execute
        self._expire = expire
        self._queue: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._stop = False
        self._thread: Optional[threading.Thread] = None
        #: the exception that killed the last worker thread, if any —
        #: read by the supervisor; cleared on start().
        self.crashed: Optional[BaseException] = None
        self._inflight: List = []      # group held by a running execute

    # ------------------------------------------------------------------
    def start(self) -> "ContinuousBatcher":
        if self._thread is None or not self._thread.is_alive():
            self._stop = False
            self.crashed = None
            self._thread = threading.Thread(target=self._run,
                                            name="thermal-batcher",
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
        # terminal drain: whatever is still queued (worker gone, or it
        # exited before draining) gets a structured answer — no future
        # may hang past stop().
        with self._cond:
            leftovers = list(self._queue)
            self._queue.clear()
        for p in leftovers:
            self._expire(p)

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    @property
    def stopping(self) -> bool:
        return self._stop

    def depth(self) -> int:
        with self._cond:
            return len(self._queue)

    # ------------------------------------------------------------------
    def submit(self, pending) -> Optional[bool]:
        """Enqueue; False means the queue is full, None means the
        batcher is stopping (caller reports the structured overflow /
        shutdown response — nothing was enqueued)."""
        with self._cond:
            if self._stop:
                return None
            if len(self._queue) >= self.max_queue:
                return False
            pending.queue_depth = len(self._queue)
            self._queue.append(pending)
            self._cond.notify()
            return True

    def requeue_front(self, pendings: List) -> None:
        """Put re-driven requests back at the HEAD of the queue (they
        already waited their turn once); used by the supervisor."""
        with self._cond:
            self._queue.extendleft(reversed(pendings))
            self._cond.notify()

    def take_inflight(self) -> List:
        """Claim (and clear) the group the dead worker held mid-flight.
        Meaningful only after a crash — the supervisor calls this before
        restarting the worker so nothing is answered twice."""
        with self._cond:
            taken, self._inflight = self._inflight, []
            return taken

    # ------------------------------------------------------------------
    def _collect(self) -> List:
        """Pop the head's group (<= capacity live requests; expired ones
        are answered with timeouts on the spot). Called under the lock;
        returns [] only when stopping/empty."""
        now = time.monotonic()
        expired, group = [], []
        while self._queue and self._queue[0].deadline is not None \
                and now > self._queue[0].deadline:
            expired.append(self._queue.popleft())
        if self._queue:
            head_key = self._queue[0].group_key
            kept = collections.deque()
            while self._queue and len(group) < self.capacity:
                p = self._queue.popleft()
                if p.deadline is not None and now > p.deadline:
                    expired.append(p)
                elif p.group_key == head_key:
                    group.append(p)
                else:
                    kept.append(p)
            kept.extend(self._queue)
            self._queue = kept
        for p in expired:
            self._expire(p)
        return group

    def _run(self) -> None:
        """Worker-thread entry: a crash is recorded, never re-raised
        into the interpreter's threading excepthook — the group that was
        mid-flight stays claimable via take_inflight()."""
        try:
            self._loop()
        except BaseException as exc:   # worker death: supervisor's cue
            self.crashed = exc

    def _loop(self) -> None:
        while True:
            with self._cond:
                if not self._queue and not self._stop:
                    with span("batcher.wait"):
                        while not self._queue and not self._stop:
                            self._cond.wait(timeout=0.5)
                if self._stop:
                    return             # fail fast; stop() drains leftovers
                with span("batcher.collect"):
                    group = self._collect()
                    self._inflight = group
            if group:
                faults.fire("serving.worker")
                self._execute(group[0].group_key, group)
            with self._cond:
                self._inflight = []
