"""Traffic kind "oracle_fleet": runtime thermal managers on one oracle.

One ``ThermalOracle(capacity)`` serves requests that each carry a
``steps``-row window of one of the WL2-WL6 traces scaled by
``power_scale``: DTPM control traces (``submit_dtpm``, fidelity "dss")
and ROM transients (``submit_transient``, fidelity "rom"), in blocks of
the traffic file's ``block`` of kinds, each block in an order, and each
request with a window, drawn from the seed. Request ``j`` is the same
for a seed however the window runs.

The load is one of two, fixed by the traffic file:

* ``clients``: a closed loop of that many managers, each sending its
  next trace as soon as its last is answered, as controllers do. With
  more managers than the oracle can serve at once its queue never
  empties, so the window measures the oracle's capacity with no ceiling
  from the load: the answers ``ok`` completed by the window's close,
  over the window. A request is due when its manager's previous answer
  landed.
* ``rate_per_s``: open-loop arrivals at that rate, as ``tools/knee.py``
  offers them to find the knee. Every seed sends the same number of
  requests with the same multiset of inter-arrival gaps (the quantiles
  of the exponential distribution scaled to span the window exactly),
  in an order drawn from the seed.

Each request is timed from when it was due: the generator's lateness
(``enq_t - due``) plus the oracle's own latency; a request that is not
answered, or answered with an error, counts as infinitely late. After
the window the check replays a sample of the answered requests, drawn
from the seed, with the host float64 reference: DTPM max-temperature
traces under the throttle sequence the device chose, and ROM transients
against the exact full-order zero-order hold.

The control (``control=True``, never in the benchmark's own runs) puts
the reference, stepped in bfloat16, in the program's place for the same
sampled requests and throttle sequences: ``bench/reference/lowp.py``.
"""
from __future__ import annotations

import sys
import time
from collections import deque

import numpy as np

from bench.drivers.power import nn_trace
from bench.reference import lowp
from bench.reference import network as rn
from bench.reference import package as rp

_ORDER, _WINDOWS, _SAMPLE, _WARM, _ARRIVALS = range(5)


def arrival_times(n: int, seconds: float, rng) -> np.ndarray:
    """Send times of ``n`` requests over ``seconds``: exponential gaps at
    the quantiles ``(i + 1/2) / n``, in an order drawn from ``rng``,
    scaled so the last request is due at ``seconds``."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())


def percentile(values, q: float) -> float:
    """The nearest-rank ``q``-th percentile (an observed value)."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(q / 100.0 * v.size)) - 1)])


class Run:
    def __init__(self, cfg, traffic, seed, chips, seconds, control=False):
        import jax.numpy as jnp
        from repro.core import package_from_name
        from repro.serving import ThermalOracle
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.control = control
        self.pkg, n_src = package_from_name(cfg["preset"])
        self.steps = int(traffic["steps"])
        self.dt = float(cfg["ts"])
        rng = np.random.default_rng([seed, _WINDOWS])
        pw = cfg["power"]
        self.traces = [nn_trace(name, n_src, self.dt, pw["p_max"],
                                pw["p_idle"], int(rng.integers(1 << 62)))
                       * float(traffic["power_scale"])
                       for name in traffic["windows"]]
        self.oracle = ThermalOracle(
            capacity=int(traffic["capacity"]),
            build_opts={"dtype": getattr(jnp, cfg["dtype"])})
        self.oracle.warm(self.pkg, fidelity="dss", ts=self.dt)
        rom_key, _, _ = self.oracle.warm(self.pkg, fidelity="rom",
                                         ts=self.dt)
        self.tags = list(self.oracle.cache.get(rom_key).tags)
        warm_rng = np.random.default_rng([seed, _WARM])
        w = int(warm_rng.integers(0, len(self.traces)))
        warm = self.traces[w][:self.steps]
        for p in (self._send("dtpm", warm), self._send("transient", warm)):
            resp = p.result(timeout=600)
            if not resp.ok:
                raise RuntimeError(f"warm-up request answered "
                                   f"{resp.status!r}: {resp.detail}")
        self.plan(seconds, rate=traffic.get("rate_per_s"),
                  clients=traffic.get("clients"))

    def plan(self, seconds: float, rate=None, clients=None) -> None:
        """Set the window's load: ``clients`` managers in a closed loop,
        or open-loop arrivals at ``rate`` per second."""
        if (rate is None) == (clients is None):
            raise ValueError("an oracle_fleet mix names one of rate_per_s "
                             "and clients")
        self.clients = None if clients is None else int(clients)
        self.arrivals = None
        if rate is not None:
            n = max(1, int(round(float(rate) * seconds)))
            self.arrivals = arrival_times(
                n, seconds, np.random.default_rng([self.seed, _ARRIVALS]))
        self.kinds, self.which, self.start = [], [], []
        self.pending, self.responses, self.due = [], [], []

    def _draw(self, j: int) -> None:
        """Draw requests up to index ``j``, a block of kinds at a time."""
        block = self.traffic["block"]
        while len(self.kinds) <= j:
            rng = np.random.default_rng(
                [self.seed, _ORDER, len(self.kinds) // len(block)])
            for kind in rng.permutation(block):
                w = int(rng.integers(0, len(self.traces)))
                self.kinds.append(str(kind))
                self.which.append(w)
                self.start.append(int(rng.integers(
                    0, self.traces[w].shape[0] - self.steps + 1)))

    def payload(self, i: int) -> np.ndarray:
        w, s = self.which[i], self.start[i]
        return self.traces[w][s:s + self.steps]

    def _send(self, kind, powers):
        if kind == "dtpm":
            return self.oracle.submit_dtpm(self.pkg, powers, fidelity="dss",
                                           opts={"ts": self.dt})
        return self.oracle.submit_transient(self.pkg, powers, self.dt,
                                            fidelity="rom",
                                            opts={"ts": self.dt})

    def _submit(self, due: float) -> int:
        i = len(self.pending)
        self._draw(i)
        self.due.append(due)
        self.pending.append(self._send(self.kinds[i], self.payload(i)))
        self.responses.append(None)
        return i

    @staticmethod
    def _landed(p, r) -> float:
        return p.enq_t + r.latency_s

    def _closed_loop(self, close_at: float) -> None:
        """Every manager sends its next request once its last is
        answered; the wait is on the oldest request in flight."""
        live = deque(self._submit(self.t0) for _ in range(self.clients))
        while True:
            left = close_at - time.monotonic()
            if left <= 0:
                return
            try:
                self.pending[live[0]].result(left)
            except TimeoutError:
                return
            for i in [i for i in live if self.pending[i].done()]:
                live.remove(i)
                r = self.responses[i] = self.pending[i].result(0)
                live.append(self._submit(self._landed(self.pending[i], r)))

    def _open_loop(self) -> None:
        for t in self.t0 + self.arrivals:
            delay = t - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            self._submit(t)

    def measure(self, seconds: float) -> dict:
        import jax
        self.t0 = t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.submit"):
            if self.clients is not None:
                self._closed_loop(t0 + seconds)
            else:
                self._open_loop()
        self.close = close = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.wait"):
            for i, p in enumerate(self.pending):
                if self.responses[i] is not None:
                    continue
                left = close + float(self.traffic["wait_s"]) - time.monotonic()
                try:
                    self.responses[i] = p.result(max(0.0, left))
                except TimeoutError:
                    pass
        late = np.array([p.enq_t - d for p, d in zip(self.pending, self.due)])
        print(f"generator_late_ms p50 {np.median(late) * 1e3:.4f} "
              f"p99 {percentile(late, 99) * 1e3:.4f} "
              f"max {late.max() * 1e3:.4f}", file=sys.stderr, flush=True)
        self.latency = np.array([
            (p.enq_t - d) + r.latency_s if r is not None and r.ok
            else np.inf
            for p, d, r in zip(self.pending, self.due, self.responses)])
        answered = sum(r is not None and r.ok and self._landed(p, r) <= close
                       for p, r in zip(self.pending, self.responses))
        return {"oracle_answers_per_s": answered / (close - t0)}

    def answered(self) -> list:
        return [i for i, r in enumerate(self.responses)
                if r is not None and r.ok]

    def counts(self) -> dict:
        ok = [self.responses[i] for i in self.answered()]
        batches = {}       # one batch: the responses it answered at once
        for i in self.answered():
            r = self.responses[i]
            batches[round(self._landed(self.pending[i], r), 9)] = \
                r.occupancy
        return {"attempted": len(self.responses),
                "failed": len(self.responses) - len(ok),
                "latency_ms": list(1e3 * self.latency),
                "queue_s": [r.queue_s for r in ok],
                "batch_occupancy": list(batches.values()),
                "fallbacks": sum(r.fallback is not None for r in ok)}

    def release(self) -> None:
        import gc
        self.oracle.shutdown()
        self.oracle = None
        gc.collect()

    def checks(self, limits: dict) -> list:
        rng = np.random.default_rng([self.seed, _SAMPLE])
        done = self.answered()
        pick = sorted(rng.choice(done, min(len(done),
                                           int(self.traffic["check_requests"])),
                                 replace=False)) if done else []
        net = rn.build(rp.make_package(self.cfg["preset"]))
        modal = rn.Modal(net, self.dt)
        cols = [self.tags.index(t) for t in net.tags]
        dtpm = [i for i in pick if self.kinds[i] == "dtpm"]
        rom = [i for i in pick if self.kinds[i] == "transient"]
        errs = {"dtpm": [np.nan], "transient": [np.nan]}
        if dtpm:
            powers = np.stack([self.payload(i) for i in dtpm])
            thr = np.stack([np.asarray(self.responses[i].info["throttle_traj"],
                                       np.float64) for i in dtpm])
            exponent = float(self.traffic["throttle_exponent"])
            want = modal.dtpm_tmax(powers, thr, exponent)
            got = lowp.dtpm_tmax(modal, powers, thr, exponent, "bfloat16") \
                if self.control else np.stack([
                    np.asarray(self.responses[i].value, np.float64)
                    for i in dtpm])
            errs["dtpm"] = np.abs(got - want).max(axis=1)
        if rom:
            q = np.stack([self.payload(i) for i in rom])
            want = modal.rollout(q)
            got = lowp.rollout(modal, q, "bfloat16") if self.control \
                else np.stack([np.asarray(self.responses[i].value,
                                          np.float64)[:, cols] for i in rom])
            errs["transient"] = np.abs(got - want).max(axis=(1, 2))
        c = self.counts()
        return [("dtpm_max_err_c", float(np.max(errs["dtpm"])),
                 limits["dtpm_max_err_c"]),
                ("rom_max_err_c", float(np.max(errs["transient"])),
                 limits["rom_max_err_c"]),
                ("failed", c["failed"], 0),
                ("fallbacks", c["fallbacks"], 0)]
