"""Adaptive fidelity router: ``build(pkg, "auto", tol=...)`` with
certified error bars.

The fidelity ladder gives four ways to answer one thermal query —
``rom`` (microsecond r x r steps), ``rc`` (exact sparse solves),
``dss`` (full-order exact ZOH), ``fvm`` (voxel reference) — and until
now choosing among them was the caller's problem. The router makes the
choice per query from two ingredients:

  * **A measured cost model** (:class:`CostModel`) seeded from the
    repo's own ``BENCH_exec_time.json`` crossover data (the ``rom``,
    ``sparse_solver`` and ``systems`` sections), log-log interpolated
    over node count, with embedded fallbacks for containers without a
    bench file. It orders the candidate rungs cheapest-first.

  * **Error certificates** (:class:`ErrorCertifier`) that upper-bound
    the OBSERVATION error of a candidate answer against the full-order
    f64 network reference — so a cheap answer is returned only when it
    is *provably* within the accuracy target, and the router escalates
    a rung when the certificate fails:

    - *Steady* answers carry an EXACT dual-weighted residual
      certificate: with ``W = (-G)^-T H^T`` (one block solve per
      router), the observation error of any expanded full-state answer
      x is identically ``W' (P q - (-G) x)`` — the O(E) residual matvec
      is a host f64 sparse product on the expanded ROM state, and the
      certificate is the exact error times a small roundoff safety.
    - *Transient* ROM answers carry a discrete decay bound: the
      full-order ZOH error recursion ``d_(k+1) = Ad d_k + r_k`` with
      computable residual ``r_k = Ad V th_k + Bd q_k - V th_(k+1)``
      contracts in the C-norm at EXACTLY ``exp(-lambda_min dt)`` per
      step (``lambda_min`` from the reference rung's eigendecomposition,
      :class:`~repro.core.dss.EighZOH`), giving
      ``|obs err_k| <= max_j ||H_j C^(-1/2)|| * eta_k`` with
      ``eta_(k+1) = exp(-lambda dt) eta_k + ||r_k||_C``. Sound up to
      f64 roundoff (covered by the safety factor), and linear in the
      drive — certificates scale with the power trace.
    - The *reference rungs themselves* (``rc`` steady, ``dss``
      transient) answer in the same f64 discretization class the
      certificates are measured against, so they carry a documented
      roundoff-floor certificate and terminate every escalation chain.

A-priori estimates make routing cheap before any answer is computed:
per-source steady ROM certificates (computed once, summed by
``|q_s|`` — rigorous by linearity + triangle inequality) and a
self-calibrating transient estimate (the last certificate per (dt, T)
scaled by the trace amplitude — a routing heuristic only; acceptance is
always decided by the actual certificate). ``fvm`` sits in the cost
model but is selected only by explicit override (``rung="fvm"``): its
model-form error vs the network reference is not certifiable here.

Serving integration: ``RoutedThermalSimulator`` implements the
``ThermalSimulator`` protocol (full-order state convention), so
``ThermalOracle(fidelity="auto")`` works unchanged; every query stashes
``last_route`` (and ``last_batch_routes`` for batched rollouts), which
the oracle forwards into ``serving/telemetry.py`` route events.
``build_family(fam, "auto", tol=...)`` routes once per batch via a
certified probe on the family template and answers with the chosen
rung's family model (per-candidate answers inherit the template's
certificate as a routing estimate, not a per-candidate bound — f32
family execution adds dtype error on top).

Tier-1 acceptance (``tests/test_router.py``): on every Table-6 system
and tol in {1e-1, 1e-2, 1e-3}, routed answers measure within tol of the
f64 full-order reference, certificates upper-bound measured error, and
loose tolerances demonstrably route to cheaper rungs than tight ones.
"""
from __future__ import annotations

import dataclasses
import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..testing import faults
from .dss import EighZOH, zoh_discretize
from .fidelity import (register_family_fidelity, register_fidelity,
                       resolve_solver)
from .geometry import Package
from .rc_model import (RCNetwork, _resolve_cap_multipliers, build_network,
                       observation_matrix)
from .rom import ROMModel, _make_neg_g_solver, krylov_basis

# ---------------------------------------------------------------------------
# Measured cost model
# ---------------------------------------------------------------------------
# Fallback cost tables, (nodes, seconds) points per rung/metric, taken
# from a representative container run of benchmarks/exec_time.py (the
# same numbers BENCH_exec_time.json tracks). "fvm" has no bench row —
# its entries are deliberately conservative placeholders that keep it
# ranked last, matching its reference-only role.
_EMBEDDED_COSTS: Dict[str, Dict[str, List[Tuple[float, float]]]] = {
    "rom": {"steady": [(564.0, 2.7e-4), (2116.0, 3.6e-4)],
            "per_step": [(564.0, 1.0e-6), (2116.0, 2.4e-6)],
            "setup": [(564.0, 5.0e-5)]},
    "rc": {"steady": [(564.0, 1.1e-3), (8196.0, 1.7e-2)],
           "per_step": [(564.0, 1.6e-4), (8196.0, 2.8e-3)],
           "setup": [(564.0, 0.0)]},
    "dss": {"steady": [(564.0, 1.1e-3), (8196.0, 1.7e-2)],
            "per_step": [(564.0, 2.3e-5), (2116.0, 3.2e-4)],
            "setup": [(564.0, 0.21), (2116.0, 2.9)]},
    "fvm": {"steady": [(564.0, 0.5)],
            "per_step": [(564.0, 2.5e-3)],
            "setup": [(564.0, 1.0)]},
}


def _loglog_eval(pts: List[Tuple[float, float]], n: float) -> float:
    """Evaluate a (nodes, seconds) curve at n nodes by log-log linear
    interpolation, extrapolating with the boundary segment's slope
    (cost curves here are power laws in N to good approximation)."""
    pts = sorted((float(a), max(float(b), 1e-12)) for a, b in pts)
    if len(pts) == 1:
        return pts[0][1]
    xs = np.log([p[0] for p in pts])
    ys = np.log([p[1] for p in pts])
    x = np.log(max(float(n), 1.0))
    if x <= xs[0]:
        seg = (0, 1)
    elif x >= xs[-1]:
        seg = (len(xs) - 2, len(xs) - 1)
    else:
        hi = int(np.searchsorted(xs, x))
        seg = (hi - 1, hi)
    slope = (ys[seg[1]] - ys[seg[0]]) / (xs[seg[1]] - xs[seg[0]])
    return float(np.exp(ys[seg[0]] + slope * (x - xs[seg[0]])))


class CostModel:
    """Per-rung query-cost curves seeded from ``BENCH_exec_time.json``.

    ``tables[rung][metric]`` is a list of measured (nodes, seconds)
    points; queries log-log interpolate over node count. Metrics:
    ``steady`` (one steady answer), ``per_step`` (one transient step),
    ``setup`` (per-(query, dt) amortized preparation — the O(N^2) ZOH
    discretization of the dss rung dominates this column and is what
    pushes short traces toward the ROM rung: the measured crossover
    data the router's ordering is built on).
    """

    def __init__(self, tables: Dict[str, Dict[str, list]]):
        self.tables = tables

    # -- construction -------------------------------------------------------
    @classmethod
    def from_bench(cls, path: Optional[str] = None) -> "CostModel":
        """Seed from the repo's BENCH file; any missing section keeps
        the embedded fallback points (never raises — a container
        without a bench file still routes)."""
        tables = {r: {m: list(v) for m, v in t.items()}
                  for r, t in _EMBEDDED_COSTS.items()}
        bench = cls._find_bench(path)
        if bench is None:
            return cls(tables)
        try:
            cls._merge_bench(tables, bench)
        except (KeyError, TypeError, ValueError):
            pass                      # malformed section: fallback wins
        return cls(tables)

    @staticmethod
    def _find_bench(path: Optional[str]) -> Optional[dict]:
        cand = Path(path) if path else \
            Path(__file__).resolve().parents[3] / "BENCH_exec_time.json"
        try:
            with open(cand) as fh:
                return json.load(fh)
        except (OSError, json.JSONDecodeError):
            return None

    @staticmethod
    def _merge_bench(tables: dict, bench: dict) -> None:
        def put(rung, metric, nodes, seconds):
            if seconds and seconds > 0:
                pts = [p for p in tables[rung][metric]
                       if abs(p[0] - nodes) > 0.5]
                tables[rung][metric] = sorted(pts + [(float(nodes),
                                                      float(seconds))])
        for row in bench.get("rom") or []:
            put("rom", "steady", row["nodes"], row.get("steady_rom_s"))
            put("rom", "per_step", row["nodes"],
                row.get("per_step_rom_s"))
        for row in (bench.get("sparse_solver") or {}).get("systems", []):
            n = row["nodes"]
            steady = min(filter(None, [row.get("steady_dense_s"),
                                       row.get("steady_cg_s")]))
            per = min(filter(None, [row.get("per_step_dense_s"),
                                    row.get("per_step_cg_s")]))
            put("rc", "steady", n, steady)
            put("rc", "per_step", n, per)
            put("dss", "steady", n, steady)   # same fixed-point solve
        for row in bench.get("systems") or []:
            n = row["nodes"]["dss"]
            put("dss", "per_step", n, row["per_step_s"].get("dss"))
            put("dss", "setup", n,
                row.get("times", {}).get("dss_regeneration"))

    # -- queries ------------------------------------------------------------
    def steady_s(self, rung: str, n_nodes: int) -> float:
        return _loglog_eval(self.tables[rung]["steady"], n_nodes)

    def transient_s(self, rung: str, n_nodes: int, n_steps: int) -> float:
        t = self.tables[rung]
        return _loglog_eval(t["setup"], n_nodes) \
            + n_steps * _loglog_eval(t["per_step"], n_nodes)

    def order(self, rungs, kind: str, n_nodes: int,
              n_steps: int = 0) -> List[str]:
        """Candidate rungs cheapest-first for one query shape."""
        def cost(r):
            if kind == "steady":
                return self.steady_s(r, n_nodes)
            return self.transient_s(r, n_nodes, n_steps)
        return sorted(rungs, key=cost)


# ---------------------------------------------------------------------------
# Error certificates
# ---------------------------------------------------------------------------
class ErrorCertifier:
    """Observation-error certificates against the full-order f64
    network reference (see module docstring for the math).

    One instance per router: the one-time costs are the dual block
    solve ``W = (-G)^-T H^T`` (n_obs columns through the resolved
    solver tier) and — lazily, on the first transient certification —
    the reference rung's eigendecomposition (shared with the router's
    ``dss`` answers via :meth:`reference`). Per-query costs are O(E)
    host sparse residual matvecs (steady) or O(N (r+S))
    per step residual products (transient).
    """

    #: multiplicative roundoff headroom on the exact steady identity
    SAFETY_STEADY = 1.05
    #: headroom on the transient decay bound (eigh + accumulation
    #: roundoff; the bound itself is mathematically an upper bound)
    SAFETY_TRANSIENT = 1.25
    #: additive floor: nothing is certified below f64 noise
    FLOOR = 1e-9
    #: certificate of the reference rungs themselves (same
    #: discretization class as the comparison reference; the floor
    #: covers f64 roundoff between algebraically identical paths)
    FLOOR_REFERENCE = 1e-8

    def __init__(self, net: RCNetwork, tags: Optional[list] = None,
                 solver: str = "auto"):
        self.net = net
        self.h = observation_matrix(net, tags)           # (n_obs, N)
        self._c_sqrt = np.sqrt(np.asarray(net.C, np.float64))
        self._c_isqrt = 1.0 / self._c_sqrt
        # |H_j e| <= ||H_j C^(-1/2)||_2 ||e||_C, the observation side of
        # the transient bound
        self._h_cnorm = float(np.linalg.norm(
            self.h * self._c_isqrt[None, :], axis=1).max())
        self._solve = _make_neg_g_solver(net, resolve_solver(solver, net.n))
        self.W = self._solve(self.h.T)                   # (N, n_obs)
        # O(E) residual matvec on the expanded state: host f64 sparse
        self._neg_g = net.neg_g_sparse()
        self._ref: Optional[EighZOH] = None
        self._adv_cache: dict = {}

    # ------------------------------------------------------------------
    def neg_g_mv(self, x: np.ndarray) -> np.ndarray:
        """(-G) x on the host in f64, x (..., N)."""
        x = np.asarray(x, np.float64)
        flat = x.reshape(-1, x.shape[-1])
        return (self._neg_g @ flat.T).T.reshape(x.shape)

    def reference(self) -> EighZOH:
        """The shared full-order f64 reference rung (lazy: steady-only
        routers never pay the eigendecomposition)."""
        if self._ref is None:
            self._ref = EighZOH(self.net)
        return self._ref

    # -- steady --------------------------------------------------------
    def steady_observation_error(self, x_full: np.ndarray,
                                 q: np.ndarray) -> float:
        """EXACT max-abs observation error of the expanded steady answer
        ``x_full`` for drive q: ``W' (P q - (-G) x)`` (dual identity)."""
        rho = self.net.P @ np.asarray(q, np.float64) \
            - self.neg_g_mv(np.asarray(x_full, np.float64))
        return float(np.abs(self.W.T @ rho).max())

    def certify_steady(self, x_full: np.ndarray, q: np.ndarray) -> float:
        return self.steady_observation_error(x_full, q) \
            * self.SAFETY_STEADY + self.FLOOR

    # -- transient (ROM) -----------------------------------------------
    def _adv(self, v_basis: np.ndarray, dt: float) -> np.ndarray:
        """``Ad V`` at dt (cached; O(N^2 r) once per (basis, dt))."""
        key = (round(float(dt), 12), id(v_basis))
        hit = self._adv_cache.get(key)
        if hit is None:
            if len(self._adv_cache) >= 8:
                self._adv_cache.pop(next(iter(self._adv_cache)))
            ad, _ = self.reference().discretize(dt)
            hit = self._adv_cache[key] = ad @ v_basis
        return hit

    def certify_rom_transient(self, rom: ROMModel,
                              th_traj: np.ndarray, q_traj: np.ndarray,
                              dt: float,
                              d0: Optional[np.ndarray] = None) -> float:
        """Decay-bound certificate of a reduced trajectory (see module
        docstring): ``th_traj`` is the (T+1, r) host-f64 reduced states
        (index 0 = initial), ``d0`` an optional full-order initial
        error. Sound: every factor (contraction rate, residual norms)
        is exact up to f64 roundoff, covered by SAFETY_TRANSIENT."""
        ref = self.reference()
        _, bd = ref.discretize(dt)
        adv = self._adv(rom.V, dt)
        th = np.asarray(th_traj, np.float64)
        q = np.asarray(q_traj, np.float64)
        # discrete residuals r_k = Ad V th_k + Bd q_k - V th_(k+1), all
        # steps at once: (N, T)
        resid = adv @ th[:-1].T + bd @ q.T - rom.V @ th[1:].T
        w_c = np.linalg.norm(self._c_sqrt[:, None] * resid, axis=0)
        decay = float(np.exp(-ref.lambda_min * float(dt)))
        eta = 0.0 if d0 is None else float(np.linalg.norm(
            self._c_sqrt * np.asarray(d0, np.float64)))
        worst = eta
        for wk in w_c:
            eta = decay * eta + float(wk)
            worst = max(worst, eta)
        return self._h_cnorm * worst * self.SAFETY_TRANSIENT + self.FLOOR


# ---------------------------------------------------------------------------
# Circuit breakers (self-healing rung selection)
# ---------------------------------------------------------------------------
class CircuitBreaker:
    """Per-rung breaker: repeated solver failures open it so traffic
    falls straight to the next certified rung without re-paying the
    failing solve; after ``cooldown_s`` one half-open probe is allowed
    — success closes the breaker, failure re-opens it for another
    cooldown. States: "closed" -> "open" -> "half_open" -> ... .

    The router is driven from the oracle's single worker thread, so the
    state machine is deliberately lock-free; ``trips`` (transitions to
    open) feed the telemetry ``router`` block via route events.
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0):
        if threshold < 1 or cooldown_s < 0:
            raise ValueError("breaker threshold must be >= 1 and "
                             "cooldown_s >= 0")
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self.failures = 0          # consecutive failures while closed
        self.trips = 0             # closed/half-open -> open transitions
        self._state = "closed"
        self._open_until = 0.0

    @property
    def state(self) -> str:
        if self._state == "open" \
                and time.monotonic() >= self._open_until:
            return "half_open"     # cooldown elapsed: probe territory
        return self._state

    def allow(self) -> bool:
        """May this query try the rung? Open rungs say no until their
        cooldown elapses, then admit one half-open probe."""
        if self._state == "closed":
            return True
        if self._state == "open" \
                and time.monotonic() >= self._open_until:
            self._state = "half_open"
            return True
        return self._state == "half_open"

    def record_success(self) -> None:
        self.failures = 0
        self._state = "closed"

    def record_failure(self) -> bool:
        """Count one failure; returns True when this call TRIPPED the
        breaker open (a half-open probe failure re-opens immediately)."""
        self.failures += 1
        if self._state == "half_open" or self.failures >= self.threshold:
            tripped = self._state != "open"
            self._state = "open"
            self._open_until = time.monotonic() + self.cooldown_s
            if tripped:
                self.trips += 1
            return tripped
        return False

    def snapshot(self) -> dict:
        return {"state": self.state, "failures": self.failures,
                "trips": self.trips}


# ---------------------------------------------------------------------------
# Routed answers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class RoutedAnswer:
    """One certified routed answer (returned by the ``query_*`` API)."""
    value: np.ndarray                 # (n_obs,) or (T, n_obs), abs degC
    kind: str                         # "steady" | "transient"
    rung: str                         # answering rung
    certified: Optional[float]        # obs-error upper bound (None: fvm)
    tol: float                        # accuracy target it was held to
    escalations: int                  # rungs passed over (skip or fail)
    tried: list                       # [{"rung", "certified"|"apriori"|
                                      #   "error"|"breaker"}]
    overhead_s: float                 # routing + certification seconds
    state: Optional[np.ndarray] = None  # full-order steady state (N,)
    #: False when the ladder was exhausted without any rung certifying
    #: within tol (best-effort answer: lowest certificate wins, flagged
    #: — never silently returned as certified) or the answering rung
    #: carries no certificate at all (forced ``fvm``).
    certified_ok: bool = True

    @property
    def margin(self) -> Optional[float]:
        return None if self.certified is None else self.tol - self.certified

    @property
    def route(self) -> dict:
        """The telemetry route event (``serving/telemetry.py``)."""
        return {"kind": self.kind, "rung": self.rung,
                "certified": self.certified, "tol": self.tol,
                "margin": self.margin, "escalations": self.escalations,
                "certified_ok": self.certified_ok,
                "overhead_s": self.overhead_s, "tried": self.tried}


# ---------------------------------------------------------------------------
# The router
# ---------------------------------------------------------------------------
class RoutedThermalSimulator:
    """``build(pkg, "auto", tol=...)``: per-query rung selection with
    certified error bars (see module docstring).

    Implements the ``ThermalSimulator`` protocol in the FULL-ORDER state
    convention — ``steady_state`` returns the expanded (N,) host-f64
    state whatever rung answered, ``observe`` applies the shared f64
    observation operator — so the routed model drops into every ladder
    consumer, including ``ThermalOracle(fidelity="auto")``. The richer
    ``query_steady`` / ``query_transient`` API returns
    :class:`RoutedAnswer` with the certificate attached; ``tol=`` per
    query overrides the built accuracy target (one router instance
    serves many targets over the same cached rungs), ``rung=`` forces a
    rung (the only way to the uncertified ``fvm`` reference).
    """

    fidelity = "auto"
    STEADY_LADDER = ("rom", "rc")
    TRANSIENT_LADDER = ("rom", "dss")

    def __init__(self, pkg: Package, tol: float = 1e-2, ts: float = 0.01,
                 solver: str = "auto", cap_multipliers: Optional[dict] = None,
                 rom_opts: Optional[dict] = None,
                 cost_model: Optional[CostModel] = None,
                 breaker_threshold: int = 3,
                 breaker_cooldown_s: float = 30.0,
                 dtype=jnp.float32):
        if tol <= 0:
            raise ValueError(f"tol must be > 0, got {tol}")
        self.pkg = pkg
        self.tol = float(tol)
        self.ts = float(ts)
        self.dtype = dtype           # protocol-compat; answers are host f64
        self.solver = solver
        self.rom_opts = dict(rom_opts or {})
        self.net = build_network(pkg, cap_multipliers=_resolve_cap_multipliers(
            pkg, cap_multipliers))
        self.tags = sorted({t for t in self.net.grid.tags if t})
        self.source_names = list(self.net.grid.source_names)
        self.t_ambient = float(self.net.t_ambient)
        self.certifier = ErrorCertifier(self.net, solver=solver)
        self.cost = cost_model if cost_model is not None \
            else CostModel.from_bench()
        self._rungs: dict = {}
        self._apriori_steady_rom: Optional[np.ndarray] = None
        self._apriori_transient: dict = {}     # (dt, T) -> cert per unit q
        self.last_route: Optional[dict] = None
        self.last_batch_routes: Optional[list] = None
        # one breaker per rung, shared by the steady and transient
        # ladders (a rung whose solver is sick is sick for both)
        self._breakers = {
            name: CircuitBreaker(breaker_threshold, breaker_cooldown_s)
            for name in {*self.STEADY_LADDER, *self.TRANSIENT_LADDER,
                         "fvm"}}

    def breaker_states(self) -> dict:
        """{rung: {"state", "failures", "trips"}} for telemetry."""
        return {name: brk.snapshot()
                for name, brk in sorted(self._breakers.items())}

    # -- rung construction (lazy, cached) ------------------------------
    def _rung(self, name: str):
        if name not in self._rungs:
            if name == "rom":
                basis = krylov_basis(self.net, solver=self.solver,
                                     **self.rom_opts)
                self._rungs[name] = ROMModel(self.net, basis, ts=self.ts,
                                             dtype=self.dtype)
            elif name == "rc":
                self._rungs[name] = None      # answered by the certifier
            elif name == "dss":
                self._rungs[name] = self.certifier.reference()
            elif name == "fvm":
                from .fidelity import build
                self._rungs[name] = build(self.pkg, "fvm")
            else:
                raise KeyError(f"unknown rung {name!r}")
        return self._rungs[name]

    @property
    def n(self) -> int:
        return int(self.net.n)

    # -- a-priori estimates --------------------------------------------
    def _apriori(self, rung: str, kind: str, q, dt=None,
                 n_steps=None) -> Optional[float]:
        if rung != "rom":
            return None               # reference rungs never pre-skip
        if kind == "steady":
            if self._apriori_steady_rom is None:
                rom = self._rung("rom")
                # per-source exact certificates, once: X = V (-Ghat)^-1
                # Phat expands every unit-source ROM answer at once
                x_unit = rom.V @ np.linalg.solve(-rom.ghat, rom.phat)
                rho = self.net.P - self.certifier.neg_g_mv(x_unit.T).T
                self._apriori_steady_rom = np.abs(
                    self.certifier.W.T @ rho).max(axis=0)
            # rigorous by linearity + triangle inequality
            return float(self._apriori_steady_rom
                         @ np.abs(np.asarray(q, np.float64))) \
                * ErrorCertifier.SAFETY_STEADY + ErrorCertifier.FLOOR
        unit = self._apriori_transient.get((round(float(dt), 12),
                                            int(n_steps)))
        if unit is None:
            return None               # never certified this shape yet
        return unit * float(np.abs(q).max())

    # -- per-rung answers ----------------------------------------------
    def _steady_answer(self, rung: str, q: np.ndarray):
        """-> (x_full | None, obs (n_obs,), certified | None)."""
        if rung == "rom":
            rom = self._rung("rom")
            th_hat = rom._cho_solve(rom._cho,
                                    rom.phat @ np.asarray(q, np.float64))
            x = rom.V @ th_hat
            return x, self.certifier.h @ x + self.t_ambient, \
                self.certifier.certify_steady(x, q)
        if rung == "rc":
            self._rung("rc")
            x = self.certifier._solve(
                self.net.P @ np.asarray(q, np.float64)[:, None])[:, 0]
            return x, self.certifier.h @ x + self.t_ambient, \
                ErrorCertifier.FLOOR_REFERENCE * max(
                    1.0, float(np.abs(q).sum()))
        if rung == "dss":
            ref = self._rung("dss")
            x = ref.steady(q)
            return x, self.certifier.h @ x + self.t_ambient, \
                ErrorCertifier.FLOOR_REFERENCE * max(
                    1.0, float(np.abs(q).sum()))
        if rung == "fvm":
            fvm = self._rung("fvm")
            obs = np.asarray(fvm.observe(fvm.steady_state(q)), np.float64)
            return None, obs, None    # model-form error: uncertified
        raise KeyError(f"unknown rung {rung!r}")

    def _transient_answer(self, rung: str, q_traj: np.ndarray, dt: float,
                          theta0: Optional[np.ndarray]):
        """-> (obs (T, n_obs), certified | None)."""
        q = np.asarray(q_traj, np.float64)
        if rung == "rom":
            rom = self._rung("rom")
            ad, bd = zoh_discretize(rom._a, rom._b, dt)   # r x r, host
            th = np.zeros((q.shape[0] + 1, rom.r))
            d0 = None
            if theta0 is not None and np.any(theta0):
                full0 = np.asarray(theta0, np.float64)
                th[0] = rom.V.T @ (self.net.C * full0)    # C-projection
                d0 = full0 - rom.V @ th[0]
            for k in range(q.shape[0]):
                th[k + 1] = ad @ th[k] + bd @ q[k]
            obs = th[1:] @ rom.hhat.T + self.t_ambient
            cert = self.certifier.certify_rom_transient(rom, th, q, dt,
                                                        d0=d0)
            scale = float(np.abs(q).max())
            if d0 is None and scale > 0:   # self-calibrating a-priori
                self._apriori_transient[(round(float(dt), 12),
                                         q.shape[0])] = cert / scale
            return obs, cert
        if rung == "dss":
            ref = self._rung("dss")
            th0 = np.zeros(self.n) if theta0 is None \
                else np.asarray(theta0, np.float64)
            return ref.simulate(th0, q, dt), \
                ErrorCertifier.FLOOR_REFERENCE * max(
                    1.0, float(np.abs(q).max()))
        if rung == "fvm":
            fvm = self._rung("fvm")
            sim = fvm.make_simulator(dt)
            return np.asarray(sim(fvm.zero_state(), q), np.float64), None
        raise KeyError(f"unknown rung {rung!r}")

    # -- routing core ---------------------------------------------------
    def query_steady(self, q, tol: Optional[float] = None,
                     rung: Optional[str] = None) -> RoutedAnswer:
        t0 = time.perf_counter()
        tol = self.tol if tol is None else float(tol)
        q = np.asarray(q, np.float64)
        forced = rung is not None
        ladder = (rung,) if forced else tuple(self.cost.order(
            self.STEADY_LADDER, "steady", self.n))
        tried: list = []
        answer_s = 0.0
        best = None     # (cert, name, x, obs, i): lowest-cert survivor
        for i, name in enumerate(ladder):
            brk = self._breakers[name]
            if not forced and not brk.allow():
                tried.append({"rung": name, "breaker": "open"})
                continue
            if not forced and i < len(ladder) - 1:
                est = self._apriori(name, "steady", q)
                if est is not None and est > tol:
                    tried.append({"rung": name, "apriori": est})
                    continue
            ta = time.perf_counter()
            try:
                faults.fire(f"router.steady.{name}")
                x, obs, cert = self._steady_answer(name, q)
                if not np.isfinite(np.asarray(obs, np.float64)).all():
                    raise FloatingPointError(
                        f"non-finite observation from rung {name!r}")
            except Exception as exc:   # rung is sick: breaker + next rung
                answer_s += time.perf_counter() - ta
                if forced:
                    raise              # explicit rung= bypasses healing
                entry = {"rung": name,
                         "error": f"{type(exc).__name__}: {exc}"}
                if brk.record_failure():
                    entry["breaker_tripped"] = True
                tried.append(entry)
                continue
            answer_s += time.perf_counter() - ta
            brk.record_success()
            tried.append({"rung": name, "certified": cert})
            ok = cert is not None and cert <= tol
            if forced or ok:
                ans = RoutedAnswer(
                    value=obs, kind="steady", rung=name, certified=cert,
                    tol=tol, escalations=i, tried=tried,
                    overhead_s=time.perf_counter() - t0 - answer_s,
                    state=x, certified_ok=ok)
                self.last_route = ans.route
                return ans
            if cert is not None and (best is None or cert < best[0]):
                best = (cert, name, x, obs, i)
        if best is None:               # every rung failed or was open
            raise RuntimeError(
                f"steady routing exhausted at tol={tol}: "
                f"no rung produced an answer (tried={tried})")
        cert, name, x, obs, i = best   # best effort, flagged — never
        ans = RoutedAnswer(            # silently passed off as certified
            value=obs, kind="steady", rung=name, certified=cert,
            tol=tol, escalations=i, tried=tried,
            overhead_s=time.perf_counter() - t0 - answer_s,
            state=x, certified_ok=False)
        self.last_route = ans.route
        return ans

    def query_transient(self, q_traj, dt: Optional[float] = None,
                        tol: Optional[float] = None,
                        rung: Optional[str] = None,
                        theta0=None) -> RoutedAnswer:
        t0 = time.perf_counter()
        tol = self.tol if tol is None else float(tol)
        dt = self.ts if dt is None else float(dt)
        q = np.asarray(q_traj, np.float64)
        forced = rung is not None
        ladder = (rung,) if forced else tuple(self.cost.order(
            self.TRANSIENT_LADDER, "transient", self.n, q.shape[0]))
        tried: list = []
        answer_s = 0.0
        best = None     # (cert, name, obs, i): lowest-cert survivor
        for i, name in enumerate(ladder):
            brk = self._breakers[name]
            if not forced and not brk.allow():
                tried.append({"rung": name, "breaker": "open"})
                continue
            if not forced and i < len(ladder) - 1 and theta0 is None:
                est = self._apriori(name, "transient", q, dt=dt,
                                    n_steps=q.shape[0])
                if est is not None and est > tol:
                    tried.append({"rung": name, "apriori": est})
                    continue
            ta = time.perf_counter()
            try:
                faults.fire(f"router.transient.{name}")
                obs, cert = self._transient_answer(name, q, dt, theta0)
                if not np.isfinite(np.asarray(obs, np.float64)).all():
                    raise FloatingPointError(
                        f"non-finite observation from rung {name!r}")
            except Exception as exc:   # rung is sick: breaker + next rung
                answer_s += time.perf_counter() - ta
                if forced:
                    raise              # explicit rung= bypasses healing
                entry = {"rung": name,
                         "error": f"{type(exc).__name__}: {exc}"}
                if brk.record_failure():
                    entry["breaker_tripped"] = True
                tried.append(entry)
                continue
            answer_s += time.perf_counter() - ta
            brk.record_success()
            tried.append({"rung": name, "certified": cert})
            ok = cert is not None and cert <= tol
            if forced or ok:
                ans = RoutedAnswer(
                    value=obs, kind="transient", rung=name,
                    certified=cert, tol=tol, escalations=i, tried=tried,
                    overhead_s=time.perf_counter() - t0 - answer_s,
                    certified_ok=ok)
                self.last_route = ans.route
                return ans
            if cert is not None and (best is None or cert < best[0]):
                best = (cert, name, obs, i)
        if best is None:               # every rung failed or was open
            raise RuntimeError(
                f"transient routing exhausted at tol={tol}: "
                f"no rung produced an answer (tried={tried})")
        cert, name, obs, i = best      # best effort, flagged — never
        ans = RoutedAnswer(            # silently passed off as certified
            value=obs, kind="transient", rung=name, certified=cert,
            tol=tol, escalations=i, tried=tried,
            overhead_s=time.perf_counter() - t0 - answer_s,
            certified_ok=False)
        self.last_route = ans.route
        return ans

    # -- ThermalSimulator protocol (full-order state convention) -------
    def zero_state(self, batch: Optional[int] = None) -> np.ndarray:
        shape = (self.n,) if batch is None else (batch, self.n)
        return np.zeros(shape)

    def steady_state(self, q_src) -> np.ndarray:
        ans = self.query_steady(q_src)
        if ans.state is None:         # cannot happen on the cert ladder
            raise RuntimeError(f"rung {ans.rung!r} has no network state")
        return ans.state

    def observe(self, state) -> np.ndarray:
        return self.certifier.h @ np.asarray(state, np.float64) \
            + self.t_ambient

    def make_simulator(self, dt: Optional[float] = None):
        dt = self.ts if dt is None else float(dt)

        def simulate(theta0, q_traj):
            return self.query_transient(q_traj, dt, theta0=theta0).value

        return simulate

    def simulate_batch(self, theta0, q_traj,
                       dt: Optional[float] = None) -> np.ndarray:
        """(B, N), (T, B, S) -> (T, B, n_obs); each slot routes
        independently (per-slot routes land in ``last_batch_routes``
        for the serving layer)."""
        dt = self.ts if dt is None else float(dt)
        q = np.asarray(q_traj, np.float64)
        outs, routes = [], []
        for b in range(q.shape[1]):
            th0 = None if theta0 is None else np.asarray(theta0)[b]
            ans = self.query_transient(q[:, b, :], dt, theta0=th0)
            outs.append(ans.value)
            routes.append(ans.route)
        self.last_batch_routes = routes
        return np.stack(outs, axis=1)


# ---------------------------------------------------------------------------
# Family-level routing: certified probe on the template
# ---------------------------------------------------------------------------
class RoutedFamilySimulator:
    """``build_family(fam, "auto", tol=...)``: one certified routing
    decision per batch, taken on the family TEMPLATE, answered by the
    chosen rung's batched family model.

    Per-candidate certification would need per-candidate dual solves —
    exactly the cost the family path exists to avoid — so the router
    probes the worst-amplitude slot of each batch against the template
    package (full f64 certificate machinery) and applies that rung to
    the whole batch. ``last_route`` records the probe's certificate
    with ``basis="template_probe"``: a routing estimate for the batch,
    not a per-candidate bound (family execution in f32 adds dtype error
    on top — pinned honestly in the route event, not hidden).
    """

    fidelity = "auto"

    def __init__(self, family, tol: float = 1e-2, ts: float = 0.01,
                 cost_model: Optional[CostModel] = None,
                 rom_opts: Optional[dict] = None, **family_opts):
        self.family = family
        self.tol = float(tol)
        self.ts = float(ts)
        self.probe = RoutedThermalSimulator(
            family.template, tol=tol, ts=ts, cost_model=cost_model,
            rom_opts=rom_opts)
        self.tags = self.probe.tags
        self.source_names = self.probe.source_names
        self.param_names = list(family.param_names)
        self.family_opts = dict(family_opts)
        self._models: dict = {}
        self._steady_model = None
        self.last_route: Optional[dict] = None

    def _fam_model(self, rung: str):
        if rung not in self._models:
            from .fidelity import build_family
            self._models[rung] = build_family(self.family, rung,
                                              ts=self.ts,
                                              **self.family_opts)
        return self._models[rung]

    @staticmethod
    def _probe_route(ans: RoutedAnswer) -> dict:
        return {**ans.route, "basis": "template_probe"}

    def steady_state_batch(self, params, q_src):
        q = np.asarray(q_src, np.float64)
        probe_q = q[int(np.argmax(np.abs(q).sum(axis=1)))]
        ans = self.probe.query_steady(probe_q, tol=self.tol)
        self.last_route = self._probe_route(ans)
        self._steady_model = self._fam_model(ans.rung)
        return self._steady_model.steady_state_batch(params, q_src)

    def observe_batch(self, state, params):
        if self._steady_model is None:
            raise RuntimeError("observe_batch before steady_state_batch: "
                               "the routed family model is stateful per "
                               "batch (rung chosen at the steady solve)")
        return self._steady_model.observe_batch(state, params)

    def simulate_family(self, params, q_traj,
                        dt: Optional[float] = None):
        dt = self.ts if dt is None else float(dt)
        q = np.asarray(q_traj, np.float64)
        probe_b = int(np.argmax(np.abs(q).sum(axis=(0, 2))))
        ans = self.probe.query_transient(q[:, probe_b, :], dt,
                                         tol=self.tol)
        self.last_route = self._probe_route(ans)
        return self._fam_model(ans.rung).simulate_family(params, q_traj,
                                                         dt)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
@register_fidelity("auto")
def build_auto(pkg: Package, tol: float = 1e-2,
               **opts) -> RoutedThermalSimulator:
    """Registry builder: ``build(pkg, "auto", tol=...)`` — the adaptive
    router. Routing knobs (tol, rom_opts, cost_model overrides) are part
    of ``fidelity.cache_key``, so auto-built models cache per
    (geometry, tol) and never alias hand-picked rungs."""
    return RoutedThermalSimulator(pkg, tol=tol, **opts)


@register_family_fidelity("auto")
def build_auto_family(family, tol: float = 1e-2,
                      **opts) -> RoutedFamilySimulator:
    return RoutedFamilySimulator(family, tol=tol, **opts)
