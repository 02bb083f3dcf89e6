"""Discrete State-Space (DSS) model (paper §4.4, Eqs. 8-14).

Exact zero-order-hold discretization of the thermal RC state space:

    A  = C^-1 G,  B = C^-1
    Ad = expm(A Ts)
    Bd = A^-1 (Ad - I) B            (paper Eq. 13)
    theta[k+1] = Ad theta[k] + Bd qdot[k]

We additionally fold the source-distribution matrix P into Bd
(Bd_src = Bd P, shape N x S) so the runtime step consumes per-source powers
directly — fewer MACs, no loss of fidelity.

Regeneration from a config/sampling-period change is a few dense ops and
takes milliseconds (benchmarked in benchmarks/exec_time.py), matching the
paper's claim that a DSS model is rebuilt rather than maintained. A model
retains only the minimal continuous-time arrays needed for that —
:class:`ContinuousSS` ``(A, B_src, H)`` as HOST float64 — not the parent
``ThermalRCModel`` (which would pin a second dense N x N G on device for
the lifetime of a serving process).

Batched design spaces: :class:`DSSFamilyModel` (``build_family(fam,
"dss")``) evaluates Ad/Bd per candidate with a vmapped ``expm`` over the
family's traced numeric assembly, so a parameter batch rides one device
batch axis end to end.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg

from ..kernels.dss_step.ops import dss_rollout, dss_step
from ..kernels.fused_cg.ops import all_finite, record_fallback
from ..runtime import span
from ..testing import faults
from .fidelity import (register_family_fidelity,
                       register_fidelity)
from .geometry import Package
from .rc_model import (RCFamilyModel, ThermalRCModel, build_model,
                       observation_matrix)


@dataclasses.dataclass
class ContinuousSS:
    """Minimal continuous-time state space for DSS regeneration.

    Host float64 numpy (never device-resident): regeneration is a host
    ``expm`` anyway, and keeping these off-device frees the second dense
    N x N matrix a retained parent RC model used to pin in long-lived
    serving processes.
    """
    a: np.ndarray            # (N, N)  C^-1 G
    b_src: np.ndarray        # (N, S)  C^-1 P (source distribution folded)
    h: np.ndarray            # (n_obs, N) observation operator
    t_ambient: float
    tags: list
    source_names: list


@dataclasses.dataclass
class DSSModel:
    ad: jnp.ndarray        # (N, N)
    bd: jnp.ndarray        # (N, S)  (P folded in)
    ad_t: jnp.ndarray      # transposed copies for the batched GEMM kernel
    bd_t: jnp.ndarray
    H: jnp.ndarray         # (n_obs, N) observation
    ts: float
    t_ambient: float
    tags: list = dataclasses.field(default_factory=list)
    source_names: list = dataclasses.field(default_factory=list)
    css: Optional[ContinuousSS] = None  # minimal regeneration state (host)
    # matrix-free steady solve (cg solver tier): a standalone jitted
    # closure over O(E) COO arrays (NOT the parent RC model — see module
    # docstring); shared unchanged by regenerated models
    steady_fn: Optional[callable] = dataclasses.field(default=None,
                                                      repr=False)
    _regen_cache: dict = dataclasses.field(default_factory=dict, repr=False)
    # numerical guardrail: structured record of the most recent solve's
    # promotion to the dense/reference path (None = primary path)
    last_fallback: Optional[dict] = dataclasses.field(default=None,
                                                      repr=False)

    fidelity = "dss"

    @property
    def n(self) -> int:
        return int(self.ad.shape[0])

    @property
    def n_sources(self) -> int:
        return int(self.bd.shape[1])

    def step(self, theta: jnp.ndarray, q_src: jnp.ndarray,
             backend: str = "auto") -> jnp.ndarray:
        """Single-trace step. theta (N,), q_src (S,)."""
        out = dss_step(theta[None, :], q_src[None, :], self.ad_t, self.bd_t,
                       backend=backend)
        return out[0]

    def simulate(self, theta0: jnp.ndarray, q_traj: jnp.ndarray,
                 backend: str = "auto") -> jnp.ndarray:
        """theta0 (N,), q_traj (T, S) -> chiplet temps (T, n_obs).

        Numerical guardrail: NaN/Inf rollout output (e.g. f32 overflow
        on a stiff pencil) promotes to the host-f64 exact-ZOH reference
        rollout of the retained continuous-time system, recorded in
        ``last_fallback`` instead of propagating poison."""
        thetas = dss_rollout(theta0[None], q_traj[:, None, :], self.ad_t,
                             self.bd_t, backend=backend)[:, 0]
        obs = thetas @ self.H.T + self.t_ambient
        self.last_fallback = None
        if not all_finite(faults.corrupt("dss.transient", obs)) \
                and self.css is not None:
            record_fallback("dss.transient")
            obs = self._host_reference_rollout(theta0, q_traj)
            self.last_fallback = {
                "site": "dss.transient",
                "to": "host-f64 exact-ZOH rollout",
                "reason": "non-finite rollout output"}
        return obs

    def _host_reference_rollout(self, theta0, q_traj) -> np.ndarray:
        """Guardrail reference: host-f64 exact ZOH of the retained
        continuous-time arrays at the built ``ts``."""
        ad, bd = zoh_discretize(self.css.a, self.css.b_src, self.ts)
        th = np.asarray(theta0, np.float64)
        q = np.asarray(q_traj, np.float64)
        obs = np.empty((q.shape[0], self.css.h.shape[0]))
        for k in range(q.shape[0]):
            th = ad @ th + bd @ q[k]
            obs[k] = self.css.h @ th
        return obs + self.t_ambient

    def simulate_batch(self, theta0: jnp.ndarray, q_traj: jnp.ndarray,
                       dt: Optional[float] = None,
                       backend: str = "auto") -> jnp.ndarray:
        """Batched-DSE rollout: theta0 (B,N), q_traj (T,B,S) -> (T,B,n_obs).

        The CPU implementation in the paper evaluates one trace at a time;
        batching candidate configurations through one GEMM is the TPU-native
        speedup (DESIGN.md §2) — the DSS step needs no vmap wrapper (unlike
        the other fidelities' shared ``simulate_batch_via_vmap`` helper).
        ``dt`` other than the built ``ts`` regenerates from the retained
        continuous-time arrays (milliseconds). The launch is the span
        ``dss.dispatch``.
        """
        if dt is not None and abs(dt - self.ts) > 1e-12:
            return self._regenerated(dt).simulate_batch(
                theta0, q_traj, backend=backend)
        with span("dss.dispatch"):
            thetas = dss_rollout(theta0, q_traj, self.ad_t, self.bd_t,
                                 backend=backend)
            return jnp.einsum("tbn,on->tbo", thetas, self.H) \
                + self.t_ambient

    # -- common ThermalSimulator protocol -----------------------------------
    def _regenerated(self, ts: float) -> "DSSModel":
        if self.css is None:
            raise ValueError(
                f"DSS model built for ts={self.ts} retains no "
                f"continuous-time state to regenerate at ts={ts}")
        key = round(ts, 12)  # match the 1e-12 dt tolerance of the callers
        if key not in self._regen_cache:  # expm is O(N^3); pay it once
            if len(self._regen_cache) >= 8:  # bound long-lived processes
                self._regen_cache.pop(next(iter(self._regen_cache)))
            self._regen_cache[key] = discretize_css(
                self.css, ts=ts, dtype=self.ad.dtype,
                steady_fn=self.steady_fn)
        return self._regen_cache[key]

    def steady_state(self, q_src) -> jnp.ndarray:
        """ZOH fixed point: solve (I - Ad) theta = Bd q.

        Dense tier: host float64 solve. cg tier (``steady_fn`` set by
        ``build(pkg, "dss", solver="cg")``): the continuous fixed point
        ``(-G)^-1 P q`` — mathematically identical to the ZOH fixed
        point — solved matrix-free on the COO kernel, never forming an
        N x N system.
        """
        self.last_fallback = None
        if self.steady_fn is not None:
            theta = faults.corrupt(
                "dss.steady",
                np.asarray(self.steady_fn(q_src), np.float64))
            if np.isfinite(theta).all():
                return jnp.asarray(theta, self.ad.dtype)
            # numerical guardrail: poisoned CG output -> dense ZOH
            # fixed point (mathematically the same steady state)
            record_fallback("dss.steady")
            self.last_fallback = {
                "site": "dss.steady",
                "to": "dense ZOH fixed-point solve",
                "reason": "non-finite CG steady output"}
        ad = np.asarray(self.ad, np.float64)
        bd = np.asarray(self.bd, np.float64)
        q = np.asarray(q_src, np.float64)
        theta = np.linalg.solve(np.eye(self.n) - ad, bd @ q)
        return jnp.asarray(theta, self.ad.dtype)

    def observe(self, theta) -> jnp.ndarray:
        """Absolute temperature at the observation tags (self.tags order)."""
        return self.H @ theta + self.t_ambient

    def make_simulator(self, dt: Optional[float] = None,
                       backend: str = "auto"):
        """simulate(theta0, q_traj[T,S]) -> (T, n_obs) at sampling period
        dt (defaults to the built ts; other dt regenerates — paper §4.4)."""
        if dt is not None and abs(dt - self.ts) > 1e-12:
            return self._regenerated(dt).make_simulator(backend=backend)

        def simulate(theta0, q_traj):
            return self.simulate(theta0, q_traj, backend=backend)

        return simulate

    def zero_state(self, batch: Optional[int] = None) -> jnp.ndarray:
        shape = (self.n,) if batch is None else (batch, self.n)
        return jnp.zeros(shape, self.ad.dtype)


def continuous_ss(rc: ThermalRCModel) -> ContinuousSS:
    """Extract the minimal (A, B, H) regeneration state from an RC model
    (host float64, independent of the RC model's device arrays)."""
    C = np.asarray(rc.net.C, np.float64)
    G = np.asarray(rc.net.g_dense(), np.float64)
    P = np.asarray(rc.net.P, np.float64)
    return ContinuousSS(a=G / C[:, None], b_src=P / C[:, None],
                        h=observation_matrix(rc.net, rc.tags),
                        t_ambient=rc.t_ambient, tags=list(rc.tags),
                        source_names=list(rc.source_names))


def zoh_discretize(a: np.ndarray, b: np.ndarray, ts: float):
    """Exact zero-order-hold discretization (paper Eq. 13), host f64:
    ``Ad = expm(A Ts)``, ``Bd = A^-1 (Ad - I) B``.

    THE discretization of the ladder's state-space rungs: the full-order
    DSS model feeds it (N x N), and the ROM rung (``core/rom.py``) feeds
    it the reduced r x r system — same math, node-count-independent cost.
    """
    a = np.asarray(a, np.float64)
    ad = _expm(a * ts)
    bd = np.linalg.solve(a, ad - np.eye(a.shape[0])) \
        @ np.asarray(b, np.float64)
    return ad, bd


def discretize_css(css: ContinuousSS, ts: float = 0.01,
                   dtype=jnp.float32,
                   steady_fn: Optional[callable] = None) -> DSSModel:
    """ZOH-discretize a continuous-time state space (paper Eq. 13).

    Computed in float64 on host (expm of a stiff matrix), stored in the
    requested runtime dtype. ``steady_fn`` (cg solver tier) rides along
    unchanged — the steady state is sampling-period independent.
    """
    ad, bd = zoh_discretize(css.a, css.b_src, ts)
    return DSSModel(ad=jnp.asarray(ad, dtype), bd=jnp.asarray(bd, dtype),
                    ad_t=jnp.asarray(ad.T, dtype),
                    bd_t=jnp.asarray(bd.T, dtype),
                    H=jnp.asarray(css.h, dtype), ts=ts,
                    t_ambient=css.t_ambient, tags=list(css.tags),
                    source_names=list(css.source_names), css=css,
                    steady_fn=steady_fn)


def discretize_rc(rc: ThermalRCModel, ts: float = 0.01,
                  dtype=jnp.float32) -> DSSModel:
    """Build the DSS model from a thermal RC model (paper Eq. 13).

    Only the minimal continuous-time (A, B, H) arrays are retained for
    later regeneration — NOT ``rc`` itself (see module docstring). If the
    RC model runs on the "cg" solver tier, its standalone matrix-free
    steady closure (O(E) arrays only) is carried over so ``steady_state``
    stays matrix-free too.
    """
    # ready-to-call (the device part is jitted inside; on the f32 tier
    # it is the mixed-precision refined solve, no x64 required)
    steady_fn = rc.make_steady_solver() if rc.solver == "cg" else None
    return discretize_css(continuous_ss(rc), ts=ts, dtype=dtype,
                          steady_fn=steady_fn)


@register_fidelity("dss")
def build_dss(pkg: Package, ts: float = 0.01, cap_multipliers=None,
              dtype=jnp.float32, solver: str = "dense",
              cg_tol=None, cg_maxiter: int = 1000,
              cg_impl: str = "auto") -> DSSModel:
    """Registry builder: package -> RC network -> exact-ZOH DSS model.

    ``solver`` is the solver-tier knob: the ZOH discretization itself is
    inherently dense (``expm``), so the tier governs the steady-state
    path — "cg"/"auto" (above the crossover) solve the continuous fixed
    point matrix-free as fused CG-step launches (``kernels/fused_cg``;
    ``cg_impl="unfused"`` falls back to the one-op-per-piece
    composition) instead of the host dense solve.
    ``dtype``/``cg_tol``/``cg_maxiter``/``cg_impl`` thread through to
    that solve; its convergence stats are readable post-call on the
    retained closure (``model.steady_fn.last_stats``).
    """
    return discretize_rc(
        build_model(pkg, cap_multipliers=cap_multipliers, solver=solver,
                    dtype=dtype, cg_tol=cg_tol, cg_maxiter=cg_maxiter,
                    cg_impl=cg_impl),
        ts=ts, dtype=dtype)


def _expm(a: np.ndarray) -> np.ndarray:
    """Scaling-and-squaring matrix exponential (host, float64)."""
    return scipy.linalg.expm(np.asarray(a, np.float64))


class EighZOH:
    """Host-float64 exact-ZOH reference evaluator over ONE symmetric
    eigendecomposition of the whitened pencil.

    Whitening the state (``z = C^(1/2) theta``) turns the RC dynamics
    into ``z' = Sym z + C^(-1/2) P q`` with ``Sym = C^(-1/2) G C^(-1/2)``
    symmetric negative definite, so a single ``eigh`` (cheaper and
    better-conditioned than a stiff ``expm``, and reusable) yields the
    exact ZOH pair at ANY sampling period as two O(N^2) products:

        Ad = C^(-1/2) U e^(w dt) U' C^(1/2),
        Bd = C^(-1/2) U diag((e^(w dt)-1)/w) U' C^(-1/2) P.

    This is the adaptive router's reference rung (``core/router.py``):
    its transient answers are full-order f64 exact-ZOH rollouts — the
    same discretization class the acceptance tests measure against — and
    the factor cache doubles as the error certifier's source of the
    exact decay rate ``lambda_min`` (the whitened pencil's eigenvalue
    closest to zero) and of the ``Ad V`` products behind the ROM
    transient certificates. The spectrum is strictly negative for any
    grounded (convection-coupled) package; a non-negative mode means the
    network has a floating component and is rejected.
    """

    def __init__(self, net, tags: Optional[list] = None):
        import scipy.linalg as sla
        self.net = net
        c = np.asarray(net.C, np.float64)
        self._c_sqrt = np.sqrt(c)
        self._c_isqrt = 1.0 / self._c_sqrt
        sym = net.g_dense() * self._c_isqrt[:, None] * self._c_isqrt
        self.w, self.u = sla.eigh(0.5 * (sym + sym.T))
        if self.w.max() >= 0.0:
            raise ValueError(
                f"whitened pencil has a non-decaying mode "
                f"(max eig {self.w.max():.3e} >= 0): floating network?")
        self.h = observation_matrix(net, tags)
        self.tags = sorted({t for t in net.grid.tags if t}) \
            if tags is None else list(tags)
        self.source_names = list(net.grid.source_names)
        self.t_ambient = float(net.t_ambient)
        self._p_white = self._c_isqrt[:, None] * np.asarray(net.P,
                                                            np.float64)
        self._disc: dict = {}

    @property
    def lambda_min(self) -> float:
        """Exact slowest decay rate of the pencil (-G, C): the whitened
        spectrum's eigenvalue closest to zero, negated."""
        return float(-self.w.max())

    def discretize(self, dt: float):
        """Exact host-f64 ZOH pair ``(ad, bd)`` at sampling period dt —
        O(N^2) from the cached factors, bounded per-dt cache (same
        policy as ``DSSModel._regen_cache``)."""
        key = round(float(dt), 12)
        hit = self._disc.get(key)
        if hit is not None:
            return hit
        if len(self._disc) >= 8:
            self._disc.pop(next(iter(self._disc)))
        e = np.exp(self.w * dt)
        ad_w = (self.u * e) @ self.u.T
        bd_w = (self.u * ((e - 1.0) / self.w)) @ (self.u.T @ self._p_white)
        ad = self._c_isqrt[:, None] * ad_w * self._c_sqrt
        bd = self._c_isqrt[:, None] * bd_w
        self._disc[key] = (ad, bd)
        return self._disc[key]

    def steady(self, q_src) -> np.ndarray:
        """Exact steady state ``(-G)^-1 P q`` from the factors (host f64)."""
        q = np.asarray(q_src, np.float64)
        z = -(self.u / self.w) @ (self.u.T @ (self._p_white @ q))
        return self._c_isqrt * z

    def simulate(self, theta0, q_traj, dt: float) -> np.ndarray:
        """theta0 (N,), q_traj (T, S) -> observations (T, n_obs) in
        absolute degC, post-step sampling (the ladder's convention)."""
        ad, bd = self.discretize(dt)
        th = np.asarray(theta0, np.float64)
        q = np.asarray(q_traj, np.float64)
        obs = np.empty((q.shape[0], self.h.shape[0]))
        for k in range(q.shape[0]):
            th = ad @ th + bd @ q[k]
            obs[k] = self.h @ th
        return obs + self.t_ambient


def spectral_radius(dss: DSSModel) -> float:
    """max |eig(Ad)| — must be < 1 for a dissipative package (stability;
    property-tested in tests/test_dss.py)."""
    return float(np.max(np.abs(np.linalg.eigvals(np.asarray(dss.ad,
                                                            np.float64)))))


# ---------------------------------------------------------------------------
# Batched design-space model
# ---------------------------------------------------------------------------
def family_zoh_simulate(discretize_one, n_state: int, dtype):
    """Shared family-transient kernel of the state-space rungs.

    ``discretize_one(p) -> (ad, bd, h, t_amb, scale)`` is the traced
    per-candidate exact-ZOH discretization — full-order N x N for the
    DSS family, reduced r x r for the ROM family. The returned
    ``simulate(params, q_traj)`` (ready to jit) vmaps it over the
    parameter batch and rolls the trace with one batched GEMM pair per
    step, from the zero state, emitting absolute degC observations.
    """
    def simulate(params, q_traj):
        ad, bd, h, t_amb, scale = jax.vmap(discretize_one)(params)

        def body(th, qt):  # th (B, n_state), qt (B, S)
            q = qt.astype(th.dtype) * scale[:, None]
            th = jnp.einsum("bnm,bm->bn", ad, th) \
                + jnp.einsum("bns,bs->bn", bd, q)
            return th, jnp.einsum("bon,bn->bo", h, th)

        th0 = jnp.zeros((params.shape[0], n_state), dtype)
        _, obs = jax.lax.scan(body, th0, q_traj)
        return obs + t_amb[None, :, None]

    return simulate


class DSSFamilyModel:
    """DSS model over a ``PackageFamily``: per-candidate exact-ZOH
    discretization as a traced, vmapped function of the parameter vector.

    Steady state delegates to the RC family's template-preconditioned CG —
    the ZOH fixed point ``(I - Ad)^-1 Bd q`` equals the continuous fixed
    point ``(-G)^-1 P q`` exactly, so no per-candidate ``expm`` is paid
    for steady sweeps. Transients (``simulate_family``) evaluate
    ``Ad = expm(A dt)`` per candidate under vmap, then roll the batch with
    one GEMM per step (the kernel formulation of ``kernels/dss_step``,
    generalized to per-candidate Ad/Bd). Batch execution — mesh sharding,
    padding, chunk streaming — rides the embedded RC family's
    :class:`~repro.distribution.family_exec.FamilyExecutor` (one executor
    per family stack, so ``mesh=``/``chunk_size=`` passed here govern the
    steady AND transient paths).
    """

    fidelity = "dss"

    def __init__(self, family, ts: float = 0.01,
                 cap_multipliers: Optional[dict] = None,
                 dtype=jnp.float32, **rc_opts):
        self.rcf = RCFamilyModel(family, cap_multipliers=cap_multipliers,
                                 dtype=dtype, **rc_opts)
        self.family = family
        self.ts = ts
        self.dtype = dtype
        self.tags = self.rcf.tags
        self.source_names = self.rcf.source_names
        self.param_names = self.rcf.param_names

    @property
    def n(self) -> int:
        return self.rcf.n

    def steady_state_batch(self, params, q_src) -> jnp.ndarray:
        return self.rcf.steady_state_batch(params, q_src)

    def observe_batch(self, theta, params) -> jnp.ndarray:
        return self.rcf.observe_batch(theta, params)

    def simulate_family(self, params, q_traj,
                        dt: Optional[float] = None) -> jnp.ndarray:
        """params (B, P), q_traj (T, B, S) -> obs temps (T, B, n_obs).

        ``dt`` defaults to the built ``ts``; any other value simply traces
        a new discretization (regeneration is part of the same jit)."""
        dt = self.ts if dt is None else float(dt)
        rcf = self.rcf

        def discretize_one(p):
            v = rcf._network(p.astype(self.dtype))
            c = v["C"]
            g = rcf.num.dense_g(v["gvals"], v["gconv"])
            a = g / c[:, None]
            ad = jax.scipy.linalg.expm(a * dt)
            eye = jnp.eye(a.shape[0], dtype=a.dtype)
            bd = jnp.linalg.solve(a, ad - eye) @ (v["P"] / c[:, None])
            return (ad, bd, v["H"], v["t_ambient"], v["power_scale"])

        return rcf.exec.run(
            # namespaced per family stack; dt-rounded like _regenerated
            (f"{rcf._ns}:dss_simulate", round(dt, 12)),
            family_zoh_simulate(discretize_one, self.n, self.dtype),
            (params, q_traj), in_axes=(0, 1), out_axis=1,
            pad_rows=(rcf._pad_param_row, None))


@register_family_fidelity("dss")
def build_dss_family(family, ts: float = 0.01, cap_multipliers=None,
                     dtype=jnp.float32, **opts) -> DSSFamilyModel:
    return DSSFamilyModel(family, ts=ts, cap_multipliers=cap_multipliers,
                          dtype=dtype, **opts)
