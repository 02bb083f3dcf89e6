"""Thermal RC network model (paper §4.3, Eqs. 4-7) and ODE solvers.

Network assembly is host-side numpy (geometry handling); simulation is
jitted JAX. State is theta = T - T_ambient so convection to ambient becomes
a pure diagonal conductance:

    C theta_dot = G theta + P q_src        (paper Eq. 6)

where G has off-diagonal inter-node conductances and diagonal
-(sum of neighbors) - G_conv (paper Eq. 7), and P (N x S) distributes each
named source's power over its block's nodes by area fraction.

TPU adaptation (DESIGN.md §2): the paper prefactors with SuperLU; sparse LU
has no TPU analogue, but N is small (hundreds), so we prefactor the SPD
matrix M = C/dt - G with a dense Cholesky once and run triangular solves
inside lax.scan — MXU-friendly and exact. A matrix-free CG path covers
large N. Baseline tools are emulated via the `method` switch (see
core/baselines.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distribution.family_exec import FamilyExecutor
from ..kernels.coo_matvec.ops import coo_matvec, coo_plan, coo_segment_sum
from ..kernels.fused_cg.adjoint import make_implicit_steady
from ..kernels.fused_cg.ops import (CGStats, fused_cg_plan, fused_cg_solve,
                                    pcg_loop, resolve_cg_impl,
                                    warn_unconverged)
from ..runtime import span
from .assembly import NumericAssembly, adjacency_within, overlap_between
from .fidelity import (register_family_fidelity, register_fidelity,
                       resolve_solver, simulate_batch_via_vmap)
from .geometry import NodeGrid, Package, chiplet_tags, discretize

_EPS = 1e-12


@dataclasses.dataclass
class RCNetwork:
    """Assembled network: capacitances, conductance graph, source map."""
    C: np.ndarray            # (N,) J/K
    rows: np.ndarray         # (E,) int32   coo of symmetric off-diagonals
    cols: np.ndarray         # (E,)
    gvals: np.ndarray        # (E,) W/K
    gconv: np.ndarray        # (N,) W/K  diagonal convection conductance
    P: np.ndarray            # (N, S) power distribution matrix
    grid: NodeGrid
    t_ambient: float

    @property
    def n(self) -> int:
        return int(self.C.shape[0])

    @property
    def n_sources(self) -> int:
        return int(self.P.shape[1])

    def g_dense(self) -> np.ndarray:
        """Paper Eq. 7 matrix (with convection on the diagonal)."""
        n = self.n
        G = np.zeros((n, n), dtype=np.float64)
        np.add.at(G, (self.rows, self.cols), self.gvals)
        G[np.arange(n), np.arange(n)] = -(G.sum(axis=1) + self.gconv)
        return G

    def neg_g_diag(self) -> np.ndarray:
        """Diagonal of -G (host f64): off-diagonal row sums + convection.

        THE host-side -G convention: every matrix-free consumer (the
        refined steady solve, the ROM basis/projection) derives its
        diagonal here so a change to the assembly stays in one place.
        """
        return np.bincount(self.rows, weights=self.gvals,
                           minlength=self.n) + self.gconv

    def neg_g_sparse(self, shift: float = 0.0):
        """``shift * diag(C) - G`` as a host f64 scipy CSC matrix: the
        sparse operator behind the host factorizations and references."""
        import scipy.sparse as sp
        n = self.n
        diag = sp.diags(self.neg_g_diag() + shift * self.C)
        off = sp.coo_matrix((self.gvals, (self.rows, self.cols)),
                            shape=(n, n))
        return (diag - off).tocsc()

    def neg_g_matvec(self, x: np.ndarray) -> np.ndarray:
        """(-G) @ x on the host (f64, O(E n_cols)); x is (N,) or (N, k)."""
        x = np.asarray(x, np.float64)
        d = self.neg_g_diag()
        if x.ndim == 1:
            y = d * x
            contrib = self.gvals * x[self.cols]
        else:
            y = d[:, None] * x
            contrib = self.gvals[:, None] * x[self.cols]
        if self.rows.size:
            np.subtract.at(y, self.rows, contrib)
        return y


def _lateral_gvals(grid: NodeGrid, i: np.ndarray, j: np.ndarray,
                   axis: str) -> np.ndarray:
    """Series half-resistance conductances between lateral neighbor pairs."""
    if axis == "x":
        li = grid.x1[i] - grid.x0[i]
        lj = grid.x1[j] - grid.x0[j]
        ov = np.minimum(grid.y1[i], grid.y1[j]) \
            - np.maximum(grid.y0[i], grid.y0[j])
        ki, kj = grid.kx[i], grid.kx[j]
    else:
        li = grid.y1[i] - grid.y0[i]
        lj = grid.y1[j] - grid.y0[j]
        ov = np.minimum(grid.x1[i], grid.x1[j]) \
            - np.maximum(grid.x0[i], grid.x0[j])
        ki, kj = grid.ky[i], grid.ky[j]
    area = ov * grid.lz[i]  # same layer -> same thickness
    r = 0.5 * li / (ki * area) + 0.5 * lj / (kj * area)
    return 1.0 / r


def build_network(pkg: Package, grid: Optional[NodeGrid] = None,
                  cap_multipliers: Optional[dict] = None) -> RCNetwork:
    """Assemble the RC network from the package geometry.

    Neighbor discovery is the vectorized O(E log E) sweep of
    ``core/assembly.py`` (the seed's O(n^2) pair loops are preserved in
    ``core/assembly_ref.py`` for equivalence testing only); conductances are
    then evaluated from the matched rects' coordinates, so the result is
    bitwise-identical to the reference builder.

    cap_multipliers: optional {layer_index: float} from capacitance tuning
    (paper §4.3 "Capacitance Tuning").
    """
    if grid is None:
        grid = discretize(pkg)
    n = grid.n
    C = grid.cv * grid.volume
    if cap_multipliers:
        for li, mult in cap_multipliers.items():
            C = np.where(grid.layer == li, C * mult, C)

    rows, cols, gvals = [], [], []  # per-layer COO chunks

    def _emit(i, j, g):
        if len(i):
            rows.append(np.concatenate([i, j]))
            cols.append(np.concatenate([j, i]))
            gvals.append(np.concatenate([g, g]))

    layer_nodes = [np.nonzero(grid.layer == li)[0]
                   for li in range(grid.n_layers)]

    # --- lateral neighbors within each layer -------------------------------
    for li in range(grid.n_layers):
        idx = layer_nodes[li]
        if idx.size == 0:
            continue
        (xi, xj), (yi, yj) = adjacency_within(
            grid.x0[idx], grid.x1[idx], grid.y0[idx], grid.y1[idx], _EPS)
        for pi, pj, axis in ((xi, xj, "x"), (yi, yj, "y")):
            i, j = idx[pi], idx[pj]
            _emit(i, j, _lateral_gvals(grid, i, j, axis))

    # --- vertical neighbors between adjacent layers (xy overlap) -----------
    for li in range(grid.n_layers - 1):
        lower, upper = layer_nodes[li], layer_nodes[li + 1]
        if lower.size == 0 or upper.size == 0:
            continue
        pi, pj = overlap_between(
            grid.x0[lower], grid.x1[lower], grid.y0[lower], grid.y1[lower],
            grid.x0[upper], grid.x1[upper], grid.y0[upper], grid.y1[upper],
            _EPS)
        i, j = lower[pi], upper[pj]
        ox = np.minimum(grid.x1[i], grid.x1[j]) \
            - np.maximum(grid.x0[i], grid.x0[j])
        oy = np.minimum(grid.y1[i], grid.y1[j]) \
            - np.maximum(grid.y0[i], grid.y0[j])
        area = ox * oy
        r = 0.5 * grid.lz[i] / (grid.kz[i] * area) + \
            0.5 * grid.lz[j] / (grid.kz[j] * area)
        _emit(i, j, 1.0 / r)

    # --- convection boundaries (both package faces; Table 1 feature) -------
    gconv = np.zeros(n, dtype=np.float64)
    top = grid.layer == grid.n_layers - 1
    bot = grid.layer == 0
    gconv[top] += pkg.htc_top * grid.area[top]
    gconv[bot] += pkg.htc_bottom * grid.area[bot]

    # --- power distribution matrix -----------------------------------------
    S = len(grid.source_names)
    P = np.zeros((n, S), dtype=np.float64)
    for s in range(S):
        nodes = np.nonzero(grid.power_idx == s)[0]
        total = grid.area[nodes].sum()
        P[nodes, s] = grid.area[nodes] / total

    cat = lambda parts, dt: (np.concatenate(parts).astype(dt) if parts
                             else np.zeros(0, dtype=dt))
    return RCNetwork(C=C,
                     rows=cat(rows, np.int32),
                     cols=cat(cols, np.int32),
                     gvals=cat(gvals, np.float64),
                     gconv=gconv, P=P, grid=grid, t_ambient=pkg.t_ambient)


# ---------------------------------------------------------------------------
# Observation operator: per-chiplet temperature (area-weighted quadrant mean)
# ---------------------------------------------------------------------------
def observation_matrix(net: RCNetwork, tags: Optional[list] = None
                       ) -> np.ndarray:
    """(n_obs, N) matrix mapping node theta -> per-chiplet mean theta."""
    if tags is None:
        tags = sorted({t for t in net.grid.tags if t})
    H = np.zeros((len(tags), net.n), dtype=np.float64)
    for k, tag in enumerate(tags):
        idx = net.grid.nodes_of_tag(tag)
        w = net.grid.area[idx]
        H[k, idx] = w / w.sum()
    return H


# ---------------------------------------------------------------------------
# Solvers
# ---------------------------------------------------------------------------
# method mapping applied when the model runs on the "cg" solver tier:
# dense-factorization integrators fall through to their matrix-free twin
_CG_METHOD_MAP = {"be_chol": "be_cg", "be_lu": "be_cg", "trap": "trap_cg"}


class ThermalRCModel:
    """Continuous-time thermal RC model with pluggable integrators.

    method:
      'be_chol' — backward Euler, dense Cholesky prefactored (OURS; the
                  TPU-native stand-in for the paper's SuperLU+BLAS)
      'be_cg'   — backward Euler, matrix-free Jacobi-preconditioned CG
                  (large-N path)
      'be_lu'   — backward Euler, per-step dense solve (3D-ICE-like cost)
      'trap'    — trapezoidal per-step solve (PACT/Xyce TRAP-like)
      'trap_cg' — trapezoidal, matrix-free Jacobi-preconditioned CG
      'rk4'     — explicit RK4 with stability substepping (HotSpot-like)

    solver (the solver TIER, orthogonal to the integrator):
      'dense'   — materialize the dense (N, N) G; steady state is a dense
                  solve; integrators as requested. Exact; right for the
                  paper's few-hundred-node networks.
      'cg'      — fully matrix-free: the dense G is never built, steady
                  state is Jacobi-preconditioned CG on the O(E) COO
                  matvec kernel (``kernels/coo_matvec``), and dense
                  integrators map to their matrix-free twin
                  (be_chol/be_lu -> be_cg, trap -> trap_cg). On f32
                  models the steady solve is wrapped in a mixed-precision
                  iterative-refinement loop (f64 host residuals, f32
                  device corrections) reaching f64-dense agreement
                  without JAX_ENABLE_X64; opt out with refine_passes=0.
      'auto'    — 'cg' at or above the measured crossover node count
                  (``fidelity.SOLVER_CROSSOVER_NODES``), else 'dense'.

    cg_impl (how a CG iteration executes, orthogonal to the tier):
      'fused'   — the whole PCG iteration (matvec, Jacobi apply,
                  reductions, axpys) is one ``kernels/fused_cg`` step:
                  a single Pallas launch on TPU, a single gather-only
                  ELL ``while_loop`` body on CPU.
      'unfused' — the historical one-op-per-piece composition
                  (``segment_sum`` matvec), kept as the escape hatch and
                  the benchmark A/B contrast.
      'auto'    — 'fused' (the default everywhere).
    Every CG solve records per-solve convergence stats; see
    ``last_cg_stats`` and the ``last_stats`` attribute on the closures
    returned by :meth:`make_steady_solver` / :meth:`make_simulator`.
    """

    fidelity = "rc"

    def __init__(self, net: RCNetwork, dtype=jnp.float32,
                 method: str = "be_chol", solver: str = "dense",
                 cg_tol: Optional[float] = None, cg_maxiter: int = 1000,
                 matvec_backend: str = "auto", cg_impl: str = "auto",
                 refine_rtol: float = 1e-9, refine_passes: int = 4):
        self.net = net
        self.dtype = dtype
        self.solver = resolve_solver(solver, net.n)
        self.default_method = _CG_METHOD_MAP.get(method, method) \
            if self.solver == "cg" else method
        self.tags = sorted({t for t in net.grid.tags if t})
        self.source_names = list(net.grid.source_names)
        self.C = jnp.asarray(net.C, dtype)
        self.P = jnp.asarray(net.P, dtype)
        self._h64 = observation_matrix(net, self.tags)  # host f64
        self.H = jnp.asarray(self._h64, dtype)
        self.t_ambient = net.t_ambient
        # COO pattern + values for the matrix-free path (always kept:
        # O(E), and the be_cg/trap_cg integrators are method-selectable
        # even on the dense tier)
        self._plan = coo_plan(net.rows, net.cols, net.n)
        self._backend = matvec_backend
        self.cg_impl = resolve_cg_impl(cg_impl)
        self._gvals = jnp.asarray(net.gvals, dtype)
        self._gdiag = jnp.asarray(-net.neg_g_diag(), dtype)
        self._fused_plan_cache = None  # fused-CG plan, built lazily
        self.last_cg_stats: Optional[CGStats] = None
        # steady-solve CG controls; f32 runs to its residual floor, so the
        # default tolerance is tier-appropriate rather than aspirational
        self.cg_tol = cg_tol if cg_tol is not None else \
            (1e-11 if dtype == jnp.float64 else 1e-5)
        self.cg_maxiter = cg_maxiter
        # mixed-precision iterative-refinement controls (f32 cg steady)
        self.refine_rtol = refine_rtol
        self.refine_passes = refine_passes
        self._G = None  # dense G, built lazily (never on the cg tier)

    @property
    def G(self) -> jnp.ndarray:
        """Dense paper-Eq.-7 G — materialized on first access only (the
        'cg' solver tier never touches it)."""
        if self._G is None:
            self._G = jnp.asarray(self.net.g_dense(), self.dtype)
        return self._G

    @property
    def _fused_plan(self):
        """Fused-CG plan (RCM ordering, windowed tiles, ELL arrays) —
        built on first CG solve only; the dense tier never pays it."""
        if self._fused_plan_cache is None:
            self._fused_plan_cache = fused_cg_plan(
                self.net.rows, self.net.cols, self.net.n)
        return self._fused_plan_cache

    # -- matrix-free G @ theta ----------------------------------------------
    def _gmatvec(self, theta):
        off = coo_matvec(self._plan, self._gvals, theta,
                         backend=self._backend)
        return off + self._gdiag * theta

    def make_steady_solver(self, refine: Optional[bool] = None):
        """Standalone matrix-free steady solve ``q_src -> theta``
        (ready to call; the device part is jitted internally).

        Neither path pins the model or a dense N x N matrix: the
        unrefined closure captures only O(E) device arrays (plan, COO
        values, diagonal, P), and the refined path additionally holds
        the host :class:`RCNetwork` (O(E)+O(N) numpy arrays, incl. its
        grid) for the f64 residual matvec — so long-lived consumers
        (e.g. a DSS model on the cg tier) can keep it cheaply. Solves
        (-G) theta = P q by Jacobi-preconditioned CG on the COO matvec
        kernel.

        ``refine`` (default: on unless the model already runs in float64)
        wraps the CG in a mixed-precision ITERATIVE-REFINEMENT outer
        loop: residuals and the solution accumulate in float64 on the
        host (an O(E) numpy matvec), correction solves run the f32 device
        CG. The refined solve returns a float64 numpy theta that agrees
        with the f64 dense tier to <=1e-6 degC WITHOUT ``JAX_ENABLE_X64``
        — ``observe`` keeps such states on the host f64 path end to end.

        Either closure records a :class:`CGStats` on itself as
        ``.last_stats`` after each concrete call (device iteration count,
        final relative residual, ``converged``) and warns host-side when
        the solve hit the iteration cap. ``.device_solve`` is the jitted
        device CG solve ``rhs (N,) -> (x, CGStats)`` both closures run,
        exposed so callers can lower it and inspect the program.
        """
        gvals, gdiag = self._gvals, self._gdiag
        dtype, backend, impl = self.dtype, self._backend, self.cg_impl
        tol, maxiter = self.cg_tol, self.cg_maxiter
        neg_diag = -gdiag
        plan_f = self._fused_plan

        @jax.jit
        def solve_dev(rhs):  # (-G) x = rhs by Jacobi-PCG, device dtype
            return fused_cg_solve(plan_f, neg_diag, gvals, rhs,
                                  tol=tol, maxiter=maxiter,
                                  impl=impl, backend=backend)

        p_dev = self.P

        @jax.jit
        def steady_dev(q_src):
            return solve_dev(p_dev @ jnp.asarray(q_src, dtype))

        if refine is None:  # refine_passes=0 opts out of refinement
            refine = dtype != jnp.float64 and self.refine_passes > 0
        if not refine:
            def steady_plain(q_src):
                sol, stats = steady_dev(q_src)
                if not isinstance(sol, jax.core.Tracer):
                    steady_plain.last_stats = stats
                    warn_unconverged(stats, "rc steady CG")
                return sol

            steady_plain.last_stats = None
            steady_plain.device_solve = solve_dev
            return steady_plain

        # host float64 side: residuals via the network's O(E) COO matvec
        net = self.net
        p64 = net.P
        # an EXPLICIT refine=True overrides refine_passes=0 (which would
        # otherwise return the zero initial guess unsolved)
        rtol = self.refine_rtol
        max_passes = max(self.refine_passes, 1)

        def steady(q_src):
            rhs = p64 @ np.asarray(q_src, np.float64)
            bnorm = np.linalg.norm(rhs) or 1.0
            x = np.zeros(net.n)
            iters = 0
            for _ in range(max_passes):
                res = rhs - net.neg_g_matvec(x)
                if np.linalg.norm(res) <= rtol * bnorm:
                    break
                corr, st = solve_dev(jnp.asarray(res, dtype))
                iters += int(np.asarray(st.iterations))
                x = x + np.asarray(corr, np.float64)
            # stats in the refined solve's own terms: total device CG
            # iterations across passes, final HOST f64 relative residual,
            # convergence against the refinement target
            rel = np.linalg.norm(rhs - net.neg_g_matvec(x)) / bnorm
            stats = CGStats(iterations=np.int32(iters),
                            residual=np.float64(rel),
                            converged=np.bool_(rel <= rtol))
            steady.last_stats = stats
            warn_unconverged(stats, "rc refined steady CG")
            return x

        steady.last_stats = None
        steady.device_solve = solve_dev
        return steady

    def steady_state(self, q_src):
        """Steady theta: solve -G theta = P q (dense or matrix-free CG,
        by solver tier). On the f32 cg tier the solve is refined to f64
        accuracy (see :meth:`make_steady_solver`) and returned as a host
        float64 array."""
        if self.solver == "cg":
            if not hasattr(self, "_steady_fn"):
                self._steady_fn = self.make_steady_solver()
            sol = self._steady_fn(q_src)
            self.last_cg_stats = self._steady_fn.last_stats
            return sol
        rhs = self.P @ jnp.asarray(q_src, self.dtype)
        return jnp.linalg.solve(-self.G, rhs)

    def observe(self, theta) -> jnp.ndarray:
        """Absolute temperature at the observation tags (self.tags order).

        Host-float64 states (from the refined cg steady solve) stay on
        the host f64 observation operator, so the <=1e-6 degC agreement
        with the f64 dense tier survives observation without x64.
        """
        if isinstance(theta, np.ndarray) and theta.dtype == np.float64:
            return self._h64 @ theta + self.t_ambient
        return self.H @ theta + self.t_ambient

    def make_stepper(self, dt: float, method: Optional[str] = None):
        """Return step(theta, q_src) -> theta' (jittable)."""
        method = method or self.default_method
        if self.solver == "cg":  # never factor/materialize dense G
            method = _CG_METHOD_MAP.get(method, method)
        C, P = self.C, self.P
        if method == "be_chol":
            M = jnp.diag(C / dt) - self.G
            chol = jax.scipy.linalg.cho_factor(M)

            def step(theta, q):
                rhs = C / dt * theta + P @ q
                return jax.scipy.linalg.cho_solve(chol, rhs)
        elif method == "be_cg":
            # backward Euler, matrix-free: (C/dt - G) th' = C/dt th + P q
            # = diag(C/dt - gdiag) - offdiag(gvals), one fused CG step per
            # iteration (kernels/fused_cg)
            cdt = C / dt
            diag = cdt - self._gdiag
            plan_f, gvals = self._fused_plan, self._gvals
            impl, backend = self.cg_impl, self._backend
            tol = min(self.cg_tol, 1e-8)

            def step_stats(theta, q):
                rhs = cdt * theta + P @ q
                return fused_cg_solve(plan_f, diag, gvals, rhs, x0=theta,
                                      tol=tol, maxiter=200,
                                      impl=impl, backend=backend)

            def step(theta, q):
                return step_stats(theta, q)[0]

            step.with_stats = step_stats
        elif method == "be_lu":
            M = jnp.diag(C / dt) - self.G

            def step(theta, q):
                rhs = C / dt * theta + P @ q
                return jnp.linalg.solve(M, rhs)
        elif method == "trap":
            Ml = jnp.diag(C / dt) - 0.5 * self.G
            Mr = jnp.diag(C / dt) + 0.5 * self.G

            def step(theta, q):
                rhs = Mr @ theta + P @ q
                return jnp.linalg.solve(Ml, rhs)
        elif method == "trap_cg":
            # trapezoidal, matrix-free: (C/dt - G/2) th' = (C/dt + G/2) th
            # + P q; the left side is diag(C/dt - gdiag/2) -
            # offdiag(gvals/2), solved by the fused CG step; the explicit
            # right side reuses the plain COO matvec (one op per step)
            cdt = C / dt
            diag = cdt - 0.5 * self._gdiag
            plan_f = self._fused_plan
            gvals_half = 0.5 * self._gvals
            impl, backend = self.cg_impl, self._backend
            gm = self._gmatvec
            tol = min(self.cg_tol, 1e-8)

            def step_stats(theta, q):
                rhs = cdt * theta + 0.5 * gm(theta) + P @ q
                return fused_cg_solve(plan_f, diag, gvals_half, rhs,
                                      x0=theta, tol=tol, maxiter=200,
                                      impl=impl, backend=backend)

            def step(theta, q):
                return step_stats(theta, q)[0]

            step.with_stats = step_stats
        elif method == "rk4":
            # Gershgorin bound on |lambda|_max of C^-1 G -> substep count
            if self.solver == "cg":  # O(E) bound; no dense materialization
                row_abs = np.bincount(self.net.rows,
                                      weights=np.abs(self.net.gvals),
                                      minlength=self.net.n) \
                    + np.abs(np.asarray(self._gdiag, np.float64))
                lam = float(np.max(row_abs / self.net.C))
                gmv = self._gmatvec

                def gx(theta):
                    return gmv(theta)
            else:
                G = self.G
                lam = float(np.max((np.abs(self.net.g_dense())
                                    .sum(axis=1)) / self.net.C))

                def gx(theta):
                    return G @ theta
            nsub = max(1, int(np.ceil(dt * lam / 2.5)))
            h = dt / nsub

            def f(theta, qn):
                return (gx(theta) + qn) / C

            def step(theta, q):
                qn = P @ q

                def sub(th, _):
                    k1 = f(th, qn)
                    k2 = f(th + 0.5 * h * k1, qn)
                    k3 = f(th + 0.5 * h * k2, qn)
                    k4 = f(th + h * k3, qn)
                    return th + h / 6 * (k1 + 2 * k2 + 2 * k3 + k4), None

                th, _ = jax.lax.scan(sub, theta, None, length=nsub)
                return th
        else:
            raise ValueError(f"unknown method {method!r}")
        return step

    def make_simulator(self, dt: float, method: Optional[str] = None):
        """Return simulate(theta0, q_traj[T,S]) -> obs_temps[T,n_obs]
        (the device part is jitted internally; the closure is vmappable).

        Output is absolute temperature at the chiplet observation points.
        For the matrix-free integrators (be_cg/trap_cg) the per-step CG
        stats accumulate inside the scan and land on the closure as
        ``simulate.last_stats`` (a (T,)-shaped :class:`CGStats`) after
        each concrete call, with a host-side warning if any step's solve
        hit the iteration cap.
        """
        step = self.make_stepper(dt, method)
        step_stats = getattr(step, "with_stats", None)
        H = self.H
        t_amb = self.t_ambient

        @jax.jit
        def simulate_dev(theta0, q_traj):
            def body(theta, q):
                if step_stats is None:
                    th = step(theta, q.astype(theta.dtype))
                    return th, (H @ th, None)
                th, st = step_stats(theta, q.astype(theta.dtype))
                return th, (H @ th, st)

            _, (obs, stats) = jax.lax.scan(body, theta0.astype(self.dtype),
                                           q_traj)
            return obs + t_amb, stats

        def simulate(theta0, q_traj):
            obs, stats = simulate_dev(theta0, q_traj)
            if stats is not None and not isinstance(obs, jax.core.Tracer):
                simulate.last_stats = stats
                warn_unconverged(stats, "rc transient CG")
            return obs

        simulate.last_stats = None
        return simulate

    def simulate_batch(self, theta0, q_traj, dt: float,
                       method: Optional[str] = None) -> jnp.ndarray:
        """Batched rollout: theta0 (B,N), q_traj (T,B,S) -> (T,B,n_obs)."""
        return simulate_batch_via_vmap(self, theta0, q_traj, dt,
                                       method=method or self.default_method)

    def zero_state(self, batch: Optional[int] = None) -> jnp.ndarray:
        shape = (self.net.n,) if batch is None else (batch, self.net.n)
        return jnp.zeros(shape, self.dtype)

    def node_temps(self, theta) -> jnp.ndarray:
        return theta + self.t_ambient

    def layer_heatmap(self, theta, layer_idx: int):
        """(value, extent) pairs for Fig. 10-style heat maps."""
        g = self.net.grid
        idx = np.nonzero(g.layer == layer_idx)[0]
        vals = np.asarray(theta)[idx] + self.t_ambient
        rects = [(g.x0[i], g.y0[i], g.x1[i], g.y1[i]) for i in idx]
        return vals, rects


def _resolve_cap_multipliers(pkg: Package,
                             cap_multipliers: Optional[dict]) -> dict:
    """None -> tuned per-layer defaults for the package's stack (paper
    §4.3 "Capacitance Tuning"; regenerate with scripts/tune_caps.py);
    ``{}`` -> explicitly untuned; any other dict -> used as given."""
    if cap_multipliers is not None:
        return cap_multipliers
    from .calibrate import default_cap_multipliers  # lazy: avoids cycle
    return default_cap_multipliers(pkg)


@register_fidelity("rc")
def build_model(pkg: Package, cap_multipliers: Optional[dict] = None,
                dtype=jnp.float32, method: str = "be_chol",
                solver: str = "dense", cg_tol: Optional[float] = None,
                cg_maxiter: int = 1000, cg_impl: str = "auto",
                refine_rtol: float = 1e-9, refine_passes: int = 4,
                grid: Optional[NodeGrid] = None) -> ThermalRCModel:
    """Registry builder. ``cap_multipliers=None`` applies the tuned
    per-layer defaults for the package's layer stack (override with an
    explicit dict, or pass ``{}`` for the untuned network). ``solver``
    selects the solver tier, ``cg_impl`` how its CG iterations execute
    ("fused" single-launch kernel vs "unfused" escape hatch), and
    ``refine_rtol``/``refine_passes`` the mixed-precision refinement of
    its f32 cg steady solve (``refine_passes=0`` opts out; see
    :class:`ThermalRCModel`)."""
    return ThermalRCModel(
        build_network(pkg, grid=grid,
                      cap_multipliers=_resolve_cap_multipliers(
                          pkg, cap_multipliers)),
        dtype=dtype, method=method, solver=solver, cg_tol=cg_tol,
        cg_maxiter=cg_maxiter, cg_impl=cg_impl, refine_rtol=refine_rtol,
        refine_passes=refine_passes)


# ---------------------------------------------------------------------------
# Batched design-space model: one family, many packages per device call
# ---------------------------------------------------------------------------
def _batched_pcg(matvec, prec, rhs, x0, tol: float, maxiter: int):
    """Masked batched preconditioned CG on SPD systems ``A x = rhs``.

    Back-compat wrapper around :func:`repro.kernels.fused_cg.ops.pcg_loop`
    (the generic callable-matvec loop, which also returns per-row
    :class:`CGStats`); kept because external consumers (``core/rom.py``)
    import the x-only form. ``matvec``/``prec`` map (B, N) -> (B, N);
    batch rows converge independently against a RELATIVE residual ``tol``
    and are frozen (masked updates) while the rest iterate.
    """
    return pcg_loop(matvec, prec, rhs, x0, tol, maxiter)[0]


class RCFamilyModel:
    """Thermal RC model over a :class:`~repro.core.family.PackageFamily`.

    The family's template is assembled once into a fixed symbolic network;
    every method then evaluates a ``(B, P)`` parameter batch as a pure-jax
    numeric phase (``core/assembly.py``) plus a batched solve:

      * ``steady_state_batch`` — batched CG on the SPD steady matrix
        ``-G(p)``. On the default "dense" tier it is preconditioned with
        the Cholesky factor of the TEMPLATE's ``-G(p0)``, factored once
        on the host: each iteration is one shared BLAS-3
        triangular-solve pair over the whole batch plus an O(E) COO
        matvec per candidate — no O(N^3) factorization per candidate,
        which is what makes the batched sweep beat a per-package
        ``build()`` loop by an order of magnitude. On the "cg" tier the
        solve is fully matrix-free: every iteration is ONE fused
        Jacobi-PCG step (``kernels/fused_cg``) over the whole batch.
      * ``simulate_family`` — per-candidate backward Euler. On the
        default "dense" solver tier, one batched Cholesky of
        ``C/dt - G(p)`` amortized over all T steps; on the "cg" tier the
        factorization is never formed — each step is a warm-started
        batched Jacobi-CG on the COO matvec kernel, the large-N path.

    This class expresses only the per-candidate math; BATCH EXECUTION —
    vmap/jit plumbing, mesh sharding of the candidate axis, padding of
    non-divisible B, and chunk streaming of larger-than-memory sweeps
    (with the steady CG warm-started across chunks) — is delegated to a
    :class:`~repro.distribution.family_exec.FamilyExecutor` (PR 5).
    Construct with ``mesh=``/``chunk_size=`` (or a shared ``executor=``,
    as the DSS/ROM rungs do) to select the execution layout.

    Use ``dtype=jnp.float64`` (inside ``repro.runtime.x64()``)
    to validate against a per-candidate ``build()`` loop to <=1e-6 degC.
    """

    fidelity = "rc"

    def __init__(self, family, cap_multipliers: Optional[dict] = None,
                 dtype=jnp.float32, cg_tol: Optional[float] = None,
                 cg_maxiter: int = 150, solver: str = "dense",
                 cg_impl: str = "auto", mesh=None,
                 chunk_size: Optional[int] = None,
                 executor: Optional[FamilyExecutor] = None):
        self.family = family
        self.exec = executor if executor is not None else \
            FamilyExecutor(mesh=mesh, chunk_size=chunk_size)
        self._ns = self.exec.register()  # jit-cache namespace
        self.num = NumericAssembly(
            family.sym, dtype=dtype,
            cap_multipliers=_resolve_cap_multipliers(family.template,
                                                     cap_multipliers))
        self.dtype = dtype
        self.tags = list(family.sym.tags)
        self.source_names = list(family.sym.source_names)
        self.param_names = list(family.param_names)
        # relative-residual targets chosen so the steady-state error stays
        # orders of magnitude under the 1e-6 degC family-vs-loop bar (f64)
        # / the f32 solve class, without over-iterating
        self.cg_tol = cg_tol if cg_tol is not None else \
            (1e-9 if dtype == jnp.float64 else 1e-6)
        self.cg_maxiter = cg_maxiter
        self.solver = resolve_solver(solver, family.sym.n)
        self.cg_impl = resolve_cg_impl(cg_impl)
        self._fused_plan_cache = None
        self._implicit_steady_cache = None
        self.last_cg_stats: Optional[CGStats] = None
        self._cbase = jnp.asarray(family.coord_base, dtype)
        self._cjac = jnp.asarray(family.coord_jac, dtype)
        self._slots = family.scalar_slots
        self._htc_bottom = family.template.htc_bottom
        self.t_ambient = family.template.t_ambient  # template value
        self._chol0_cache = None

    @property
    def _chol0(self) -> jnp.ndarray:
        """Template preconditioner: -G(p0) Cholesky-factored once on the
        host (f64) — lazily, so consumers that never touch the batched
        steady solve (e.g. the ROM family riding only ``reduced_ops``)
        skip the O(N^3) factorization entirely. The CACHE holds the host
        numpy factor: first access usually happens inside a jit trace,
        and caching the device conversion there would leak a tracer into
        later traces (each trace re-embeds the constant instead)."""
        if self._chol0_cache is None:
            net0 = self.family.template_network()
            self._chol0_cache = np.linalg.cholesky(-net0.g_dense())
        return jnp.asarray(self._chol0_cache, self.dtype)

    @property
    def n(self) -> int:
        return self.num.sym.n

    @property
    def _fused_plan(self):
        """Fused-CG plan over the family's FIXED symbolic edge pattern —
        shared by every candidate (the batch rides the kernel's sublane
        axis), built lazily on the first matrix-free solve."""
        if self._fused_plan_cache is None:
            sym = self.num.sym
            self._fused_plan_cache = fused_cg_plan(sym.rows, sym.cols,
                                                   sym.n)
        return self._fused_plan_cache

    # -- traced numeric phase ------------------------------------------------
    def _scalar(self, p, name):
        idx, const = self._slots[name]
        return p[idx] if idx >= 0 else jnp.asarray(const, self.dtype)

    def _network(self, p):
        """One parameter vector -> network value dict (pure jax; vmap me).

        This is the ``params -> (G_coo, C)`` numeric phase: coordinates
        are an affine map of ``p``; values are evaluated over the fixed
        edge pattern.
        """
        coords = self._cbase + jnp.einsum("cnk,k->cn", self._cjac,
                                          p.astype(self.dtype))
        vals = self.num.network(coords, self._scalar(p, "htc_top"),
                                jnp.asarray(self._htc_bottom, self.dtype))
        vals["t_ambient"] = self._scalar(p, "t_ambient")
        vals["power_scale"] = self._scalar(p, "power_scale")
        return vals

    def reduced_ops(self, p, v_basis):
        """Basis-projection hook (the ROM rung, ``core/rom.py``): reduced
        ``(Ghat, Chat, Phat, Hhat, t_ambient, power_scale)`` for ONE
        parameter vector over a fixed (N, r) basis.

        Pure jax and vmappable: ``G(p) V`` is the O(E r) COO segment-sum
        matvec over the basis columns (batch on the kernel's leading
        axis, no dense G), everything else is a GEMM against ``v_basis``.
        """
        v = self._network(p)
        num = self.num
        neg_diag = num.neg_g_diag(v["gvals"], v["gconv"])
        gv_t = coo_matvec(num.plan, v["gvals"], v_basis.T,
                          backend=num.matvec_backend) \
            - neg_diag * v_basis.T            # (r, N) rows = (G v_k)'
        ghat = gv_t @ v_basis
        ghat = 0.5 * (ghat + ghat.T)
        chat = (v_basis.T * v["C"]) @ v_basis
        chat = 0.5 * (chat + chat.T)
        return (ghat, chat, v_basis.T @ v["P"], v["H"] @ v_basis,
                v["t_ambient"], v["power_scale"])

    # -- batched steady state ------------------------------------------------
    @property
    def _pad_param_row(self) -> np.ndarray:
        """Pad element for non-divisible B: the template's own parameter
        vector, so executor padding always evaluates valid geometry."""
        return np.asarray(self.family.base_params())

    def _pcg(self, gvals, gconv, rhs, x0):
        """Batched PCG on (-G(p)) x = rhs -> (x (B, N), CGStats (B,)).

        gvals (B, E_sym), gconv (B, N), rhs (B, N), x0 (B, N). On the
        "cg" tier the whole iteration is one fused CG step
        (``kernels/fused_cg``, Jacobi preconditioner — fully matrix-free,
        no O(N^2) template factor; the cap is raised to cover Jacobi's
        higher iteration count at family tolerances). On the "dense" tier
        the template preconditioner is kept: the Cholesky factor of the
        TEMPLATE's ``-G(p0)``, one BLAS-3 triangular-solve pair over the
        whole batch per iteration — dense-memory-class but far fewer
        iterations. ``x0`` is the warm start the executor threads across
        streamed chunks.
        """
        num = self.num
        diag = num.neg_g_diag(gvals, gconv)  # (B, N), batched natively
        if self.solver == "cg":
            return fused_cg_solve(self._fused_plan, diag, gvals, rhs,
                                  x0=x0, tol=self.cg_tol,
                                  maxiter=max(self.cg_maxiter, 1000),
                                  impl=self.cg_impl,
                                  backend=num.matvec_backend)

        def matvec(x):
            return diag * x - coo_matvec(num.plan, gvals, x,
                                         backend=num.matvec_backend)

        chol0 = self._chol0

        def prec(r):  # one BLAS-3 triangular-solve pair for the batch
            return jax.scipy.linalg.cho_solve((chol0, True), r.T).T

        return pcg_loop(matvec, prec, rhs, x0,
                        self.cg_tol, self.cg_maxiter)

    def steady_state_batch(self, params, q_src) -> jnp.ndarray:
        """params (B, P), q_src (B, S) -> steady theta (B, N).

        Natively batched through the executor: candidates shard over the
        mesh, and chunk-streamed sweeps warm-start each chunk's CG from
        the previous chunk's converged states (placements in one sweep
        are thermally similar, so the carry saves iterations). Per-solve
        convergence stats land on ``self.last_cg_stats`` (a (B,)-shaped
        :class:`CGStats`), with a host-side warning when any candidate's
        solve hit the iteration cap. Spans: ``rc.steady`` around the
        executor's spans and ``rc.check``, that warning's read of the
        stats."""
        def _steady(x0, params, q):
            def net(p):
                v = self._network(p)
                return (v["gvals"], v["gconv"], v["P"], v["power_scale"])

            gvals, gconv, pmat, scale = jax.vmap(net)(
                params.astype(self.dtype))
            rhs = jnp.einsum("bns,bs->bn", pmat,
                             q.astype(self.dtype) * scale[:, None])
            th, stats = self._pcg(gvals, gconv, rhs, x0)
            return (th, stats), th

        with span("rc.steady"):
            th, stats = self.exec.run(
                f"{self._ns}:rc_steady", _steady, (params, q_src),
                in_axes=(0, 0),
                out_axis=0, pad_rows=(self._pad_param_row, None),
                make_carry=lambda b: jnp.zeros((b, self.n), self.dtype))
            if not isinstance(th, jax.core.Tracer):
                self.last_cg_stats = stats
                with span("rc.check"):
                    warn_unconverged(stats, "rc family steady CG")
        return th

    def observe_batch(self, theta, params) -> jnp.ndarray:
        """theta (B, N), params (B, P) -> absolute degC (B, n_obs)."""
        def one(th, p):
            # XLA dead-code-eliminates the unused network values
            v = self._network(p.astype(self.dtype))
            return v["H"] @ th.astype(self.dtype) + v["t_ambient"]

        with span("rc.observe"):
            return self.exec.run(f"{self._ns}:rc_observe", one,
                                 (theta, params),
                                 in_axes=(0, 0), per_candidate=True,
                                 pad_rows=(None, self._pad_param_row))

    @property
    def _implicit_steady(self):
        """Reverse-differentiable matrix-free steady solver (cg tier):
        the ``jax.custom_vjp`` implicit-adjoint wrapper around the fused
        CG kernel (``kernels/fused_cg/adjoint.py``) — forward is the
        unchanged fused ``while_loop``, backward is ONE extra fused CG
        solve of the self-adjoint system. Built lazily per model; stats
        from both directions land on the adjoint registry under the
        sites named here (see ``adjoint.last_stats``/``solve_counts``)."""
        if self._implicit_steady_cache is None:
            self._implicit_steady_cache = make_implicit_steady(
                self._fused_plan, tol=self.cg_tol,
                maxiter=max(self.cg_maxiter, 1000), impl=self.cg_impl,
                backend=self.num.matvec_backend,
                site="rc family peak_steady adjoint CG")
        return self._implicit_steady_cache

    def _steady_obs_one(self, p, qb):
        """ONE candidate's steady observation temps (n_obs,), pure jax
        and reverse-differentiable on BOTH solver tiers. The cg tier
        rides the implicit-adjoint fused solve (matrix-free, no dense
        N x N anywhere in the grad graph); the dense tier factors the
        SPD ``-G`` with a Cholesky solve."""
        v = self._network(p.astype(self.dtype))
        rhs = v["P"] @ (qb.astype(self.dtype) * v["power_scale"])
        if self.solver == "cg":
            diag = self.num.neg_g_diag(v["gvals"], v["gconv"])
            th = self._implicit_steady(diag, v["gvals"], rhs)
        else:
            g = self.num.dense_g(v["gvals"], v["gconv"])
            chol = jnp.linalg.cholesky(-g)
            th = jax.scipy.linalg.cho_solve((chol, True), rhs)
        return v["H"] @ th + v["t_ambient"]

    def _peak_one(self, p, qb, tau):
        """Scalar peak objective for one candidate. ``tau`` None -> the
        true max (gradient follows the argmax observation point);
        otherwise the smooth-max ``tau * logsumexp(obs / tau)`` the
        optimizer anneals (an upper bound on max that -> max as
        tau -> 0)."""
        obs = self._steady_obs_one(p, qb)
        if tau is None:
            return jnp.max(obs)
        return tau * jax.scipy.special.logsumexp(obs / tau)

    def peak_steady(self, params, q_src) -> jnp.ndarray:
        """Differentiable peak steady temperature per candidate (B,).

        ``jax.grad``-able w.r.t. ``params`` end to end on BOTH solver
        tiers: the numeric phase is pure jax, the dense tier solves by
        Cholesky (reverse-differentiable), and the cg tier uses the
        implicit-adjoint fused solve — one extra CG solve per backward
        pass instead of an unrolled ``while_loop``. Executor-routed, so
        candidate batches shard over the mesh like any sweep (for
        chunk-streamed or padded batches take gradients through
        :meth:`peak_steady_and_grad`, whose padding is masked on the
        host — tracing ``jax.grad`` through a chunked ``run()`` would
        hit the host landing). Softmax-free: the true max.
        """
        # q pad rows are ones, not zeros: a zero rhs makes the relative CG
        # residual 0/0 and trips warn_unconverged for rows that are
        # discarded anyway.
        return self.exec.run(
            f"{self._ns}:rc_peak", lambda p, q: self._peak_one(p, q, None),
            (params, q_src), in_axes=(0, 0), per_candidate=True,
            pad_rows=(self._pad_param_row, 1.0))

    def peak_steady_and_grad(self, params, q_src, tau=None):
        """Per-candidate peak objective AND its params-gradient:
        ``params (B, P), q_src (S,) -> (value (B,), grad (B, P))``.

        The multi-start optimizer's inner evaluation (``core/optimize.py``):
        one workload shared across all starts, per-start value/grad rows.
        Routed through the executor's pad-aware value-and-grad mode, so
        start batches mesh-shard and chunk-stream like any sweep while
        pad rows (the template's ``base_params()``) are masked out of the
        result. ``tau`` selects the smooth-max temperature (a traced
        scalar — annealing it does NOT retrace); None = true max."""
        use_tau = tau is not None
        tau_arg = jnp.asarray(1.0 if tau is None else tau, self.dtype)

        def objective(p, q, t):
            return self._peak_one(p, q, t if use_tau else None)

        return self.exec.run_value_and_grad(
            (f"{self._ns}:rc_peak_grad", use_tau), objective,
            (params, q_src, tau_arg), in_axes=(0, None, None),
            pad_rows=(self._pad_param_row, None, None))

    # -- batched transient ---------------------------------------------------
    def simulate_family(self, params, q_traj, dt: float) -> jnp.ndarray:
        """params (B, P), q_traj (T, B, S) -> obs temps (T, B, n_obs).

        Backward Euler from ambient. Solver tier "dense": one batched
        Cholesky of ``C/dt - G(p)`` per candidate, amortized over all T
        steps. Tier "cg": no factorization is ever formed — every step is
        a warm-started batched Jacobi-CG on the COO matvec kernel. Both
        tiers ride the executor (mesh-sharded / chunk-streamed batch).
        """
        if self.solver == "cg":
            return self.exec.run(
                (f"{self._ns}:rc_simulate_cg", float(dt)),
                self._make_simulate_cg(dt),
                (params, q_traj), in_axes=(0, 1), out_axis=1,
                pad_rows=(self._pad_param_row, None))

        def one(p, q_t):  # q_t (T, S)
            v = self._network(p.astype(self.dtype))
            c_dt = v["C"] / dt
            m = jnp.diag(c_dt) - self.num.dense_g(v["gvals"], v["gconv"])
            chol = jnp.linalg.cholesky(m)
            pmat, h = v["P"], v["H"]
            scale = v["power_scale"]

            def body(th, qt):
                rhs = c_dt * th + pmat @ (qt.astype(self.dtype) * scale)
                th = jax.scipy.linalg.cho_solve((chol, True), rhs)
                return th, h @ th

            th0 = jnp.zeros((self.n,), self.dtype)
            _, obs = jax.lax.scan(body, th0, q_t)
            return obs + v["t_ambient"]

        return self.exec.run((f"{self._ns}:rc_simulate", float(dt)), one,
                             (params, q_traj), in_axes=(0, 1), out_axis=1,
                             per_candidate=True,
                             pad_rows=(self._pad_param_row, None))

    def _make_simulate_cg(self, dt: float):
        """Matrix-free family transient: backward Euler where each step
        is one batched Jacobi-CG solve of ``(C/dt - G(p)) th' = rhs``
        executed as fused CG-step launches (``kernels/fused_cg``),
        warm-started from the previous state (params, q_traj as in
        :meth:`simulate_family`). Per-step stats stay inside the scan
        (the executor's time-major output layout has no room for them);
        steady solves are where convergence is observable."""
        num = self.num
        tol, maxiter = self.cg_tol, self.cg_maxiter
        impl, backend = self.cg_impl, num.matvec_backend
        plan_f = self._fused_plan

        def simulate(params, q_traj):
            def net(p):
                v = self._network(p)
                return (v["C"], v["gvals"], v["gconv"], v["P"], v["H"],
                        v["t_ambient"], v["power_scale"])

            c, gvals, gconv, pmat, h, t_amb, scale = jax.vmap(net)(
                params.astype(self.dtype))
            cdt = c / dt
            neg_g_diag = num.neg_g_diag(gvals, gconv)   # (B, N)
            mdiag = cdt + neg_g_diag                    # diag of C/dt - G

            def body(th, qt):  # th (B, N), qt (B, S)
                rhs = cdt * th + jnp.einsum(
                    "bns,bs->bn", pmat,
                    qt.astype(self.dtype) * scale[:, None])
                th, _ = fused_cg_solve(plan_f, mdiag, gvals, rhs, x0=th,
                                       tol=tol, maxiter=maxiter,
                                       impl=impl, backend=backend)
                return th, jnp.einsum("bon,bn->bo", h, th)

            th0 = jnp.zeros((params.shape[0], self.n), self.dtype)
            _, obs = jax.lax.scan(body, th0, q_traj)
            return obs + t_amb[None, :, None]

        return simulate


@register_family_fidelity("rc")
def build_rc_family(family, cap_multipliers: Optional[dict] = None,
                    dtype=jnp.float32, **opts) -> RCFamilyModel:
    """Registry builder. Besides the solver-tier knobs, ``mesh=`` (a
    ``jax.sharding.Mesh`` or an int device count) and ``chunk_size=``
    select the family execution layout (see
    ``distribution/family_exec.py``)."""
    return RCFamilyModel(family, cap_multipliers=cap_multipliers,
                         dtype=dtype, **opts)
