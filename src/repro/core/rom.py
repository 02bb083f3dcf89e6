"""ROM fidelity rung: Krylov moment-matching projection of the RC network.

The fastest transient paths in the ladder still scale with node count —
per-step cost of the dense BE tier is O(N^2) (triangular solves) and the
matrix-free CG tier pays O(E * iters) per step. This module adds the
standard escape hatch of the thermal-simulation literature (3D-ICE 4.0's
MOR mode, PRIMA-class RC macromodels): project the continuous-time LTI
system

    C theta_dot = G theta + P q,    y = H theta + t_amb

onto an r-dimensional block-Krylov subspace spanning the first ``m`` block
moments of the transfer function around s = 0,

    span{ (-G)^-1 P, [(-G)^-1 C] (-G)^-1 P, ..., [(-G)^-1 C]^(m-1) (-G)^-1 P },

giving the reduced system ``(Ghat, Chat, Phat, Hhat) = (V' G V, V' C V,
V' P, H V)`` whose steady solves and exact-ZOH transient steps are dense
r x r operations — INDEPENDENT of the node count N. Because G is
symmetric negative definite and C diagonal positive, the congruence
projection preserves definiteness for any full-rank V (PRIMA's
stability/passivity argument); the basis is additionally C-orthonormalized
(``V' C V = I`` up to roundoff), which keeps the block Arnoldi recursion
well conditioned and makes the reduced pencil symmetric.

Basis construction is a one-time cost per package. On the ``"dense"``
solver tier the inner solves ``(-G)^-1 B`` reuse one host Cholesky
factorization; on the ``"cg"`` tier (``"auto"`` above the measured
crossover) they reuse one host sparse LU of the O(E) COO operator — G
is never materialized even at 8k+ nodes. The reduced system is then
sampled with the SAME exact-ZOH discretization as the full-order DSS rung
(:func:`~repro.core.dss.zoh_discretize`, fed the r x r pencil), so
``build(pkg, "rom")`` exposes the full ``ThermalSimulator`` protocol and
drops into every DSS consumer, including the runtime
:class:`~repro.core.dtpm.ThermalManager`.

Accuracy knob: ``n_moments`` (default 6: <=0.03 degC max observation
error vs the full DSS on the Table-6 WL1 traces, ~0.04 at 5, ~0.12 at 4)
or an explicit dimension ``r`` that truncates the dominant-ordered basis.
Each block moment adds up to S columns (S = number of sources), so the
default lands at r = 6 S << N.

Batched design spaces: :class:`ROMFamilyModel` (``build_family(fam,
"rom")``) builds ONE basis from the family's template and evaluates the
reduced ``params -> (Ghat, Chat, Phat, Hhat)`` projection inside the
traced numeric phase (the ``reduced_ops`` basis-projection hook of
``RCFamilyModel``), turning the family transient's per-candidate CG
iterations into batched r x r GEMMs.
"""
from __future__ import annotations

import collections
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.fused_cg.ops import all_finite, record_fallback
from ..runtime import span
from ..testing import faults
from .dss import family_zoh_simulate, zoh_discretize
from .fidelity import (evict_stale_jits, register_family_fidelity,
                       register_fidelity, resolve_solver)
from .geometry import NodeGrid, Package
from .rc_model import (RCFamilyModel, RCNetwork,
                       _resolve_cap_multipliers, build_network,
                       observation_matrix)

# default number of block moments matched around s=0; calibrated against
# the full DSS on the Table-6 WL1 traces (see module docstring)
DEFAULT_MOMENTS = 6

# relative C-norm drop tolerance for deflating (near-)dependent block
# columns during orthonormalization
_DROP_TOL = 1e-8


def _make_neg_g_solver(net: RCNetwork, solver: str, shift: float = 0.0):
    """Block solver ``B (N, k) -> (shift*C - G)^-1 B`` in float64 (host
    in/out); ``shift=0`` is the plain ``(-G)^-1`` of the single-point
    Krylov recursion and of every steady-state consumer.

    ``shift > 0`` keeps the operator SPD (-G is SPD, C positive
    diagonal), so both tiers apply unchanged: a positive shift only ADDS
    to the diagonal. This is the solve behind the rational multi-point
    Krylov option (expansion at s = shift) and the error certifier's
    dual solves.

    "dense": one host Cholesky of (shift*C - G), reused for every block.
    "cg": one host sparse LU (``scipy.sparse.linalg.splu``) of the O(E)
    COO operator, reused for every block — the dense G is never formed.
    Host f64 math stays on the host: nothing here touches the device.
    """
    shift = float(shift)
    if shift < 0.0:
        raise ValueError(f"shift must be >= 0 (SPD operator), got {shift}")
    if solver == "dense":
        import scipy.linalg as sla
        a = -net.g_dense()
        if shift:
            a[np.diag_indices_from(a)] += shift * net.C
        cho = sla.cho_factor(a)
        return lambda b: sla.cho_solve(cho, b)

    from scipy.sparse.linalg import splu
    lu = splu(net.neg_g_sparse(shift))
    dense_fallback: list = []    # lazily built guardrail solver

    def solve_block(b):
        res = faults.corrupt("rom.basis_solve",
                             lu.solve(np.asarray(b, np.float64)))
        if not np.isfinite(res).all():
            # numerical guardrail: a poisoned sparse solve must not leak
            # into the Krylov basis — promote this block to the dense
            # Cholesky tier (built once, reused for later blocks)
            record_fallback("rom.basis_solve")
            if not dense_fallback:
                dense_fallback.append(
                    _make_neg_g_solver(net, "dense", shift=shift))
            res = dense_fallback[0](b)
        return res

    return solve_block


def krylov_basis(net: RCNetwork, r: Optional[int] = None,
                 n_moments=DEFAULT_MOMENTS, solver: str = "auto",
                 drop_tol: float = _DROP_TOL,
                 shifts: tuple = (0.0,)) -> np.ndarray:
    """C-orthonormal block-Krylov basis V (N, r) matching block moments
    of ``H (sC - G)^-1 P`` around the expansion points ``shifts``
    (PRIMA-style, host float64; default single-point s = 0).

    Block Arnoldi with full reorthogonalization: each block is
    C-orthogonalized against the accepted basis (twice), then
    rank-revealed in the C inner product (eigendecomposition of its
    C-Gram matrix) so dependent directions deflate and the kept columns
    are ordered by dominance — an explicit ``r`` truncates to the leading
    directions and keeps generating moments until ``r`` columns exist (or
    the recursion deflates to nothing). ``r=None`` keeps every
    independent column of ``n_moments`` blocks, i.e. r <= n_moments * S.

    ``shifts`` is the rational multi-point option: ``(0.0, s1, ...)``
    runs one recursion per expansion point with the SPD solve
    ``(s_j C - G)^-1``, all orthogonalizing against the ONE shared
    basis, in order. ``n_moments`` may be a matching tuple giving each
    point its own block count (a scalar splits near-evenly); an explicit
    ``r`` is a single shared column cap consumed in shift order, so the
    trailing point's block is dominance-truncated to whatever budget
    remains. Front-loading moments at DC and spending the last few
    columns on one block at a shift near the fast end of the spectrum
    (``s ~ 1/dt``) covers the transfer function with fewer total columns
    than piling all moments at s = 0: e.g. ``n_moments=(5, 1),
    shifts=(0.0, 100.0), r=84`` certifies tighter transient error than
    the default single-point 6S basis — the knob that cuts r below 6S
    at equal certified error (pinned by ``tests/test_rom.py``; the
    adaptive router exposes it as ``rom_opts={"shifts": ...,
    "n_moments": ...}``).

    ``solver`` is the solver-tier knob for the inner block solves
    (resolved against the node count as everywhere else).
    """
    n = net.n
    solver = resolve_solver(solver, n)
    shifts = tuple(float(s) for s in shifts)
    if not shifts:
        raise ValueError("shifts must name at least one expansion point")
    c_diag = np.asarray(net.C, np.float64)
    r_cap = n if r is None else min(int(r), n)
    if r is not None and r_cap < 1:
        raise ValueError(f"r must be >= 1, got {r}")

    n_shifts = len(shifts)
    if isinstance(n_moments, (tuple, list)):
        if len(n_moments) != n_shifts:
            raise ValueError(
                f"n_moments tuple length {len(n_moments)} != "
                f"{n_shifts} shifts")
        moments = tuple(int(m) for m in n_moments)
    else:
        m_base, m_rem = divmod(int(n_moments), n_shifts)
        moments = tuple(m_base + (1 if j < m_rem else 0)
                        for j in range(n_shifts))
    v_basis = np.zeros((n, 0))
    for j, s in enumerate(shifts):
        m_j = moments[j]
        if m_j == 0 or v_basis.shape[1] >= r_cap:
            continue
        solve_block = _make_neg_g_solver(net, solver, shift=s)
        # single-shift explicit r keeps generating moments until the
        # budget fills; with several points each spends exactly its
        # moment count so later shifts see the leftover budget
        max_blocks = m_j if (r is None or n_shifts > 1) else max(m_j, n)
        block = solve_block(np.asarray(net.P, np.float64))
        for blk in range(max_blocks):
            # deflation reference: the block's PRE-orthogonalization
            # column C-norms — once the recursion exhausts the reachable
            # subspace, the orthogonalized residual is pure roundoff
            # relative to THIS scale (judging against the residual's own
            # largest eigenvalue would keep amplified noise columns and
            # break C-orthonormality)
            col_sq = np.einsum("ij,ij->j", block,
                               c_diag[:, None] * block)
            scale_pre = float(col_sq.max()) if col_sq.size else 0.0
            if scale_pre <= 0.0:
                break                        # empty block (no sources)
            for _ in range(2):  # MGS reorthogonalization vs the basis
                if v_basis.shape[1]:
                    block = block - v_basis @ (
                        v_basis.T @ (c_diag[:, None] * block))
            gram = block.T @ (c_diag[:, None] * block)
            gram = 0.5 * (gram + gram.T)
            w, u = np.linalg.eigh(gram)
            w, u = w[::-1], u[:, ::-1]      # dominant directions first
            keep = w > scale_pre * drop_tol ** 2
            if not keep.any():
                break                        # block fully deflated
            new = block @ (u[:, keep] / np.sqrt(w[keep]))
            new = new[:, :r_cap - v_basis.shape[1]]
            v_basis = np.hstack([v_basis, new])
            if v_basis.shape[1] >= r_cap or blk == max_blocks - 1:
                break                        # don't pay an unused solve
            block = solve_block(c_diag[:, None] * new)
    if v_basis.shape[1] == 0:
        raise ValueError("Krylov recursion produced an empty basis "
                         "(no sources?)")
    return v_basis


def project_network(net: RCNetwork, v_basis: np.ndarray,
                    tags: Optional[list] = None):
    """Reduced operators ``(Ghat, Chat, Phat, Hhat)`` for one network
    over a fixed basis (host float64, matrix-free in G: the product
    ``G V`` is an O(E r) COO accumulation, never a dense N x N matrix).
    """
    v64 = np.asarray(v_basis, np.float64)
    gv = -net.neg_g_matvec(v64)        # G V, O(E r), no dense G
    ghat = v64.T @ gv
    ghat = 0.5 * (ghat + ghat.T)             # V' G V of symmetric G
    chat = v64.T @ (net.C[:, None] * v64)
    chat = 0.5 * (chat + chat.T)
    phat = v64.T @ net.P
    hhat = observation_matrix(net, tags) @ v64
    return ghat, chat, phat, hhat


class ROMModel:
    """Reduced-order thermal model: the ``"rom"`` rung of the ladder.

    Holds the reduced ``(Ghat, Chat, Phat, Hhat)`` system (host float64)
    and the exact-ZOH discrete step ``(ad, bd)`` at the built sampling
    period (:func:`~repro.core.dss.zoh_discretize` of the r x r reduced
    pencil) — one r x r GEMM per transient sample, independent of the
    node count. Rollouts are dtype-faithful jitted scans (the reduced
    GEMVs are too small to benefit from the f32 ``dss_step`` kernel, and
    staying in the requested dtype keeps the f64 validation path exact);
    regeneration at another dt is an r x r ``expm`` — microseconds. The
    model exposes ``ad``/``bd``/``H``/``t_ambient``/``n`` so it drops
    into every DSS consumer (notably the runtime ``ThermalManager``).

    State is the reduced coordinate vector ``theta_hat (r,)``;
    ``expand(theta_hat)`` recovers the full N-node theta for heat maps or
    debugging.
    """

    fidelity = "rom"

    def __init__(self, net: RCNetwork, v_basis: np.ndarray,
                 ts: float = 0.01, dtype=jnp.float32):
        import scipy.linalg as sla
        if v_basis.ndim != 2 or v_basis.shape[0] != net.n:
            raise ValueError(f"basis must be (N={net.n}, r), got "
                             f"{v_basis.shape}")
        self.net = net
        self.V = np.asarray(v_basis, np.float64)
        self.dtype = dtype
        self.ts = ts
        self.tags = sorted({t for t in net.grid.tags if t})
        self.source_names = list(net.grid.source_names)
        self.t_ambient = net.t_ambient
        self.ghat, self.chat, self.phat, self.hhat = \
            project_network(net, self.V, self.tags)
        # reduced continuous-time pencil, kept (host f64, r x r) for
        # regeneration at any sampling period
        self._a = np.linalg.solve(self.chat, self.ghat)
        self._b = np.linalg.solve(self.chat, self.phat)
        self.H = jnp.asarray(self.hhat, dtype)
        self._zoh_cache: "collections.OrderedDict" = \
            collections.OrderedDict()
        self.ad, self.bd = self._zoh(ts)
        self._cho = sla.cho_factor(-self.ghat)
        self._cho_solve = sla.cho_solve
        self._jits: dict = {}
        # numerical guardrail state: the most recent solve's structured
        # fallback record (None = answered on the primary path), and the
        # lazily built dense full-order reference solver behind it
        self.last_fallback: Optional[dict] = None
        self._ref_solve = None

    # -- dimensions ---------------------------------------------------------
    @property
    def r(self) -> int:
        return int(self.V.shape[1])

    @property
    def n(self) -> int:
        """State dimension (r) — the DSS-consumer contract."""
        return self.r

    @property
    def n_full(self) -> int:
        """Node count of the projected network."""
        return int(self.net.n)

    @property
    def reduction_ratio(self) -> float:
        return self.n_full / self.r

    # -- ZOH regeneration ----------------------------------------------------
    # per-dt (ad, bd) cache bound, mirroring the executor's dt-keyed jit
    # cache policy (``fidelity.evict_stale_jits`` keep=8): a DTPM
    # controller sweeping sampling periods must not accumulate one pair
    # per dt forever
    _ZOH_CACHE_CAP = 8

    def _zoh(self, dt: float):
        """(ad, bd) at sampling period dt — LRU-bounded cache, r x r
        expm to miss. True LRU (hits refresh recency), not FIFO: a DTPM
        loop that keeps returning to its base period must not see that
        hot pair evicted by a sweep of one-shot dts. Regeneration is
        deterministic (host f64 ``zoh_discretize`` of the fixed reduced
        pencil), so an evicted entry comes back bitwise-identical."""
        key = round(float(dt), 12)
        hit = self._zoh_cache.get(key)
        if hit is not None:
            self._zoh_cache.move_to_end(key)
            return hit
        while len(self._zoh_cache) >= self._ZOH_CACHE_CAP:
            self._zoh_cache.popitem(last=False)
        ad, bd = zoh_discretize(self._a, self._b, dt)
        pair = (jnp.asarray(ad, self.dtype), jnp.asarray(bd, self.dtype))
        self._zoh_cache[key] = pair
        return pair

    # -- ThermalSimulator protocol ------------------------------------------
    def zero_state(self, batch: Optional[int] = None) -> jnp.ndarray:
        shape = (self.r,) if batch is None else (batch, self.r)
        return jnp.zeros(shape, self.dtype)

    def steady_state(self, q_src) -> jnp.ndarray:
        """Reduced steady state: solve ``-Ghat theta_hat = Phat q`` with
        the prefactored r x r Cholesky (host float64).

        Numerical guardrail: a NaN/Inf solve output is never returned —
        it promotes to the dense full-order reference solve
        ``(-G)^-1 P q`` (lazily factored once), C-projected back onto
        the basis, with the structured record in ``last_fallback``
        (surfaced by the serving layer as the response's ``fallback``).
        """
        q = np.asarray(q_src, np.float64)
        rhs = self.phat @ q
        th = faults.corrupt(
            "rom.steady",
            np.asarray(self._cho_solve(self._cho, rhs), np.float64))
        self.last_fallback = None
        if not np.isfinite(th).all():
            record_fallback("rom.steady")
            x_full = self._reference_steady(q)
            # V'C x is the C-orthogonal projection (V'CV = I), so the
            # observed answer is the reference path's, up to the ROM's
            # own (certified-class) projection error
            th = self.V.T @ (self.net.C * x_full)
            self.last_fallback = {
                "site": "rom.steady",
                "to": "dense full-order steady solve",
                "reason": "non-finite reduced solve output"}
        return jnp.asarray(th, self.dtype)

    def _reference_steady(self, q: np.ndarray) -> np.ndarray:
        """Guardrail reference: full-order ``(-G)^-1 P q`` on the dense
        Cholesky tier (host f64, factored once per model)."""
        if self._ref_solve is None:
            self._ref_solve = _make_neg_g_solver(self.net, "dense")
        return self._ref_solve(self.net.P @ q)

    def observe(self, theta_hat) -> jnp.ndarray:
        """Absolute temperature at the observation tags (self.tags order)."""
        return self.H @ theta_hat + self.t_ambient

    def make_simulator(self, dt: Optional[float] = None):
        """Jitted ``simulate(theta_hat0, q_traj[T,S]) -> (T, n_obs)``; a
        ``dt`` other than the built ``ts`` regenerates the r x r ZOH from
        the reduced continuous-time system (microseconds)."""
        dt = self.ts if dt is None else float(dt)
        key = ("simulate", round(dt, 12))
        if key not in self._jits:
            evict_stale_jits(self._jits)
            ad, bd = self._zoh(dt)
            h, t_amb, dtype = self.H, self.t_ambient, self.dtype

            @jax.jit
            def simulate(theta0, q_traj):
                def body(th, qt):
                    th = ad @ th + bd @ qt.astype(th.dtype)
                    return th, h @ th

                _, obs = jax.lax.scan(body, theta0.astype(dtype), q_traj)
                return obs + t_amb

            self._jits[key] = simulate
        return self._jits[key]

    def simulate_batch(self, theta0, q_traj,
                       dt: Optional[float] = None) -> jnp.ndarray:
        """Batched rollout: theta0 (B, r), q_traj (T, B, S) ->
        (T, B, n_obs) — one fused r x r GEMM per step for the batch.
        Spans: ``rom.dispatch`` (``first=1`` when this call built the
        closure) and ``rom.land``, the guard's read of the output."""
        dt = self.ts if dt is None else float(dt)
        key = ("simulate_batch", round(dt, 12))
        first = key not in self._jits
        if first:
            evict_stale_jits(self._jits, prefix="simulate_batch")
            ad, bd = self._zoh(dt)
            h, t_amb, dtype = self.H, self.t_ambient, self.dtype

            @jax.jit
            def rom_rollout(theta0, q_traj):
                def body(th, qt):  # th (B, r), qt (B, S)
                    th = th @ ad.T + qt.astype(th.dtype) @ bd.T
                    return th, th @ h.T

                _, obs = jax.lax.scan(body, theta0.astype(dtype), q_traj)
                return obs + t_amb

            self._jits[key] = rom_rollout
        with span("rom.dispatch", first=int(first)):
            out = self._jits[key](theta0, q_traj)
        self.last_fallback = None
        with span("rom.land"):
            finite = all_finite(faults.corrupt("rom.transient", out))
        if not finite:
            # numerical guardrail: a poisoned/overflowed rollout (e.g.
            # f32 on a stiff pencil) promotes to the host-f64 exact-ZOH
            # reference rollout of the same reduced pencil
            record_fallback("rom.transient")
            out = self._host_rollout(theta0, q_traj, dt)
            self.last_fallback = {
                "site": "rom.transient",
                "to": "host-f64 exact-ZOH rollout",
                "reason": "non-finite batched rollout output"}
        return out

    def _host_rollout(self, theta0, q_traj, dt: float) -> np.ndarray:
        """Guardrail reference rollout: host-f64 exact ZOH of the
        reduced pencil, (B, r) x (T, B, S) -> (T, B, n_obs)."""
        ad, bd = zoh_discretize(self._a, self._b, dt)
        th = np.asarray(theta0, np.float64)
        q = np.asarray(q_traj, np.float64)
        obs = np.empty((q.shape[0], th.shape[0], self.hhat.shape[0]))
        for k in range(q.shape[0]):
            th = th @ ad.T + q[k] @ bd.T
            obs[k] = th @ self.hhat.T
        return obs + self.t_ambient

    # -- full-state recovery ------------------------------------------------
    def expand(self, theta_hat) -> np.ndarray:
        """Lift a reduced state back to the N-node theta (host f64)."""
        return self.V @ np.asarray(theta_hat, np.float64)


@register_fidelity("rom")
def build_rom(pkg: Package, r: Optional[int] = None,
              n_moments=DEFAULT_MOMENTS, ts: float = 0.01,
              solver: str = "auto", dtype=jnp.float32,
              cap_multipliers: Optional[dict] = None,
              basis: Optional[np.ndarray] = None,
              shifts: tuple = (0.0,),
              grid: Optional[NodeGrid] = None) -> ROMModel:
    """Registry builder: package -> RC network -> Krylov basis -> ROM.

    ``r`` / ``n_moments`` are the accuracy knobs (see module docstring);
    ``shifts`` selects rational multi-point expansion (see
    :func:`krylov_basis`); ``solver`` picks the tier for the one-time
    basis solves ("auto" resolves against the node count, so 8k+-node
    packages build the basis matrix-free). ``basis`` injects a
    precomputed (N, r) basis — the hook the family path and
    cross-validation tests use to share one basis across candidates.
    """
    net = build_network(pkg, grid=grid,
                        cap_multipliers=_resolve_cap_multipliers(
                            pkg, cap_multipliers))
    if basis is None:
        basis = krylov_basis(net, r=r, n_moments=n_moments, solver=solver,
                             shifts=shifts)
    return ROMModel(net, basis, ts=ts, dtype=dtype)


# ---------------------------------------------------------------------------
# Batched design-space model: one template basis, many reduced systems
# ---------------------------------------------------------------------------
class ROMFamilyModel:
    """ROM over a ``PackageFamily``: ONE template Krylov basis, a traced
    reduced assembly per candidate.

    The basis is built once from the family's template network (the same
    matrix-free construction as the single-package path); every batched
    call then evaluates ``params -> (Ghat, Chat, Phat, Hhat)`` through
    ``RCFamilyModel.reduced_ops`` — an O(E r) COO projection inside the
    traced numeric phase — and solves/steps in the reduced space. The
    family transient is an exact ZOH per candidate (vmapped r x r expm,
    amortized over all steps) whose rollout is batched r x r GEMMs: no
    per-candidate CG iteration, no N x N factorization. Batch execution
    (mesh sharding / chunk streaming, PR 5) rides the embedded RC
    family's :class:`~repro.distribution.family_exec.FamilyExecutor` —
    pass ``mesh=``/``chunk_size=`` through ``build_family``.
    """

    fidelity = "rom"

    def __init__(self, family, r: Optional[int] = None,
                 n_moments=DEFAULT_MOMENTS, ts: float = 0.01,
                 cap_multipliers: Optional[dict] = None,
                 dtype=jnp.float32, basis: Optional[np.ndarray] = None,
                 solver: str = "auto", shifts: tuple = (0.0,),
                 **rc_opts):
        self.rcf = RCFamilyModel(family, cap_multipliers=cap_multipliers,
                                 dtype=dtype, **rc_opts)
        self.family = family
        self.ts = ts
        self.dtype = dtype
        self.tags = self.rcf.tags
        self.source_names = self.rcf.source_names
        self.param_names = self.rcf.param_names
        if basis is None:
            net0 = family.template_network(
                _resolve_cap_multipliers(family.template, cap_multipliers))
            basis = krylov_basis(net0, r=r, n_moments=n_moments,
                                 solver=solver, shifts=shifts)
        self.V = np.asarray(basis, np.float64)
        self._vd = jnp.asarray(self.V, dtype)

    @property
    def r(self) -> int:
        return int(self.V.shape[1])

    @property
    def n_full(self) -> int:
        return self.rcf.n

    def _reduced(self, p):
        """Traced per-candidate reduced system (vmap me)."""
        return self.rcf.reduced_ops(p, self._vd)

    def _discretize_one(self, p, dt: float):
        """Exact ZOH of ONE candidate's reduced pencil (vmap me): the
        r x r ``expm`` + solves, pure jax and reverse-differentiable —
        shared by :meth:`simulate_family` and the transient-peak
        gradient objective."""
        ghat, chat, phat, hhat, t_amb, scale = self._reduced(
            p.astype(self.dtype))
        a = jnp.linalg.solve(chat, ghat)
        ad = jax.scipy.linalg.expm(a * dt)
        eye = jnp.eye(a.shape[0], dtype=a.dtype)
        bd = jnp.linalg.solve(a, ad - eye) \
            @ jnp.linalg.solve(chat, phat)
        return ad, bd, hhat, t_amb, scale

    def _peak_transient_one(self, p, q_t, tau, dt: float):
        """Scalar transient-peak objective for one candidate: the max
        observation temperature over a whole ZOH rollout of ``q_t``
        (T, S). The r x r scan is reverse-differentiable end to end (no
        CG in the graph), which is what makes WHOLE power traces
        optimizable on the ROM rung. ``tau`` None -> true max over
        (T, n_obs); else the annealable smooth-max."""
        ad, bd, hhat, t_amb, scale = self._discretize_one(p, dt)

        def body(th, qt):
            th = ad @ th + bd @ (qt.astype(self.dtype) * scale)
            return th, hhat @ th

        th0 = jnp.zeros((self.r,), self.dtype)
        _, obs = jax.lax.scan(body, th0, q_t.astype(self.dtype))
        obs = obs + t_amb
        if tau is None:
            return jnp.max(obs)
        return tau * jax.scipy.special.logsumexp(obs.ravel() / tau)

    def peak_transient(self, params, q_traj,
                       dt: Optional[float] = None) -> jnp.ndarray:
        """params (B, P), q_traj (T, S) shared trace -> true peak
        transient temperature per candidate (B,). Executor-routed."""
        dt = self.ts if dt is None else float(dt)
        return self.rcf.exec.run(
            (f"{self.rcf._ns}:rom_peak", round(dt, 12)),
            lambda p, q: self._peak_transient_one(p, q, None, dt),
            (params, q_traj), in_axes=(0, None), per_candidate=True,
            pad_rows=(self.rcf._pad_param_row, None))

    def peak_transient_and_grad(self, params, q_traj,
                                dt: Optional[float] = None, tau=None):
        """Per-candidate transient-peak objective and params-gradient:
        ``params (B, P), q_traj (T, S) -> (value (B,), grad (B, P))``.

        The ROM-rung transient leg of the multi-start optimizer
        (``core/optimize.py``): each backward pass reverse-scans the
        r x r rollout (node-count independent), so optimizing a whole
        WL trace costs reduced-order work only. Routed through the
        executor's pad-aware value-and-grad mode like the steady leg;
        ``tau`` is a traced smooth-max temperature (annealing does not
        retrace), None = true max."""
        dt = self.ts if dt is None else float(dt)
        use_tau = tau is not None
        tau_arg = jnp.asarray(1.0 if tau is None else tau, self.dtype)

        def objective(p, q, t):
            return self._peak_transient_one(p, q, t if use_tau else None,
                                            dt)

        return self.rcf.exec.run_value_and_grad(
            (f"{self.rcf._ns}:rom_peak_grad", round(dt, 12), use_tau),
            objective, (params, q_traj, tau_arg), in_axes=(0, None, None),
            pad_rows=(self.rcf._pad_param_row, None, None))

    def steady_state_batch(self, params, q_src) -> jnp.ndarray:
        """params (B, P), q_src (B, S) -> reduced steady states (B, r).

        Natively batched (one r x r solve per candidate); the embedded
        RC family's executor shards/streams the candidate axis."""
        def _steady(params, q):
            ghat, _, phat, _, _, scale = jax.vmap(self._reduced)(
                params.astype(self.dtype))
            rhs = jnp.einsum("brs,bs->br", phat,
                             q.astype(self.dtype) * scale[:, None])
            return jnp.linalg.solve(-ghat, rhs[..., None])[..., 0]

        return self.rcf.exec.run(
            f"{self.rcf._ns}:rom_steady", _steady, (params, q_src),
            in_axes=(0, 0),
            out_axis=0, pad_rows=(self.rcf._pad_param_row, None))

    def observe_batch(self, theta_hat, params) -> jnp.ndarray:
        """theta_hat (B, r), params (B, P) -> absolute degC (B, n_obs)."""
        def one(th, p):
            # XLA dead-code-eliminates the unused reduced blocks
            _, _, _, hhat, t_amb, _ = self._reduced(p.astype(self.dtype))
            return hhat @ th.astype(self.dtype) + t_amb

        return self.rcf.exec.run(
            f"{self.rcf._ns}:rom_observe", one, (theta_hat, params),
            in_axes=(0, 0),
            per_candidate=True, pad_rows=(None, self.rcf._pad_param_row))

    def simulate_family(self, params, q_traj,
                        dt: Optional[float] = None) -> jnp.ndarray:
        """params (B, P), q_traj (T, B, S) -> obs temps (T, B, n_obs).

        Exact ZOH per candidate: one vmapped r x r ``expm`` amortized
        over all T steps, then batched r x r GEMMs per step — sharded
        and chunk-streamed by the shared family executor.
        """
        dt = self.ts if dt is None else float(dt)

        def discretize_one(p):
            return self._discretize_one(p, dt)

        return self.rcf.exec.run(
            # namespaced per family stack; dt-rounded like the _zoh cache
            (f"{self.rcf._ns}:rom_simulate", round(dt, 12)),
            family_zoh_simulate(discretize_one, self.r, self.dtype),
            (params, q_traj), in_axes=(0, 1), out_axis=1,
            pad_rows=(self.rcf._pad_param_row, None))


@register_family_fidelity("rom")
def build_rom_family(family, r: Optional[int] = None,
                     n_moments: int = DEFAULT_MOMENTS, ts: float = 0.01,
                     cap_multipliers=None, dtype=jnp.float32,
                     **opts) -> ROMFamilyModel:
    return ROMFamilyModel(family, r=r, n_moments=n_moments, ts=ts,
                          cap_multipliers=cap_multipliers, dtype=dtype,
                          **opts)
