"""Fidelity registry: the common ``ThermalSimulator`` protocol and the
two-level single-package / package-family build API.

MFIT's value proposition (paper Fig. 2) is swapping model fidelities per
design stage — FEM-class reference for validation, thermal RC for design
iteration, DSS for runtime management — over ONE geometry description.
This module makes that swap a string, at two levels:

Level 1 — one concrete package (unchanged API)::

    from repro.core import build
    sim = build(pkg, fidelity="rc")           # or "fvm", "dss", "rom",
                                              # "hotspot", "3dice", "pact"
    theta = sim.steady_state(q)               # fidelity-native state
    temps = sim.observe(theta)                # (n_obs,) absolute degC,
                                              # shared tag ordering
    roll = sim.make_simulator(dt)             # sim(state0, q[T,S]) -> (T,O)
    batch = sim.simulate_batch(th0, q, dt)    # (T,B,S) -> (T,B,O)

Level 2 — a whole design space in one device call (PR 2)::

    from repro.core import PackageFamily, build_family
    fam = PackageFamily(pkg, params=("grid_offsets", "htc_top"))
    sim = build_family(fam, fidelity="rc")    # or "dss", "fvm", "rom"
    theta = sim.steady_state_batch(p, q)      # p (B,P) params, q (B,S)
    temps = sim.observe_batch(theta, p)       # (B, n_obs) absolute degC
    obs = sim.simulate_family(p, q_traj, dt)  # q (T,B,S) -> (T,B,n_obs)

Orthogonal to both axes is the EXECUTION LAYOUT (PR 5): every family
fidelity routes its candidate batch through a
``distribution/family_exec.FamilyExecutor`` and accepts::

    sim = build_family(fam, "rc", mesh=8, chunk_size=512)

``mesh=`` (a ``jax.sharding.Mesh`` or an int device count) shards the
``(B, P)`` axis across the mesh's ``data`` axis via ``shard_map`` —
candidates are independent, so sweeps scale with device count with zero
collectives (non-divisible B is padded with the template candidate and
sliced off). ``chunk_size=`` streams sweeps over fixed-size candidate
chunks, joined on the device when the sweep's output fits a share of
device memory, else landed in host memory chunk by chunk (the RC steady
CG warm-starts each chunk from the previous one). The ``sharded_dse``
section of ``BENCH_exec_time.json`` tracks both.

``build(pkg, fid)`` is the degenerate single-element case of the family
API: a family whose parameter set is empty pins the template, and the
batched simulators at B=1 reproduce ``build`` to solver tolerance (tested
in ``tests/test_family.py``). The single-package path keeps its own
seed-bitwise assembly.

Orthogonal to the fidelity axis is the SOLVER TIER (PR 3): every network
fidelity accepts ``solver="dense" | "cg" | "auto"``::

    sim = build(pkg, "rc", solver="cg")       # fully matrix-free
    sim = build_family(fam, "rc", solver="auto")

``"dense"`` is the prefactored Cholesky / ``expm`` path (exact, O(N^3)
factor, O(N^2) memory — the right call for the paper's few-hundred-node
networks); ``"cg"`` never materializes an N x N matrix: steady states
and implicit transients run preconditioned conjugate gradients whose
matvec is the O(E) COO segment-sum kernel (``kernels/coo_matvec``), the
path that scales to the N >> 1k networks of 64+-chiplet systems.
``"auto"`` picks by node count against the measured dense-vs-CG
crossover (:data:`SOLVER_CROSSOVER_NODES`, tracked by the
``sparse_solver`` section of ``BENCH_exec_time.json``).

Every registered fidelity exposes the same observation-tag ordering
(``sim.tags``, lexicographically sorted), so outputs are directly
comparable across the ladder — the property the accuracy benchmarks and
cross-fidelity tests rely on.

Model modules register themselves via ``@register_fidelity(name)`` (and
``@register_family_fidelity(name)`` for the batched level) at import time;
``build``/``build_family`` import them lazily to avoid import cycles.
Baseline emulations (hotspot/3dice/pact) model per-package external tools
and deliberately have no family builder — ``build_family`` raises
``NotImplementedError`` with the per-package fallback spelled out.
"""
from __future__ import annotations

from typing import (Callable, Dict, List, Optional, Protocol, Tuple,
                    runtime_checkable)


@runtime_checkable
class ThermalSimulator(Protocol):
    """What every fidelity must expose (see module docstring for shapes)."""

    fidelity: str                 # registry name of this model family
    tags: List[str]               # observation tags, shared sorted order
    source_names: List[str]       # power-source order of the q vector

    def zero_state(self, batch=None): ...

    def steady_state(self, q_src): ...          # -> fidelity-native state

    def observe(self, state): ...               # state -> (n_obs,) degC

    def make_simulator(self, dt): ...           # -> sim(state0, q[T, S])

    def simulate_batch(self, theta0, q_traj, dt): ...  # (T,B,S) -> (T,B,O)


@runtime_checkable
class BatchedThermalSimulator(Protocol):
    """What a family fidelity exposes: one fixed topology, a batch of
    parameter vectors riding a device batch axis (see module docstring)."""

    fidelity: str
    tags: List[str]
    source_names: List[str]
    param_names: List[str]        # columns of the params matrix

    def steady_state_batch(self, params, q_src): ...   # (B,P),(B,S) -> state

    def observe_batch(self, state, params): ...        # -> (B, n_obs) degC

    def simulate_family(self, params, q_traj, dt): ...  # (T,B,S) -> (T,B,O)


def simulate_batch_via_vmap(model, state0, q_traj, dt, **opts):
    """Shared batched-rollout helper: vmap ``model.make_simulator`` over
    the batch axis and cache the vmapped callable per ``(dt, opts)``.

    This is THE ``simulate_batch`` implementation for every fidelity whose
    single-trace simulator is a jitted ``sim(state0, q[T,S])`` (thermal RC
    and its baseline emulations, FVM). DSS does not use it — its step is
    natively a batched GEMM (``kernels/dss_step``), so vmap would only add
    overhead. Keeping the cache on the model instance keeps the jit cache
    warm across calls without leaking compiled functions between models.

    state0 (B, *state_shape), q_traj (T, B, S) -> (T, B, n_obs).
    """
    import jax
    cache = model.__dict__.setdefault("_batch_sims", {})
    key = (dt, tuple(sorted(opts.items())))
    if key not in cache:
        cache[key] = jax.vmap(model.make_simulator(dt, **opts),
                              in_axes=(0, 1), out_axes=1)
    return cache[key](state0, q_traj)


def evict_stale_jits(cache: Dict, prefix: str = "simulate",
                     keep: int = 8) -> None:
    """Bound a model's per-dt compiled-function cache (insertion order):
    call before inserting a new ``(prefix, dt)`` key so long-lived
    processes sweeping many sampling periods don't accumulate one XLA
    executable per dt forever (same bound as ``DSSModel._regen_cache``)."""
    keys = [k for k in cache if isinstance(k, tuple) and k[0] == prefix]
    while len(keys) >= keep:
        cache.pop(keys.pop(0))


# Dense-vs-CG steady-solve crossover in NODES, measured by the
# ``sparse_solver`` section of ``benchmarks/exec_time.py`` on this
# container's CPU (which emits a calibration WARNING whenever this
# constant drifts >2x from the fresh measurement — the guard that keeps
# "auto" honest across hardware and solver changes). The fused CG-step
# path (``kernels/fused_cg``, one launch per iteration; PR 6) removed
# the per-iteration dispatch cost that made small systems dense
# territory: refined fused-CG steady now beats the dense Cholesky ~4x
# already at 564 nodes (the smallest Table-6 system, the floor of the
# measured ladder — the true crossover lies somewhere below), ~30x at
# 2.1k and >200x at 8.2k, so ``steady_crossover_nodes`` reports the
# ladder floor. ``solver="auto"`` picks CG at or above this; the dense
# tier below it stays exact, prefactored, and reverse-differentiable.
SOLVER_CROSSOVER_NODES = 564

_SOLVERS = ("dense", "cg", "auto")


def resolve_solver(solver: str, n: int) -> str:
    """Resolve the solver-tier knob to a concrete tier for an N-node
    network: ``"auto"`` -> ``"cg"`` iff ``n >= SOLVER_CROSSOVER_NODES``."""
    if solver not in _SOLVERS:
        raise ValueError(f"unknown solver {solver!r}; expected one of "
                         f"{', '.join(_SOLVERS)}")
    if solver == "auto":
        return "cg" if n >= SOLVER_CROSSOVER_NODES else "dense"
    return solver


# ---------------------------------------------------------------------------
# Content-addressed cache keys (the serving layer's model identity)
# ---------------------------------------------------------------------------
def _canon_opt(value):
    """Canonical token of one build option value.

    Handles everything :func:`~repro.core.geometry.content_token` does,
    plus dtype OBJECTS (``jnp.float32`` / ``np.float32`` / ``np.dtype``),
    which appear as the ``dtype=`` knob on every fidelity builder — all
    object spellings of one dtype map to its ``np.dtype().str``. (A
    dtype passed as a *string* stays a string token: that can only split
    the cache, never falsely merge it.)
    """
    from .geometry import content_token
    import numpy as np
    try:
        return content_token(value)
    except TypeError:
        pass
    try:
        return ("dtype", np.dtype(value).str)
    except TypeError:
        raise TypeError(
            f"cache_key: option value {value!r} has no canonical form "
            f"(callables / model objects cannot address a content cache "
            f"— pass plain knobs and let the builder derive the rest)")


def cache_key(target, fidelity: str, opts: Optional[Dict] = None) -> str:
    """Content-addressed cache key of ``build(target, fidelity, **opts)``
    (or ``build_family`` when ``target`` is a ``PackageFamily``).

    The key is a sha256 over (a) the canonical content token of the
    geometry — every field of the ``Package``/``PackageFamily`` tree,
    bit-exact floats, see ``core/geometry.content_token`` — and (b) the
    fidelity name plus the SORTED build options. Structurally identical
    geometries built with identical knobs therefore collide (cache hit,
    skipping symbolic assembly / COO plans / the ~98 s ROM basis);
    perturbing any geometry field, material property, or solver knob
    yields a different key (no false hits). ``serving/cache.py`` is the
    consumer; tests/test_serving_cache.py pins the property.
    """
    import hashlib
    from .geometry import Package, content_token
    if isinstance(target, Package):
        tok = content_token(target)
    elif hasattr(target, "content_token"):
        tok = target.content_token()
    else:
        raise TypeError(f"cache_key: cannot canonicalize "
                        f"{type(target).__name__}; expected a Package or "
                        f"an object exposing content_token()")
    opt_tok = tuple(sorted((str(k), _canon_opt(v))
                           for k, v in (opts or {}).items()))
    return hashlib.sha256(
        repr(("build", fidelity, tok, opt_tok)).encode()).hexdigest()


_REGISTRY: Dict[str, Callable] = {}
_FAMILY_REGISTRY: Dict[str, Callable] = {}


def register_fidelity(name: str):
    """Decorator: register ``builder(pkg, **opts) -> ThermalSimulator``."""
    def deco(builder: Callable):
        _REGISTRY[name] = builder
        return builder
    return deco


def register_family_fidelity(name: str):
    """Decorator: register ``builder(family, **opts) ->
    BatchedThermalSimulator`` for the batched design-space level."""
    def deco(builder: Callable):
        _FAMILY_REGISTRY[name] = builder
        return builder
    return deco


def _ensure_registered() -> None:
    # Registration happens as an import side effect of each model module.
    from . import (baselines, dss, fvm_ref, rc_model, rom,  # noqa: F401
                   router)


def available_fidelities() -> Tuple[str, ...]:
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def available_family_fidelities() -> Tuple[str, ...]:
    _ensure_registered()
    return tuple(sorted(_FAMILY_REGISTRY))


def build(pkg, fidelity: str = "rc", **opts) -> "ThermalSimulator":
    """Build a thermal simulator for one concrete ``pkg`` at the named
    fidelity (level 1; the single-element case of :func:`build_family`).

    Extra keyword options are forwarded to the fidelity's builder (e.g.
    ``dx_target`` for "fvm", ``cap_multipliers`` for "rc", ``ts`` for
    "dss") on top of its registered defaults.
    """
    _ensure_registered()
    if fidelity not in _REGISTRY:
        raise KeyError(f"unknown fidelity {fidelity!r}; available: "
                       f"{', '.join(sorted(_REGISTRY))}")
    from .geometry import Package, validate_package
    if isinstance(pkg, Package):
        validate_package(pkg)      # precise errors, not a singular solve
    return _REGISTRY[fidelity](pkg, **opts)


def build_family(family, fidelity: str = "rc",
                 **opts) -> "BatchedThermalSimulator":
    """Build a batched design-space simulator for a ``PackageFamily``.

    The family's template is assembled ONCE (symbolic phase); every call
    then evaluates a ``(B, P)`` parameter batch as a device batch axis
    (numeric phase) — no per-candidate host assembly, jit, or dispatch.
    Implemented for "rc", "dss", "fvm" and "rom"; the baseline emulations
    model per-package external tools and raise ``NotImplementedError``.

    All family builders accept the execution-layout knobs ``mesh=`` /
    ``chunk_size=`` (or a shared ``executor=``) — see the module
    docstring and ``distribution/family_exec.py``.
    """
    _ensure_registered()
    if fidelity not in _FAMILY_REGISTRY:
        if fidelity in _REGISTRY:
            raise NotImplementedError(
                f"fidelity {fidelity!r} has no batched family builder "
                f"(it emulates a per-package external tool); available "
                f"family fidelities: {', '.join(sorted(_FAMILY_REGISTRY))}."
                f" Fall back to build(family.instantiate(p), {fidelity!r}) "
                f"in a loop.")
        raise KeyError(f"unknown fidelity {fidelity!r}; available: "
                       f"{', '.join(sorted(_REGISTRY))}")
    from .geometry import Package, validate_package
    template = getattr(family, "template", None)
    if isinstance(template, Package):
        validate_package(template)
    return _FAMILY_REGISTRY[fidelity](family, **opts)
