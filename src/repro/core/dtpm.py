"""Dynamic thermal & power management on top of the DSS-class models
(paper §1, §4.4: "DSS models ... enabling runtime thermal management").

The ThermalManager embeds a millisecond-class state-space model in the
training / serving loop: each step it advances the thermal state from the
measured chip powers, PREDICTS the next-step temperature, and adjusts a
DVFS-style throttle to keep the package under the violation threshold
(85 C per paper §5.4). Fully jittable — the controller adds two small
GEMVs per step.

The manager consumes the ``(ad, bd, H, t_ambient, n)`` surface shared by
the full-order :class:`~repro.core.dss.DSSModel` and the reduced-order
:class:`~repro.core.rom.ROMModel`, so ``from_package(pkg,
fidelity="rom")`` runs the same controller on the ROM rung: per-step cost
r x r instead of N x N — the large-package serving configuration.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..runtime import span
from .dss import DSSModel


class DTPMState(NamedTuple):
    theta: jnp.ndarray       # (N,) thermal state
    throttle: jnp.ndarray    # scalar in (0, 1]
    violations: jnp.ndarray  # int32 counter


@dataclasses.dataclass
class ThermalManager:
    dss: DSSModel             # any (ad, bd, H) state-space rung: dss | rom
    t_max: float = 85.0       # violation threshold (paper §5.4)
    t_target: float = 80.0    # control setpoint
    down: float = 0.88        # multiplicative backoff on predicted violation
    up: float = 1.03          # recovery rate
    min_throttle: float = 0.3

    @classmethod
    def from_package(cls, pkg, ts: float = 0.01, build_opts: dict = None,
                     fidelity: str = "dss", **control) -> "ThermalManager":
        """Build the controller's state-space model through the fidelity
        registry. ``fidelity`` picks the rung: "dss" (full order, exact
        ZOH of the RC network) or "rom" (Krylov reduced order — per-step
        cost independent of node count, the right call for big packages).

        ``build_opts`` go to ``fidelity.build(pkg, fidelity, ts=ts,
        ...)``; remaining keywords are controller parameters (t_max,
        t_target, ...).
        """
        from .fidelity import build
        if fidelity not in ("dss", "rom"):
            raise ValueError(
                f"ThermalManager needs a state-space rung ('dss' or "
                f"'rom'), got fidelity={fidelity!r}")
        mdl = build(pkg, fidelity, **{"ts": ts, **(build_opts or {})})
        return cls(dss=mdl, **control)

    def init_state(self) -> DTPMState:
        # the state rides the model's dtype: an f64-built rung (the
        # oracle's x64 serving mode) must not see an f32 carry in the
        # scan, and f32 rungs stay f32
        dtype = self.dss.ad.dtype
        return DTPMState(theta=jnp.zeros((self.dss.n,), dtype),
                         throttle=jnp.ones((), dtype),
                         violations=jnp.zeros((), jnp.int32))

    def update(self, state: DTPMState, chip_powers: jnp.ndarray):
        """One control step. chip_powers (S,) watts at full speed.

        Returns (new_state, info dict with temps/throttle/violation).
        """
        dss = self.dss
        p_eff = chip_powers * state.throttle ** 2.5
        theta = dss.ad @ state.theta + dss.bd @ p_eff
        temps = dss.H @ theta + dss.t_ambient
        t_now = jnp.max(temps)
        # one-step-ahead prediction at current power (ZOH)
        theta_pred = dss.ad @ theta + dss.bd @ p_eff
        t_pred = jnp.max(dss.H @ theta_pred + dss.t_ambient)
        hot = t_pred > self.t_target
        new_throttle = jnp.where(hot, state.throttle * self.down,
                                 jnp.minimum(1.0, state.throttle * self.up))
        new_throttle = jnp.maximum(new_throttle, self.min_throttle)
        violated = (t_now > self.t_max).astype(jnp.int32)
        new_state = DTPMState(theta=theta, throttle=new_throttle,
                              violations=state.violations + violated)
        info = {"temps": temps, "t_max": t_now, "t_pred": t_pred,
                "throttle": state.throttle, "violation": violated}
        return new_state, info

    def should_checkpoint(self, state: DTPMState,
                          sustained: int = 50) -> bool:
        """Pre-emptive checkpoint trigger: sustained violations mean the
        package cannot be held under t_max even at min throttle — the
        host should snapshot before a thermal trip (DESIGN.md §3)."""
        return bool(state.violations >= sustained)

    def run(self, powers_traj: jnp.ndarray):
        """Roll the controller over a (T, S) power trace (jitted scan).

        The jitted closure is cached on the manager, KEYED on the
        controller parameters and the model's operator arrays (they are
        baked into the executable as compile-time constants), so
        repeated runs over same-shaped traces reuse one XLA executable
        while mutating t_max/t_target/..., swapping the model, or a
        model regeneration still take effect. The cache holds STRONG
        references to the keyed objects, so identity comparison cannot
        be fooled by garbage-collected id reuse. The launch is the span
        ``dtpm.dispatch``, with ``first=1`` when this call built the
        closure (its launch compiles).
        """
        key = (self.t_max, self.t_target, self.down, self.up,
               self.min_throttle, self.dss.t_ambient)
        refs = (self.dss, self.dss.ad, self.dss.bd, self.dss.H)
        cached = getattr(self, "_run_cache", None)
        built = cached is None or cached[0] != key or \
            any(a is not b for a, b in zip(cached[1], refs))
        if built:
            @jax.jit
            def dtpm_scan(traj):
                def body(st, p):
                    st, info = self.update(st, p)
                    return st, (info["t_max"], info["throttle"])

                st, (tmax, thr) = jax.lax.scan(body, self.init_state(),
                                               traj)
                return st, tmax, thr

            self._run_cache = (key, refs, dtpm_scan)
        with span("dtpm.dispatch", first=int(built)):
            return self._run_cache[2](powers_traj)

    def serve_trace(self, powers_traj):
        """Answer one serving request: ``(t_max_trace, telemetry)``.

        The per-request form of :meth:`run` for the thermal oracle
        (``serving/oracle.py``): rolls the controller over the trace and
        reduces the result into the structured telemetry dict that rides
        back on the response's ``info`` field — peak/final max
        temperature, violation count, throttle behaviour, remaining
        headroom to ``t_max``, and the pre-emptive checkpoint
        recommendation. Host numpy out (serving responses are consumed
        on client threads, not inside jit). Spans: ``dtpm.serve`` around
        ``dtpm.dispatch`` (in :meth:`run`) and ``dtpm.land``, the reads
        of the scan's outputs.
        """
        with span("dtpm.serve"):
            state, tmax, thr = self.run(powers_traj)
            with span("dtpm.land"):
                tmax = np.asarray(tmax)
                thr = np.asarray(thr)
                telemetry = {
                    "t_max_peak": float(tmax.max()),
                    "t_max_final": float(tmax[-1]),
                    "violations": int(state.violations),
                    "min_throttle": float(thr.min()),
                    "mean_throttle": float(thr.mean()),
                    "headroom_c": float(self.t_max - tmax.max()),
                    "throttle_traj": thr,
                    "checkpoint_recommended": self.should_checkpoint(state),
                }
        return tmax, telemetry
