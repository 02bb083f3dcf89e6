"""The reference put in the program's place at a lower precision.

The controls of the cells, computed with JAX in ``dtype`` (bfloat16 for
a float32 configuration) on whatever device holds them:

  * steady states: Jacobi-preconditioned CG on each candidate's own
    network (its operands, vectors and products all in ``dtype``), for
    a fixed ``iters`` iterations, since no residual target is reachable
    in bfloat16;
  * transients: the exact zero-order hold of
    :class:`~bench.reference.network.Modal`, stepped in ``dtype``. DTPM
    requests are replayed under the throttle sequence the program
    chose, so only the precision of the plant differs from the float64
    replay.
"""
from __future__ import annotations

import numpy as np


def rollout(modal, q_traj: np.ndarray, dtype) -> np.ndarray:
    """(R, T, S) powers from rest -> (R, T, n_obs) degC, in ``dtype``."""
    import jax
    import jax.numpy as jnp
    lam = jnp.asarray(modal.lam, dtype)
    bm_t = jnp.asarray(modal.bm.T, dtype)
    hm_t = jnp.asarray(modal.hm.T, dtype)

    def step(z, q):
        z = lam * z + q @ bm_t
        return z, z @ hm_t

    q = jnp.asarray(np.swapaxes(q_traj, 0, 1), dtype)
    z0 = jnp.zeros((q_traj.shape[0], lam.size), dtype)
    _, out = jax.lax.scan(step, z0, q)
    return np.swapaxes(np.asarray(out, np.float64), 0, 1) + modal.t_ambient


def dtpm_tmax(modal, powers, throttle, exponent: float, dtype) -> np.ndarray:
    eff = powers * (throttle ** exponent)[..., None]
    return rollout(modal, eff, dtype).max(axis=2)


def steady_obs(nets, qs, dtype, iters: int = 400) -> np.ndarray:
    """Observed steady temperatures (R, n_obs) of networks sharing one
    edge pattern, for source powers ``qs`` (R, S), solved in ``dtype``."""
    import jax
    import jax.numpy as jnp
    rows, cols = nets[0].rows, nets[0].cols
    if any(not (np.array_equal(n.rows, rows) and np.array_equal(n.cols, cols))
           for n in nets):
        raise ValueError("networks do not share one edge pattern")
    n = nets[0].n
    g = jnp.asarray(np.stack([net.g for net in nets]), dtype)
    diag = jnp.asarray(np.stack([
        np.bincount(rows, weights=net.g, minlength=n) + net.gconv
        for net in nets]), dtype)
    b = jnp.asarray(np.stack([net.p @ q for net, q in zip(nets, qs)]), dtype)
    h = jnp.asarray(np.stack([net.h for net in nets]), dtype)

    def solve(g, diag, b):
        def mv(x):
            return diag * x - jax.ops.segment_sum(g * x[cols], rows, n)

        def body(_, s):
            x, r, p, rz = s
            ap = mv(p)
            pap = jnp.dot(p, ap)
            alpha = jnp.where(pap > 0, rz / jnp.where(pap > 0, pap, 1), 0)
            x = x + alpha * p
            r = r - alpha * ap
            z = r / diag
            rz_new = jnp.dot(r, z)
            beta = jnp.where(rz > 0, rz_new / jnp.where(rz > 0, rz, 1), 0)
            return x, r, z + beta * p, rz_new

        z = b / diag
        x, *_ = jax.lax.fori_loop(0, iters, body,
                                  (jnp.zeros_like(b), b, z, jnp.dot(b, z)))
        return x

    x = jax.jit(jax.vmap(solve))(g, diag, b)
    obs = jnp.einsum("rkn,rn->rk", h, x)
    return np.asarray(obs, np.float64) + np.array(
        [net.t_ambient for net in nets])[:, None]
