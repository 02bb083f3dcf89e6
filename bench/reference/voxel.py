"""Host float64 reference of the voxel (finite-volume) fidelity.

The steady conduction equation (MFIT, arXiv:2410.09188, Eq. 1 at steady
state) on a structured voxel grid over the package of ``package.py``,
written for clarity and not for speed; nothing here imports the system
under test. The discretisation:

  * the footprint is cut into ``nx = round(length / dx_target)`` by
    ``ny = round(width / dx_target)`` columns (at least 2 each), each
    layer into ``min(max_slabs, max(1, round(thickness / dz_target)))``
    slabs of equal thickness;
  * a voxel takes its layer's material unless its centre lies in a
    block's half-open footprint ``[x0, x1) x [y0, y1)``, where the block's
    material applies, later blocks of the layer overriding earlier ones;
  * neighbours couple through the harmonic mean of their half-voxel
    conductances (series half-resistances); the top slab convects to
    ambient through ``htc_top``, the bottom slab through ``htc_bottom``;
  * a source's power spreads evenly over the voxels its blocks cover in
    every slab of their layer, and a tag reads the mean of its voxels.

``steady_obs`` assembles the symmetric 7-point operator as a scipy
sparse matrix and solves it with Jacobi-preconditioned CG to a relative
residual of 1e-10, raising unless it converged. ``control_obs`` solves
the same systems in another precision (bfloat16 for the controls) with
JAX, for a fixed number of iterations, since no residual target is
reachable there.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from .package import Package

RTOL = 1e-10


@dataclasses.dataclass
class Voxels:
    gx: np.ndarray          # (nz, ny, nx - 1) W/K
    gy: np.ndarray          # (nz, ny - 1, nx)
    gz: np.ndarray          # (nz - 1, ny, nx)
    conv: np.ndarray        # (nz, ny, nx) W/K to ambient
    src: np.ndarray         # (S, V) power split of each source
    obs: np.ndarray         # (n_obs, V) mean over each tag's voxels
    sources: list
    tags: list
    t_ambient: float

    @property
    def shape(self) -> tuple:
        return self.conv.shape

    def operator(self) -> sp.csr_matrix:
        """The conduction operator ``-L`` (SPD), convection on its
        diagonal."""
        shape = self.shape
        idx = np.arange(int(np.prod(shape))).reshape(shape)
        rows, cols, vals = [], [], []
        diag = self.conv.ravel().copy()
        for g, a, b in ((self.gx, idx[:, :, :-1], idx[:, :, 1:]),
                        (self.gy, idx[:, :-1, :], idx[:, 1:, :]),
                        (self.gz, idx[:-1], idx[1:])):
            a, b, g = a.ravel(), b.ravel(), g.ravel()
            rows += [a, b]
            cols += [b, a]
            vals += [-g, -g]
            np.add.at(diag, a, g)
            np.add.at(diag, b, g)
        n = diag.size
        off = sp.coo_matrix((np.concatenate(vals),
                             (np.concatenate(rows), np.concatenate(cols))),
                            shape=(n, n))
        return (off + sp.diags(diag)).tocsr()


def voxelize(pkg: Package, dx_target: float, dz_target: float,
             max_slabs: int) -> Voxels:
    nx = max(2, int(round(pkg.length / dx_target)))
    ny = max(2, int(round(pkg.width / dx_target)))
    dx, dy = pkg.length / nx, pkg.width / ny
    xc = (np.arange(nx) + 0.5) * dx
    yc = (np.arange(ny) + 0.5) * dy
    slabs = []                                   # (layer, thickness)
    for layer in pkg.layers:
        ns = min(max_slabs, max(1, int(round(layer.thickness / dz_target))))
        slabs += [(layer, layer.thickness / ns)] * ns
    nz = len(slabs)
    kx, ky, kz = (np.empty((nz, ny, nx)) for _ in range(3))
    covered = {}                                 # name -> (nz, ny, nx) bool
    for z, (layer, _) in enumerate(slabs):
        m = layer.material
        kx[z], ky[z], kz[z] = m.kx, m.ky, m.kz
        for b in layer.blocks:
            inside = (((yc >= b.y0) & (yc < b.y1))[:, None]
                      & ((xc >= b.x0) & (xc < b.x1))[None, :])
            kx[z][inside] = b.material.kx
            ky[z][inside] = b.material.ky
            kz[z][inside] = b.material.kz
            for name in {b.power_name, b.tag} - {None, ""}:
                covered.setdefault(name, np.zeros((nz, ny, nx), bool))
                covered[name][z] |= inside
    dz = np.array([t for _, t in slabs])[:, None, None]
    gx = dy * dz / (0.5 * dx / kx[:, :, :-1] + 0.5 * dx / kx[:, :, 1:])
    gy = dx * dz / (0.5 * dy / ky[:, :-1, :] + 0.5 * dy / ky[:, 1:, :])
    gz = dx * dy / (0.5 * dz[:-1] / kz[:-1] + 0.5 * dz[1:] / kz[1:])
    conv = np.zeros((nz, ny, nx))
    conv[-1] += pkg.htc_top * dx * dy
    conv[0] += pkg.htc_bottom * dx * dy

    names = lambda attr: sorted({getattr(b, attr) for layer in pkg.layers
                                 for b in layer.blocks}
                                - {None, ""})

    def weights(keys):
        w = np.stack([covered[k].ravel().astype(np.float64) for k in keys]) \
            if keys else np.zeros((0, nz * ny * nx))
        return w / np.maximum(w.sum(axis=1, keepdims=True), 1.0)

    sources, tags = names("power_name"), names("tag")
    return Voxels(gx=gx, gy=gy, gz=gz, conv=conv, src=weights(sources),
                  obs=weights(tags), sources=sources, tags=tags,
                  t_ambient=float(pkg.t_ambient))


def solve(vox: Voxels, q) -> np.ndarray:
    """Steady rise (V,) for source powers ``q`` (S,), float64 CG."""
    a = vox.operator()
    b = vox.src.T @ np.asarray(q, np.float64)
    m = sp.diags(1.0 / a.diagonal())
    x, info = spla.cg(a, b, rtol=RTOL, maxiter=20 * b.size, M=m)
    if info != 0:
        raise RuntimeError(f"reference CG did not converge (info={info})")
    res = np.linalg.norm(b - a @ x) / np.linalg.norm(b)
    if res > 10 * RTOL:
        raise RuntimeError(f"reference CG residual {res:.2e} over {RTOL}")
    return x


def steady_obs(vox: Voxels, q) -> np.ndarray:
    """Observed steady temperatures (n_obs,) in degC, in ``vox.tags``
    order."""
    return vox.obs @ solve(vox, q) + vox.t_ambient


def control_obs(voxes, qs, dtype, iters: int = 1000) -> np.ndarray:
    """Observed steady temperatures (R, n_obs) of grids of one shape for
    source powers ``qs`` (R, S), each solved by Jacobi PCG with every
    operand, vector and product in ``dtype``."""
    import jax
    import jax.numpy as jnp

    def stack(get):
        return jnp.asarray(np.stack([get(v) for v in voxes]), dtype)

    gx, gy, gz = stack(lambda v: v.gx), stack(lambda v: v.gy), \
        stack(lambda v: v.gz)
    conv = stack(lambda v: v.conv)
    b = jnp.asarray(np.stack([
        (v.src.T @ np.asarray(q, np.float64)).reshape(v.shape)
        for v, q in zip(voxes, qs)]), dtype)

    def pad(a, axis, lo, hi):
        width = [(0, 0)] * a.ndim
        width[axis] = (lo, hi)
        return jnp.pad(a, width)

    def apply(x):
        out = conv * x
        for axis, g, f in ((-1, gx, x[..., 1:] - x[..., :-1]),
                           (-2, gy, x[..., 1:, :] - x[..., :-1, :]),
                           (-3, gz, x[..., 1:, :, :] - x[..., :-1, :, :])):
            out = out + pad(g * f, axis, 1, 0) - pad(g * f, axis, 0, 1)
        return out

    diag = conv
    for axis, g in ((-1, gx), (-2, gy), (-3, gz)):
        diag = diag + pad(g, axis, 1, 0) + pad(g, axis, 0, 1)

    def dot(u, v):
        return jnp.sum(u * v, axis=(1, 2, 3), keepdims=True)

    def body(_, s):
        x, r, p, rz = s
        ap = apply(p)
        pap = dot(p, ap)
        alpha = jnp.where(pap > 0, rz / jnp.where(pap > 0, pap, 1), 0)
        x, r = x + alpha * p, r - alpha * ap
        z = r / diag
        rz_new = dot(r, z)
        beta = jnp.where(rz > 0, rz_new / jnp.where(rz > 0, rz, 1), 0)
        return x, r, z + beta * p, rz_new

    @jax.jit
    def run(b):
        z = b / diag
        x, *_ = jax.lax.fori_loop(0, iters, body,
                                  (jnp.zeros_like(b), b, z, dot(b, z)))
        return x

    x = np.asarray(run(b), np.float64).reshape(len(voxes), -1)
    obs = np.stack([v.obs @ xi for v, xi in zip(voxes, x)])
    return obs + np.array([v.t_ambient for v in voxes])[:, None]
