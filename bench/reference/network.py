"""Host float64 reference: the RC network of a package and its answers.

Straight from the paper's equations (section 4.3, Eqs. 4-7 and the
exact zero-order hold of section 4.4), written for clarity and not for
speed: neighbours are found by comparing every pair of nodes within a
layer and between adjacent layers, conductances are series
half-resistances, convection sits on both package faces, and each
source's power spreads over its nodes by area. Steady states come from a
sparse direct solve; transients and DTPM plants from one symmetric
eigendecomposition, stepped in modal coordinates. Nothing here imports
the system under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import scipy.linalg
import scipy.sparse as sp
import scipy.sparse.linalg as spla

from .package import Package, cap_multipliers, discretize

_EPS = 1e-12


@dataclasses.dataclass
class Network:
    c: np.ndarray        # (N,) J/K
    rows: np.ndarray     # (E,) symmetric off-diagonal pattern
    cols: np.ndarray
    g: np.ndarray        # (E,) W/K
    gconv: np.ndarray    # (N,) W/K to ambient
    p: np.ndarray        # (N, S) source -> node power split
    h: np.ndarray        # (n_obs, N) per-tag area-weighted mean
    tags: list
    t_ambient: float

    @property
    def n(self) -> int:
        return int(self.c.size)

    def neg_g(self) -> sp.csc_matrix:
        """-G: off-diagonal -g, diagonal sum(g) + gconv."""
        n = self.n
        off = sp.coo_matrix((self.g, (self.rows, self.cols)), shape=(n, n))
        diag = np.bincount(self.rows, weights=self.g, minlength=n) \
            + self.gconv
        return (sp.diags(diag) - off).tocsc()


def _pairs(mask: np.ndarray):
    i, j = np.nonzero(mask)
    return i, j


def build(pkg: Package) -> Network:
    grid = discretize(pkg)
    n = grid.n
    c = grid.cv * grid.area * grid.lz * cap_multipliers(pkg)[grid.layer]
    rows, cols, gs = [], [], []

    def emit(i, j, g):
        rows.extend([i, j])
        cols.extend([j, i])
        gs.extend([g, g])

    by_layer = [np.nonzero(grid.layer == li)[0]
                for li in range(grid.n_layers)]
    for idx in by_layer:
        x0, x1 = grid.x0[idx], grid.x1[idx]
        y0, y1 = grid.y0[idx], grid.y1[idx]
        upper = np.triu(np.ones((idx.size, idx.size), bool), 1)
        oy = np.minimum(y1[:, None], y1) - np.maximum(y0[:, None], y0)
        ox = np.minimum(x1[:, None], x1) - np.maximum(x0[:, None], x0)
        touch_x = (np.abs(x1[:, None] - x0) < _EPS) \
            | (np.abs(x1 - x0[:, None]) < _EPS)
        touch_y = (np.abs(y1[:, None] - y0) < _EPS) \
            | (np.abs(y1 - y0[:, None]) < _EPS)
        for touch, ov, lo, hi, k in ((touch_x, oy, x0, x1, grid.kx),
                                     (touch_y & ~touch_x, ox, y0, y1,
                                      grid.ky)):
            a, b = _pairs(upper & touch & (ov > _EPS))
            i, j = idx[a], idx[b]
            area = ov[a, b] * grid.lz[i]
            r = 0.5 * (hi[a] - lo[a]) / (k[i] * area) \
                + 0.5 * (hi[b] - lo[b]) / (k[j] * area)
            emit(i, j, 1.0 / r)
    for lo_idx, hi_idx in zip(by_layer[:-1], by_layer[1:]):
        ox = np.minimum(grid.x1[lo_idx][:, None], grid.x1[hi_idx]) \
            - np.maximum(grid.x0[lo_idx][:, None], grid.x0[hi_idx])
        oy = np.minimum(grid.y1[lo_idx][:, None], grid.y1[hi_idx]) \
            - np.maximum(grid.y0[lo_idx][:, None], grid.y0[hi_idx])
        a, b = _pairs((ox > _EPS) & (oy > _EPS))
        i, j = lo_idx[a], hi_idx[b]
        area = ox[a, b] * oy[a, b]
        r = 0.5 * grid.lz[i] / (grid.kz[i] * area) \
            + 0.5 * grid.lz[j] / (grid.kz[j] * area)
        emit(i, j, 1.0 / r)

    gconv = np.zeros(n)
    top = grid.layer == grid.n_layers - 1
    bot = grid.layer == 0
    gconv[top] += pkg.htc_top * grid.area[top]
    gconv[bot] += pkg.htc_bottom * grid.area[bot]

    p = np.zeros((n, len(grid.sources)))
    for s in range(len(grid.sources)):
        nodes = grid.source == s
        p[nodes, s] = grid.area[nodes] / grid.area[nodes].sum()

    tags = sorted({t for t in grid.tags if t})
    tag_arr = np.array(grid.tags)
    h = np.zeros((len(tags), n))
    for k, tag in enumerate(tags):
        nodes = tag_arr == tag
        h[k, nodes] = grid.area[nodes] / grid.area[nodes].sum()

    return Network(c=c, rows=np.concatenate(rows).astype(np.int64),
                   cols=np.concatenate(cols).astype(np.int64),
                   g=np.concatenate(gs), gconv=gconv, p=p, h=h, tags=tags,
                   t_ambient=float(pkg.t_ambient))


def steady_obs(net: Network, q) -> np.ndarray:
    """Observed steady temperatures (degC) for source powers q (S,)."""
    theta = spla.spsolve(net.neg_g(), net.p @ np.asarray(q, np.float64))
    return net.h @ theta + net.t_ambient


class Modal:
    """Exact ZOH of ``C theta' = G theta + P q`` at step ``dt``.

    With ``z = U' C^(1/2) theta`` and ``U diag(w) U'`` the eigensystem of
    the symmetric ``C^(-1/2) G C^(-1/2)``, every mode steps on its own:
    ``z <- e^(w dt) z + (e^(w dt) - 1) / w * U' C^(-1/2) P q``.
    """

    def __init__(self, net: Network, dt: float):
        ci = 1.0 / np.sqrt(net.c)
        g = -net.neg_g().toarray()
        w, u = scipy.linalg.eigh(g * ci[:, None] * ci[None, :])
        if w.max() >= 0.0:
            raise ValueError("network has a non-decaying mode")
        e = np.exp(w * dt)
        self.lam = e
        self.bm = ((e - 1.0) / w)[:, None] * (u.T @ (ci[:, None] * net.p))
        self.hm = (net.h * ci[None, :]) @ u
        self.t_ambient = net.t_ambient

    def rollout(self, q_traj: np.ndarray) -> np.ndarray:
        """q_traj (R, T, S) from rest -> observations (R, T, n_obs) after
        each step."""
        r, t_len, _ = q_traj.shape
        z = np.zeros((r, self.lam.size))
        out = np.empty((r, t_len, self.hm.shape[0]))
        for k in range(t_len):
            z = self.lam * z + q_traj[:, k] @ self.bm.T
            out[:, k] = z @ self.hm.T
        return out + self.t_ambient

    def dtpm_tmax(self, powers: np.ndarray, throttle: np.ndarray,
                  exponent: float) -> np.ndarray:
        """Max observed temperature after each step of the plant driven
        by ``powers * throttle ** exponent`` (R, T, S) x (R, T)."""
        eff = powers * (throttle ** exponent)[..., None]
        return self.rollout(eff).max(axis=2)
