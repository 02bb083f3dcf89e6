"""The MFIT Table-6 packages and their node grids, for the reference.

A copy of the package description the system under test builds
(materials, layer stacks, chiplet placement, the per-layer capacitance
multipliers and the paper's section 4.3 slicing into nodes), kept with
the benchmark so that the reference never imports the program. A
configuration names its package by ``preset`` ("2p5d_N" or "3d_SxT");
``candidate`` applies a family parameter vector (chiplet column/row
offsets and the top heat-transfer coefficient) to it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Material:
    name: str
    kx: float
    ky: float
    kz: float
    rho: float
    cp: float

    @property
    def cv(self) -> float:
        return self.rho * self.cp


def _iso(name, k, rho, cp):
    return Material(name, k, k, k, rho, cp)


SILICON = _iso("silicon", 148.0, 2330.0, 712.0)
COPPER = _iso("copper", 400.0, 8960.0, 385.0)
SUBSTRATE = Material("substrate", 15.0, 15.0, 0.8, 1850.0, 1100.0)
C4_LAYER = Material("c4_layer", 0.9, 0.9, 2.8, 4200.0, 480.0)
UBUMP_LAYER = Material("ubump_layer", 1.1, 1.1, 3.4, 4600.0, 460.0)
TIM = _iso("tim", 4.0, 2300.0, 900.0)
MOLD = _iso("mold", 0.85, 1970.0, 880.0)
INTERPOSER = _iso("interposer", 142.0, 2330.0, 712.0)
H_PASSIVE = 12.0          # W/m^2K, natural convection under the substrate

# per-layer capacitance multipliers, keyed by layer-name prefix
CAP_MULTS = {
    "2p5d": {"substrate": 0.8758, "c4": 1.0057, "interposer": 0.9581,
             "ubump": 1.1323, "chiplets": 1.1414, "tim": 1.0945,
             "lid": 0.9450},
    "3d": {"substrate": 0.9032, "c4": 1.0408, "interposer": 0.9740,
           "ubump": 1.1578, "chiplets": 1.0498, "tim": 1.1319,
           "lid": 0.6555},
}


@dataclasses.dataclass(frozen=True)
class Block:
    x0: float
    y0: float
    x1: float
    y1: float
    material: Material
    nx: int = 1
    ny: int = 1
    power_name: Optional[str] = None
    tag: str = ""


@dataclasses.dataclass(frozen=True)
class Layer:
    name: str
    thickness: float
    material: Material
    nx: int = 4
    ny: int = 4
    blocks: tuple = ()


@dataclasses.dataclass(frozen=True)
class Package:
    name: str
    length: float
    width: float
    layers: tuple
    htc_top: float
    htc_bottom: float
    t_ambient: float = 25.0


def _heatsink_htc(lid: float) -> float:
    """Equivalent HTC of a forced-air copper fin sink referred to the lid
    area (paper Eq. 3), the sink scaled to twice the lid footprint."""
    base = max(0.03, 2.0 * lid)
    n_fins = int(round(base / 2.5e-3))
    h_avg, fin_h, fin_t, fin_k = 60.0, 0.015, 0.0008, 400.0
    m = math.sqrt(2.0 * h_avg / (fin_k * fin_t))
    eta = math.tanh(m * fin_h) / (m * fin_h)
    a_f = 2.0 * fin_h * base
    a_t = base * base - n_fins * fin_t * base + n_fins * a_f
    eff = a_t * (1.0 - n_fins * a_f * (1.0 - eta) / a_t)
    return h_avg * eff / (lid * lid)


_T = {"substrate": 0.40e-3, "c4": 0.07e-3, "interposer": 0.10e-3,
      "ubump": 0.03e-3, "chiplet": 0.095e-3, "tim": 0.06e-3,
      "lid": 1.10e-3}
CHIPLET_SIDE = 1.5e-3


def _chiplets(n_side: int, side: float, tier: str = "") -> list:
    pitch = side / n_side
    h = CHIPLET_SIDE / 2.0
    out = []
    for ci in range(n_side * n_side):
        i, j = divmod(ci, n_side)
        cx, cy = (i + 0.5) * pitch, (j + 0.5) * pitch
        tag = f"chiplet{tier}_{ci}"
        out.append(Block(cx - h, cy - h, cx + h, cy + h, SILICON, 2, 2,
                         power_name=tag, tag=tag))
    return out


def _funnel(chiplets, material) -> tuple:
    return tuple(dataclasses.replace(b, material=material, power_name=None,
                                     tag="") for b in chiplets)


def make_package(preset: str) -> Package:
    """The Table-6 system named ``"2p5d_N"`` or ``"3d_SxT"``."""
    if preset.startswith("3d"):
        stacks, tiers = map(int, preset[3:].split("x"))
        n_side = int(round(math.sqrt(stacks)))
        side = 15.5e-3
        c0 = _chiplets(n_side, side)
        layers = [Layer("substrate", _T["substrate"], SUBSTRATE, n_side,
                        n_side),
                  Layer("c4", _T["c4"], C4_LAYER, n_side, n_side),
                  Layer("interposer", _T["interposer"], INTERPOSER, n_side,
                        n_side, _funnel(c0, INTERPOSER))]
        for t in range(tiers):
            layers.append(Layer(f"ubump_t{t}", _T["ubump"], UBUMP_LAYER,
                                n_side, n_side, _funnel(c0, UBUMP_LAYER)))
            layers.append(Layer(f"chiplets_t{t}", _T["chiplet"], MOLD,
                                n_side, n_side,
                                tuple(_chiplets(n_side, side, f"_t{t}"))))
        layers += [Layer("tim", _T["tim"], TIM, n_side, n_side,
                         _funnel(c0, TIM)),
                   Layer("lid", _T["lid"], COPPER, n_side, n_side)]
        return Package(preset, side, side, tuple(layers),
                       _heatsink_htc(side), H_PASSIVE, 25.0)
    n = int(preset.split("_")[1])
    n_side = int(round(math.sqrt(n)))
    side = {16: 15.5e-3, 36: 21.5e-3, 64: 27.5e-3}.get(
        n, n_side * (15.5e-3 / 4))
    ch = _chiplets(n_side, side)
    layers = (
        Layer("substrate", _T["substrate"], SUBSTRATE, n_side, n_side),
        Layer("c4", _T["c4"], C4_LAYER, n_side, n_side),
        Layer("interposer", _T["interposer"], INTERPOSER, n_side, n_side,
              _funnel(ch, INTERPOSER)),
        Layer("ubump", _T["ubump"], UBUMP_LAYER, n_side, n_side,
              _funnel(ch, UBUMP_LAYER)),
        Layer("chiplets", _T["chiplet"], MOLD, n_side, n_side, tuple(ch)),
        Layer("tim", _T["tim"], TIM, n_side, n_side, _funnel(ch, TIM)),
        Layer("lid", _T["lid"], COPPER, n_side, n_side),
    )
    return Package(preset, side, side, layers, _heatsink_htc(side),
                   H_PASSIVE, 25.0)


def cap_multipliers(pkg: Package) -> np.ndarray:
    """Per-layer capacitance multiplier (1 where the stack has none)."""
    table = CAP_MULTS["3d" if pkg.name.startswith("3d") else "2p5d"]
    out = np.ones(len(pkg.layers))
    for li, layer in enumerate(pkg.layers):
        for prefix, m in table.items():
            if layer.name.startswith(prefix):
                out[li] = m
    return out


# ---------------------------------------------------------------------------
# candidates of a family over (grid_offsets, htc_top)
# ---------------------------------------------------------------------------
def _key(b: Block) -> tuple:
    return tuple(round(v, 12) for v in (b.x0, b.y0, b.x1, b.y1))


def site_grid(pkg: Package):
    """Chiplet footprints -> (column, row) by the rank of their centres."""
    feet = {_key(b) for layer in pkg.layers for b in layer.blocks
            if b.tag or b.power_name}
    xs = sorted({round(0.5 * (k[0] + k[2]), 12) for k in feet})
    ys = sorted({round(0.5 * (k[1] + k[3]), 12) for k in feet})
    return ({k: (xs.index(round(0.5 * (k[0] + k[2]), 12)),
                 ys.index(round(0.5 * (k[1] + k[3]), 12))) for k in feet},
            len(xs), len(ys))


def param_names(pkg: Package) -> list:
    """Layout of a (grid_offsets, htc_top) parameter vector."""
    _, n_cols, n_rows = site_grid(pkg)
    return ([f"grid_dx:{k}" for k in range(n_cols)]
            + [f"grid_dy:{k}" for k in range(n_rows)] + ["htc_top"])


def candidate(pkg: Package, params) -> Package:
    """The package with every chiplet footprint moved by its column's dx
    and its row's dy, and the top HTC set from the parameter vector."""
    params = np.asarray(params, np.float64)
    sites, n_cols, n_rows = site_grid(pkg)
    dx, dy = params[:n_cols], params[n_cols:n_cols + n_rows]
    layers = []
    for layer in pkg.layers:
        blocks = []
        for b in layer.blocks:
            cr = sites.get(_key(b))
            if cr is not None:
                sx, sy = float(dx[cr[0]]), float(dy[cr[1]])
                b = dataclasses.replace(b, x0=b.x0 + sx, x1=b.x1 + sx,
                                        y0=b.y0 + sy, y1=b.y1 + sy)
            blocks.append(b)
        layers.append(dataclasses.replace(layer, blocks=tuple(blocks)))
    return dataclasses.replace(pkg, layers=tuple(layers),
                               htc_top=float(params[n_cols + n_rows]))


# ---------------------------------------------------------------------------
# slicing into nodes (paper section 4.3)
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Grid:
    x0: np.ndarray
    x1: np.ndarray
    y0: np.ndarray
    y1: np.ndarray
    lz: np.ndarray
    layer: np.ndarray
    kx: np.ndarray
    ky: np.ndarray
    kz: np.ndarray
    cv: np.ndarray
    source: np.ndarray      # index into ``sources``, -1 for none
    sources: list
    tags: list
    n_layers: int

    @property
    def n(self) -> int:
        return int(self.x0.size)

    @property
    def area(self) -> np.ndarray:
        return (self.x1 - self.x0) * (self.y1 - self.y0)


def _cells(xs, ys):
    nx, ny = len(xs) - 1, len(ys) - 1
    return (np.repeat(xs[:-1], ny), np.repeat(xs[1:], ny),
            np.tile(ys[:-1], nx), np.tile(ys[1:], nx))


def _segments(layer: Layer, L: float, W: float, eps: float = 1e-12):
    if not layer.blocks:
        return [(*_cells(np.linspace(0, L, layer.nx + 1),
                         np.linspace(0, W, layer.ny + 1)),
                 layer.material, None, "")]
    segs = [(*_cells(np.linspace(b.x0, b.x1, b.nx + 1),
                     np.linspace(b.y0, b.y1, b.ny + 1)),
             b.material, b.power_name, b.tag) for b in layer.blocks]
    xc = np.unique([0.0, L] + [c for b in layer.blocks for c in (b.x0, b.x1)])
    yc = np.unique([0.0, W] + [c for b in layer.blocks for c in (b.y0, b.y1)])
    cx = 0.5 * (xc[:-1] + xc[1:])[:, None]
    cy = 0.5 * (yc[:-1] + yc[1:])[None, :]
    inside = np.zeros((len(xc) - 1, len(yc) - 1), bool)
    for b in layer.blocks:
        inside |= ((b.x0 - eps <= cx) & (cx <= b.x1 + eps)
                   & (b.y0 - eps <= cy) & (cy <= b.y1 + eps))
    keep = (~inside & (np.diff(xc)[:, None] > eps)
            & (np.diff(yc)[None, :] > eps)).ravel()
    x0, x1, y0, y1 = _cells(xc, yc)
    segs.append((x0[keep], x1[keep], y0[keep], y1[keep], layer.material,
                 None, ""))
    return segs


def discretize(pkg: Package) -> Grid:
    cols = {k: [] for k in ("x0", "x1", "y0", "y1", "lz", "layer", "kx",
                            "ky", "kz", "cv")}
    names, tags = [], []
    for li, layer in enumerate(pkg.layers):
        for x0, x1, y0, y1, m, pname, tag in _segments(
                layer, pkg.length, pkg.width):
            cnt = len(x0)
            for k, v in (("x0", x0), ("x1", x1), ("y0", y0), ("y1", y1)):
                cols[k].append(np.asarray(v, np.float64))
            for k, v in (("lz", layer.thickness), ("layer", li),
                         ("kx", m.kx), ("ky", m.ky), ("kz", m.kz),
                         ("cv", m.cv)):
                cols[k].append(np.full(cnt, v))
            names += [pname] * cnt
            tags += [tag] * cnt
    sources = sorted({p for p in names if p is not None})
    sidx = {s: i for i, s in enumerate(sources)}
    cat = {k: np.concatenate(v) for k, v in cols.items()}
    return Grid(cat["x0"], cat["x1"], cat["y0"], cat["y1"], cat["lz"],
                cat["layer"].astype(np.int32), cat["kx"], cat["ky"],
                cat["kz"], cat["cv"],
                np.array([sidx.get(p, -1) for p in names], np.int32),
                sources, tags, len(pkg.layers))
