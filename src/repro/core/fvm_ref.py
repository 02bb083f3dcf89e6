"""Finite-volume conduction solver — the golden reference model.

Stands in for the paper's ANSYS Fluent FEM reference (DESIGN.md §2): solves
the same governing PDE (paper Eq. 1)

    div(k grad T) + qdot = rho Cv dT/dt

on a structured voxel grid with harmonic-mean face conductances, per-voxel
anisotropic conductivity, volumetric sources, and convection on both package
boundaries. Implicit backward Euler; each step solved matrix-free with
Jacobi-preconditioned CG under lax.scan — fully jitted. Every solve, steady
or transient, single package or family, is the masked batched loop
``kernels/fused_cg/ops.pcg_loop`` on (B, V) rows, so each row reports its
iterations and whether it met the tolerance (``CGStats``).

Two operating points:
  * "abstracted FEM"   — mm-scale voxels over the full package (the
                         accuracy reference for RC/DSS validation);
  * "fine-grained FEM" — um-scale voxels resolving individual u-bumps on a
                         sub-block (benchmarks/abstraction.py), used to fit
                         homogenized layer conductivities via paper Eq. 2.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distribution.family_exec import FamilyExecutor
from ..kernels.fused_cg.ops import CGStats, pcg_loop, warn_unconverged
from ..runtime import span
from .fidelity import (register_family_fidelity, register_fidelity,
                       simulate_batch_via_vmap)
from .geometry import Package


@dataclasses.dataclass
class VoxelModel:
    # geometry
    dx: float
    dy: float
    dz: np.ndarray            # (nz,) slab thicknesses
    layer_of_slab: np.ndarray  # (nz,) package layer index per slab
    # fields (nz, ny, nx)
    cvol: jnp.ndarray         # heat capacity per voxel J/K
    gx: jnp.ndarray           # (nz, ny, nx-1) face conductances W/K
    gy: jnp.ndarray           # (nz, ny-1, nx)
    gz: jnp.ndarray           # (nz-1, ny, nx)
    conv: jnp.ndarray         # (nz, ny, nx) boundary convection W/K
    src: jnp.ndarray          # (S, nz, ny, nx) power distribution (sums to 1)
    obs: jnp.ndarray          # (n_obs, nz, ny, nx) observation weights
    obs_tags: list
    t_ambient: float
    source_names: list = dataclasses.field(default_factory=list)

    @property
    def shape(self):
        return self.cvol.shape

    @property
    def n_vox(self) -> int:
        return int(np.prod(self.cvol.shape))


def voxelize(pkg: Package, dx_target: float = 0.5e-3,
             dz_target: float = 0.15e-3, max_slabs: int = 6) -> VoxelModel:
    nx = max(2, int(round(pkg.length / dx_target)))
    ny = max(2, int(round(pkg.width / dx_target)))
    dx = pkg.length / nx
    dy = pkg.width / ny
    xc = (np.arange(nx) + 0.5) * dx
    yc = (np.arange(ny) + 0.5) * dy

    dz_list, layer_of_slab = [], []
    for li, layer in enumerate(pkg.layers):
        ns = min(max_slabs, max(1, int(round(layer.thickness / dz_target))))
        dz_list += [layer.thickness / ns] * ns
        layer_of_slab += [li] * ns
    dz = np.array(dz_list)
    nz = len(dz)

    kx = np.zeros((nz, ny, nx))
    ky = np.zeros((nz, ny, nx))
    kz = np.zeros((nz, ny, nx))
    cv = np.zeros((nz, ny, nx))
    src_of = {}
    XX, YY = np.meshgrid(xc, yc, indexing="xy")  # (ny, nx) with [y, x]

    for z in range(nz):
        layer = pkg.layers[layer_of_slab[z]]
        m = layer.material
        kx[z], ky[z], kz[z], cv[z] = m.kx, m.ky, m.kz, m.cv
        for b in layer.blocks:
            mask = (XX >= b.x0) & (XX < b.x1) & (YY >= b.y0) & (YY < b.y1)
            kx[z][mask], ky[z][mask], kz[z][mask] = (b.material.kx,
                                                     b.material.ky,
                                                     b.material.kz)
            cv[z][mask] = b.material.cv
            if b.power_name is not None:
                src_of.setdefault(b.power_name, []).append((z, mask))

    source_names = sorted(src_of)
    S = len(source_names)
    src = np.zeros((S, nz, ny, nx))
    for s, name in enumerate(source_names):
        for z, mask in src_of[name]:
            src[s, z][mask] = 1.0
        src[s] /= max(src[s].sum(), 1e-30)

    # observation: mean temperature over each tagged block's voxels
    obs_tags, obs_list = [], []
    for li, layer in enumerate(pkg.layers):
        zsel = [z for z in range(nz) if layer_of_slab[z] == li]
        for b in layer.blocks:
            if not b.tag:
                continue
            w = np.zeros((nz, ny, nx))
            mask = (XX >= b.x0) & (XX < b.x1) & (YY >= b.y0) & (YY < b.y1)
            for z in zsel:
                w[z][mask] = 1.0
            obs_tags.append(b.tag)
            obs_list.append(w / max(w.sum(), 1e-30))
    obs = (np.stack(obs_list) if obs_list
           else np.zeros((0, nz, ny, nx)))
    order = np.argsort(obs_tags)
    obs = obs[order]
    obs_tags = [obs_tags[i] for i in order]

    # face conductances (harmonic mean of half-cells)
    dzc = dz[:, None, None]
    gx = 1.0 / (0.5 * dx / (kx[:, :, :-1]) + 0.5 * dx / (kx[:, :, 1:])) \
        * dy * dzc
    gy = 1.0 / (0.5 * dy / (ky[:, :-1, :]) + 0.5 * dy / (ky[:, 1:, :])) \
        * dx * dzc
    rz = 0.5 * dz[:-1, None, None] / kz[:-1] + 0.5 * dz[1:, None, None] \
        / kz[1:]
    gz = (dx * dy) / rz

    conv = np.zeros((nz, ny, nx))
    conv[-1] += pkg.htc_top * dx * dy
    conv[0] += pkg.htc_bottom * dx * dy

    cvol = cv * dx * dy * dzc

    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return VoxelModel(dx=dx, dy=dy, dz=dz,
                      layer_of_slab=np.array(layer_of_slab),
                      cvol=f32(cvol), gx=f32(gx), gy=f32(gy), gz=f32(gz),
                      conv=f32(conv), src=f32(src), obs=f32(obs),
                      obs_tags=obs_tags, t_ambient=pkg.t_ambient,
                      source_names=source_names)


_FVM_DENSE_MAX_VOX = 20000  # dense (V, V) above this is an OOM foot-gun
# f32 contractions at full f32 precision: a TPU's default is one bf16 pass
_HI = jax.lax.Precision.HIGHEST


def _pad(a, axis: int, lo: int, hi: int):
    """Zero-pad ``a`` by ``lo``/``hi`` entries along ``axis``."""
    width = [(0, 0)] * a.ndim
    width[axis] = (lo, hi)
    return jnp.pad(a, width)


def neg_laplacian(gx, gy, gz, conv, theta):
    """``-L theta`` on (..., nz, ny, nx) fields: every voxel's net outflow
    through its faces plus its convection to ambient. Each face's flow,
    its conductance times the temperature step across it, leaves one
    voxel and enters the other, written with pads rather than scatters."""
    out = conv * theta
    for axis, g in zip((-1, -2, -3), (gx, gy, gz)):
        n = theta.shape[axis]
        f = g * (jax.lax.slice_in_dim(theta, 1, n, axis=axis)
                 - jax.lax.slice_in_dim(theta, 0, n - 1, axis=axis))
        out = out + _pad(f, axis, 1, 0) - _pad(f, axis, 0, 1)
    return out


def neg_laplacian_diag(gx, gy, gz, conv):
    """Diagonal of ``-L``: each voxel's face conductances plus its
    convection (the Jacobi preconditioner)."""
    out = conv
    for axis, g in zip((-1, -2, -3), (gx, gy, gz)):
        out = out + _pad(g, axis, 1, 0) + _pad(g, axis, 0, 1)
    return out


def stencil_pcg(apply, diag, rhs, x0, tol: float, maxiter: int):
    """Jacobi PCG of ``apply(x) = rhs`` over (B, nz, ny, nx) fields.

    The masked batched loop of ``pcg_loop`` on (B, V) rows: each row
    stops updating once ``||r|| <= tol ||b||`` and reports its
    iterations. Returns ``(x (B, nz, ny, nx), CGStats (B,))``."""
    b, shape = rhs.shape[0], rhs.shape
    d = diag.reshape(b, -1)
    x, stats = pcg_loop(lambda v: apply(v.reshape(shape)).reshape(b, -1),
                        lambda r: r / d, rhs.reshape(b, -1),
                        x0.reshape(b, -1), tol, maxiter)
    return x.reshape(shape), stats


def _concrete(stats: CGStats) -> bool:
    return not isinstance(stats.converged, jax.core.Tracer)


class FVMReference:
    """Jitted transient/steady conduction solver on a VoxelModel.

    solver tier: the stencil solver is natively matrix-free ("cg", also
    what "auto" resolves to — there is no crossover to chase here).
    ``solver="dense"`` assembles the (V, V) conduction matrix once and
    swaps in dense solves (steady) and a prefactored Cholesky (stepping)
    — a validation anchor for the sparse path on coarse grids, refused
    above ``_FVM_DENSE_MAX_VOX`` voxels.
    """

    fidelity = "fvm"

    def __init__(self, vm: VoxelModel, cg_tol: float = 1e-6,
                 cg_maxiter: int = 400, solver: str = "cg"):
        self.vm = vm
        self.tags = list(vm.obs_tags)
        self.source_names = list(vm.source_names)
        self.cg_tol = cg_tol
        self.cg_maxiter = cg_maxiter
        if solver not in ("dense", "cg", "auto"):
            raise ValueError(f"unknown solver {solver!r}")
        self.solver = "cg" if solver == "auto" else solver
        # diagonal of -L for Jacobi preconditioning
        self._neg_l_diag = neg_laplacian_diag(vm.gx, vm.gy, vm.gz, vm.conv)
        self.last_cg_stats: Optional[CGStats] = None
        self._steady_jit = None
        self._neg_l_dense = None
        if self.solver == "dense":
            if vm.n_vox > _FVM_DENSE_MAX_VOX:
                raise ValueError(
                    f"solver='dense' on {vm.n_vox} voxels would "
                    f"materialize a {vm.n_vox}x{vm.n_vox} matrix; use "
                    f"solver='cg' (the native path) or a coarser "
                    f"dx_target")
            self._neg_l_dense = jnp.asarray(self._assemble_dense())

    def _assemble_dense(self) -> np.ndarray:
        """Host-side dense -L (SPD, convection on the diagonal) from the
        face-conductance stencil — the validation twin of the matrix-free
        ``neg_laplacian``."""
        vm = self.vm
        nz, ny, nx = vm.shape
        v = vm.n_vox
        idx = np.arange(v).reshape(nz, ny, nx)
        a = np.zeros((v, v), np.float64)

        def couple(i, j, g):
            i, j, g = i.ravel(), j.ravel(), np.asarray(g,
                                                       np.float64).ravel()
            np.add.at(a, (i, j), -g)
            np.add.at(a, (j, i), -g)
            np.add.at(a, (i, i), g)
            np.add.at(a, (j, j), g)

        couple(idx[:, :, :-1], idx[:, :, 1:], vm.gx)
        couple(idx[:, :-1, :], idx[:, 1:, :], vm.gy)
        couple(idx[:-1], idx[1:], vm.gz)
        diag = np.arange(v)
        a[diag, diag] += np.asarray(vm.conv, np.float64).ravel()
        return a.astype(np.float32)

    def _q_field(self, q_src: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum("s,szyx->zyx", q_src.astype(jnp.float32),
                          self.vm.src, precision=_HI)

    def _obs(self, theta: jnp.ndarray) -> jnp.ndarray:
        return jnp.einsum("ozyx,zyx->o", self.vm.obs, theta, precision=_HI)

    def steady_state(self, q_src: jnp.ndarray) -> jnp.ndarray:
        """Solve -L theta = q; returns theta field. On the "cg" tier the
        solve's ``CGStats`` (shape (1,)) land on ``last_cg_stats``, with
        a warning when it hit the iteration cap."""
        if self.solver == "dense":
            rhs = self._q_field(q_src)
            sol = jnp.linalg.solve(self._neg_l_dense, rhs.ravel())
            return sol.reshape(self.vm.shape)
        if self._steady_jit is None:
            vm, diag = self.vm, self._neg_l_diag

            def fvm_steady_one(q):
                rhs = self._q_field(q)[None]
                sol, stats = stencil_pcg(
                    lambda x: neg_laplacian(vm.gx, vm.gy, vm.gz, vm.conv,
                                            x),
                    diag[None], rhs, jnp.zeros_like(rhs), self.cg_tol,
                    self.cg_maxiter * 4)
                return sol[0], stats

            self._steady_jit = jax.jit(fvm_steady_one)
        sol, stats = self._steady_jit(jnp.asarray(q_src))
        if _concrete(stats):
            self.last_cg_stats = stats
            warn_unconverged(stats, "fvm steady CG")
        return sol

    def observe(self, theta: jnp.ndarray) -> jnp.ndarray:
        """Absolute temperature at the observation tags (self.tags order)."""
        return self._obs(theta) + self.vm.t_ambient

    def make_simulator(self, dt: float):
        """Jitted simulate(theta0, q_traj[T,S]) -> obs_temps[T,n_obs].

        On the "cg" tier each step's ``CGStats`` (shape (T, 1)) land on
        the returned function's ``last_stats``, with a warning when any
        step hit the iteration cap."""
        vm = self.vm
        cdt = vm.cvol / dt
        diag = cdt + self._neg_l_diag
        qf, obs_of = self._q_field, self._obs
        tol, maxiter = self.cg_tol, self.cg_maxiter

        if self.solver == "dense":  # prefactored implicit Euler
            m = jnp.diag(cdt.ravel()) + self._neg_l_dense
            chol = jax.scipy.linalg.cho_factor(m)

            @jax.jit
            def simulate_dense(theta0, q_traj):
                def body(theta, q):
                    rhs = (cdt * theta + qf(q)).ravel()
                    th = jax.scipy.linalg.cho_solve(chol, rhs) \
                        .reshape(vm.shape)
                    return th, obs_of(th)

                _, obs = jax.lax.scan(body, theta0.astype(jnp.float32),
                                      q_traj)
                return obs + vm.t_ambient

            return simulate_dense

        def mv(x):
            return cdt * x + neg_laplacian(vm.gx, vm.gy, vm.gz, vm.conv, x)

        @jax.jit
        def simulate_dev(theta0, q_traj):
            def body(theta, q):
                rhs = (cdt * theta + qf(q))[None]
                th, stats = stencil_pcg(mv, diag[None], rhs, theta[None],
                                        tol, maxiter)
                return th[0], (obs_of(th[0]), stats)

            _, (obs, stats) = jax.lax.scan(
                body, theta0.astype(jnp.float32), q_traj)
            return obs + vm.t_ambient, stats

        def simulate(theta0, q_traj):
            obs, stats = simulate_dev(theta0, q_traj)
            if _concrete(stats):
                simulate.last_stats = stats
                warn_unconverged(stats, "fvm transient CG")
            return obs

        simulate.last_stats = None
        return simulate

    def simulate_batch(self, theta0, q_traj, dt: float) -> jnp.ndarray:
        """Batched rollout: theta0 (B,*shape), q_traj (T,B,S) -> (T,B,O)."""
        return simulate_batch_via_vmap(self, theta0, q_traj, dt)

    def zero_state(self, batch: Optional[int] = None) -> jnp.ndarray:
        shape = self.vm.shape if batch is None else (batch, *self.vm.shape)
        return jnp.zeros(shape, jnp.float32)

    def slab_mean_temp(self, theta: jnp.ndarray, layer_idx: int,
                       which: str = "all") -> float:
        """Mean temperature of a package layer (interface studies)."""
        zs = np.nonzero(self.vm.layer_of_slab == layer_idx)[0]
        if which == "top":
            zs = zs[-1:]
        elif which == "bottom":
            zs = zs[:1]
        return float(jnp.mean(theta[jnp.asarray(zs)]) + self.vm.t_ambient)


@register_fidelity("fvm")
def build_fvm(pkg: Package, dx_target: float = 0.5e-3,
              dz_target: float = 0.15e-3, max_slabs: int = 6,
              cg_tol: float = 1e-6, cg_maxiter: int = 400,
              solver: str = "cg") -> FVMReference:
    return FVMReference(voxelize(pkg, dx_target=dx_target,
                                 dz_target=dz_target, max_slabs=max_slabs),
                        cg_tol=cg_tol, cg_maxiter=cg_maxiter,
                        solver=solver)


# ---------------------------------------------------------------------------
# Batched design-space model: traced voxelization over a PackageFamily
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class _FamilyBlock:
    """Static per-block record for the traced voxelizer."""
    zmask: np.ndarray        # (nz,) bool — slabs of the block's layer
    layer_idx: int
    moving: bool             # any nonzero placement weight
    x0: float                # template corners (offsets apply on top)
    y0: float
    x1: float
    y1: float
    wx: np.ndarray           # (P,) placement weights: bx0 = x0 + wx @ p
    wy: np.ndarray
    kx: float
    ky: float
    kz: float
    cv: float
    power_name: Optional[str]
    tag: str


class FVMFamilyModel:
    """Finite-volume reference over a ``PackageFamily``.

    The voxel grid (nx, ny, slab structure) is frozen by the template;
    material/source/observation fields are re-rasterized per candidate as
    a traced function of the parameter vector (block masks move with the
    placement offsets exactly as ``voxelize`` would place them, so results
    match a per-candidate ``build(pkg, "fvm")`` loop bit-for-mask). Solves
    are the same matrix-free Jacobi PCG as :class:`FVMReference`, one
    masked batched loop over a chunk of candidates; batch execution rides
    a :class:`~repro.distribution.family_exec.FamilyExecutor`
    (``mesh=``/``chunk_size=``/``executor=``). This is the VALIDATION
    fidelity of the family ladder — it re-solves a shortlist at voxel
    resolution to ground the RC/DSS sweeps.

    STATIC blocks — all placement weights zero (non-parameterized
    chiplets, funnels of pinned sites, every block of thickness-/
    scalar-only families) — fold their material overlays into the
    background fields ONCE on the host, so the traced per-candidate
    program overlays only the MOVING blocks (for scalar-only families
    the trace contains no rasterization at all).

    Source and observation weights are never built as (S, nz, ny, nx)
    stacks. A block's footprint is the outer product of a column mask
    and a row mask over its layer's slabs, so a candidate's right-hand
    side is one (nz, ny, nb) x (nb, nx) contraction over the nb source
    blocks and its observations one (nz, ny, nx) x (nx, nb) contraction
    and a reduction: a few KB per candidate where the stacks took
    2 x S x V floats (86 MB at S = 64 and 0.25 mm voxels on 2p5d_64).
    """

    fidelity = "fvm"

    def __init__(self, family, dx_target: float = 0.5e-3,
                 dz_target: float = 0.15e-3, max_slabs: int = 6,
                 cg_tol: float = 1e-6, cg_maxiter: int = 400,
                 dtype=jnp.float32, mesh=None,
                 chunk_size: Optional[int] = None,
                 executor: Optional[FamilyExecutor] = None):
        pkg = family.template
        self.family = family
        self.dtype = dtype
        self.cg_tol, self.cg_maxiter = cg_tol, cg_maxiter
        self.param_names = list(family.param_names)
        self._slots = family.scalar_slots
        self._htc_bottom = pkg.htc_bottom
        self.exec = executor if executor is not None else \
            FamilyExecutor(mesh=mesh, chunk_size=chunk_size)
        self._ns = self.exec.register()  # jit-cache namespace
        self.last_cg_stats: Optional[CGStats] = None

        nx = max(2, int(round(pkg.length / dx_target)))
        ny = max(2, int(round(pkg.width / dx_target)))
        self.dx, self.dy = pkg.length / nx, pkg.width / ny
        xc = (np.arange(nx) + 0.5) * self.dx
        yc = (np.arange(ny) + 0.5) * self.dy
        self._xc64, self._yc64 = xc, yc

        # slab structure from the TEMPLATE thicknesses (topology fixed);
        # per-slab thickness is affine in the thickness parameters
        t_aff = family.thickness_affine()
        dz_base, dz_jac, layer_of_slab = [], [], []
        for li, layer in enumerate(pkg.layers):
            ns = min(max_slabs,
                     max(1, int(round(layer.thickness / dz_target))))
            const, w = t_aff[li]
            dz_base += [const / ns] * ns
            dz_jac += [w / ns] * ns
            layer_of_slab += [li] * ns
        self.layer_of_slab = np.array(layer_of_slab)
        nz = len(dz_base)
        self.shape = (nz, ny, nx)
        self._dz_base = jnp.asarray(np.array(dz_base), dtype)
        self._dz_jac = jnp.asarray(np.array(dz_jac), dtype)

        # static background fields + per-block records
        bg = np.zeros((4, nz, ny, nx))
        for z in range(nz):
            m = pkg.layers[layer_of_slab[z]].material
            bg[:, z] = np.array([m.kx, m.ky, m.kz, m.cv])[:, None, None]
        self.blocks = []
        for li, b, wx, wy in family.block_affine():
            zmask = self.layer_of_slab == li
            self.blocks.append(_FamilyBlock(
                zmask=zmask, layer_idx=li,
                moving=bool(wx.any() or wy.any()),
                x0=b.x0, y0=b.y0, x1=b.x1, y1=b.y1,
                wx=wx, wy=wy, kx=b.material.kx, ky=b.material.ky,
                kz=b.material.kz, cv=b.material.cv,
                power_name=b.power_name, tag=b.tag))
        self.source_names = sorted({b.power_name for b in self.blocks
                                    if b.power_name is not None})
        self.tags = sorted({b.tag for b in self.blocks if b.tag})

        # every block's footprint corners, affine in the parameters
        nb, n_p = len(self.blocks), len(self.param_names)
        field = lambda k, *shape: np.array(
            [getattr(b, k) for b in self.blocks]).reshape(nb, *shape)
        self._x0, self._x1 = field("x0"), field("x1")
        self._y0, self._y1 = field("y0"), field("y1")
        self._wx, self._wy = field("wx", n_p), field("wy", n_p)
        self._moving = field("moving").astype(bool)
        self._zmask = field("zmask", nz).astype(bool)
        # every footprint at zero offsets: the static blocks' masks
        self._mx0, self._my0 = self._spans(None)

        # hoist STATIC rasterization out of the per-candidate trace.
        # Material overlays are order-sensitive (later blocks override),
        # so a static block folds into the background only while no
        # moving block has been seen in its layer; any later static
        # block stays traced to preserve the overlay order exactly.
        self._traced_blocks = []
        self._traced_rows = []
        moving_layers: set = set()
        for i, blk in enumerate(self.blocks):
            if blk.moving or blk.layer_idx in moving_layers:
                if blk.moving:
                    moving_layers.add(blk.layer_idx)
                self._traced_blocks.append(blk)
                self._traced_rows.append(i)
            else:
                m3 = self._mask(i, self._mx0, self._my0)
                for f, v in enumerate((blk.kx, blk.ky, blk.kz, blk.cv)):
                    bg[f][m3] = v
        self._bg = jnp.asarray(bg, dtype)

        # the blocks that carry a source or a tag, and which one
        def group(key, names):
            rows = [i for i, b in enumerate(self.blocks) if key(b)]
            return (np.array(rows, np.int32),
                    np.array([names.index(key(self.blocks[i]))
                              for i in rows], np.int32), len(names))

        self._src_group = group(lambda b: b.power_name, self.source_names)
        self._tag_group = group(lambda b: b.tag or None, self.tags)

    @property
    def n_vox(self) -> int:
        return int(np.prod(self.shape))

    # -- traced voxelization -------------------------------------------------
    def _scalar(self, p, name):
        idx, const = self._slots[name]
        return p[idx] if idx >= 0 else jnp.asarray(const, self.dtype)

    def _spans(self, p):
        """Column and row masks of every block at parameter vector ``p``:
        (nb, nx) and (nb, ny) bools, true where a voxel centre lies in
        the block's half-open footprint. Static blocks are compared on
        the host in float64, as ``voxelize`` does; moving blocks in the
        model's dtype, in the trace."""
        if p is None:
            bx0, bx1 = self._x0[:, None], self._x1[:, None]
            by0, by1 = self._y0[:, None], self._y1[:, None]
            return ((self._xc64 >= bx0) & (self._xc64 < bx1),
                    (self._yc64 >= by0) & (self._yc64 < by1))
        if not self._moving.any():
            return self._mx0, self._my0
        cast = lambda a: jnp.asarray(a, self.dtype)
        ox = jnp.dot(cast(self._wx), p, precision=_HI)[:, None]
        oy = jnp.dot(cast(self._wy), p, precision=_HI)[:, None]
        xc, yc = cast(self._xc64)[None], cast(self._yc64)[None]
        mx = (xc >= cast(self._x0)[:, None] + ox) \
            & (xc < cast(self._x1)[:, None] + ox)
        my = (yc >= cast(self._y0)[:, None] + oy) \
            & (yc < cast(self._y1)[:, None] + oy)
        moving = self._moving[:, None]
        return (jnp.where(moving, mx, self._mx0),
                jnp.where(moving, my, self._my0))

    def _mask(self, i, mx, my):
        """(nz, ny, nx) voxels of block ``i`` from its span masks."""
        return (self._zmask[i][:, None, None] & my[i][None, :, None]
                & mx[i][None, None, :])

    def _weights(self, grp, mx, my):
        """Per block of a source or tag group: its slab weights divided
        by the voxel count of its source or tag (nb, nz), and its row
        and column masks as the model's dtype (None for no blocks)."""
        rows, owner, n_owner = grp
        if not rows.size:
            return None
        # host numpy while nothing moves: the trace then holds constants
        xp = np if isinstance(mx, np.ndarray) else jnp
        mx, my = mx[rows].astype(self.dtype), my[rows].astype(self.dtype)
        zm = self._zmask[rows].astype(self.dtype)
        count = zm.sum(1) * mx.sum(1) * my.sum(1)
        total = (np.bincount(owner, count, n_owner).astype(self.dtype)
                 if xp is np else jax.ops.segment_sum(count, owner, n_owner))
        return zm / xp.maximum(total, 1e-30)[owner][:, None], my, mx

    def _fields(self, p):
        """One parameter vector -> voxel fields (pure jax; vmap me).

        Only MOVING blocks are overlaid in the trace; static blocks were
        folded into ``_bg`` at construction, so the traced op count
        scales with the number of placement-parameterized blocks, not
        the package's block count."""
        mx, my = self._spans(p)
        kx, ky, kz, cv = (self._bg[i] for i in range(4))
        for i, blk in zip(self._traced_rows, self._traced_blocks):
            m3 = self._mask(i, mx, my)
            kx = jnp.where(m3, blk.kx, kx)
            ky = jnp.where(m3, blk.ky, ky)
            kz = jnp.where(m3, blk.kz, kz)
            cv = jnp.where(m3, blk.cv, cv)

        dz = self._dz_base + jnp.dot(self._dz_jac, p, precision=_HI)
        dzc = dz[:, None, None]
        dx, dy = self.dx, self.dy
        gx = 1.0 / (0.5 * dx / kx[:, :, :-1] + 0.5 * dx / kx[:, :, 1:]) \
            * dy * dzc
        gy = 1.0 / (0.5 * dy / ky[:, :-1, :] + 0.5 * dy / ky[:, 1:, :]) \
            * dx * dzc
        rz = 0.5 * dzc[:-1] / kz[:-1] + 0.5 * dzc[1:] / kz[1:]
        gz = (dx * dy) / rz

        nz = self.shape[0]
        zidx = jnp.arange(nz)[:, None, None]
        face = jnp.ones(self.shape, self.dtype) * dx * dy
        conv = jnp.where(zidx == nz - 1,
                         self._scalar(p, "htc_top") * face, 0.0) \
            + jnp.where(zidx == 0, self._htc_bottom * face, 0.0)
        return {"cvol": cv * dx * dy * dzc, "gx": gx, "gy": gy, "gz": gz,
                "conv": conv,
                "src": self._weights(self._src_group, mx, my),
                "obs": self._weights(self._tag_group, mx, my),
                "t_ambient": self._scalar(p, "t_ambient"),
                "power_scale": self._scalar(p, "power_scale")}

    def _rhs(self, f, q):
        """Heat input (nz, ny, nx) of source powers ``q`` (S,): each
        source's power spread evenly over its blocks' voxels."""
        if f["src"] is None:
            return jnp.zeros(self.shape, self.dtype)
        a, my, mx = f["src"]
        c = (q * f["power_scale"])[self._src_group[1]]
        t = (a * c[:, None]).T[:, None, :] * my.T[None]   # (nz, ny, nb)
        return jnp.matmul(t, mx, precision=_HI)

    def _obs(self, f, theta):
        """Mean rise (n_obs,) over each tag's voxels of ``theta``."""
        if f["obs"] is None:
            return jnp.zeros((0,), self.dtype)
        a, my, mx = f["obs"]
        u = jnp.matmul(theta, mx.T, precision=_HI)          # (nz, ny, nb)
        v = jnp.sum(u * my.T[None] * a.T[:, None, :], axis=(0, 1))
        return jax.ops.segment_sum(v, self._tag_group[1], len(self.tags))

    @staticmethod
    def _apply(f, x):
        return neg_laplacian(f["gx"], f["gy"], f["gz"], f["conv"], x)

    @staticmethod
    def _diag(f):
        return neg_laplacian_diag(f["gx"], f["gy"], f["gz"], f["conv"])

    def _batch_fields(self, params):
        return jax.vmap(self._fields)(params.astype(self.dtype))

    # -- batched solves ------------------------------------------------------
    @property
    def _pad_param_row(self) -> np.ndarray:
        return np.asarray(self.family.base_params())

    def steady_state_batch(self, params, q_src) -> jnp.ndarray:
        """params (B, P), q_src (B, S) -> steady theta (B, nz, ny, nx).

        One natively batched program per chunk (XLA name ``fvm_steady``):
        the fields of every candidate built under ``vmap``, then one
        masked Jacobi PCG over the chunk. Per-candidate ``CGStats`` land
        on ``last_cg_stats`` ((B,)), with a host-side warning when any
        row hit the iteration cap (counted in ``unconverged_counts()``).
        Spans: ``fvm.steady`` around the executor's spans and
        ``fvm.check``, that warning's read of the stats."""
        def fvm_steady(params, q):
            f = self._batch_fields(params)
            rhs = jax.vmap(self._rhs)(f, q.astype(self.dtype))
            return stencil_pcg(lambda x: self._apply(f, x), self._diag(f),
                               rhs, jnp.zeros_like(rhs), self.cg_tol,
                               self.cg_maxiter * 4)

        with span("fvm.steady"):
            th, stats = self.exec.run(
                f"{self._ns}:fvm_steady", fvm_steady, (params, q_src),
                in_axes=(0, 0), pad_rows=(self._pad_param_row, None))
            if _concrete(stats):
                self.last_cg_stats = stats
                with span("fvm.check"):
                    warn_unconverged(stats, "fvm family steady CG")
        return th

    def observe_batch(self, theta, params) -> jnp.ndarray:
        """theta (B, nz, ny, nx), params (B, P) -> (B, n_obs) degC."""
        def one(th, p):
            f = self._fields(p.astype(self.dtype))
            return self._obs(f, th.astype(self.dtype)) + f["t_ambient"]

        with span("fvm.observe"):
            return self.exec.run(f"{self._ns}:fvm_observe", one,
                                 (theta, params),
                                 in_axes=(0, 0), per_candidate=True,
                                 pad_rows=(None, self._pad_param_row))

    def simulate_family(self, params, q_traj, dt: float) -> jnp.ndarray:
        """params (B, P), q_traj (T, B, S) -> obs temps (T, B, n_obs).

        Implicit Euler, each step one masked PCG over the chunk warm
        started from the last state; a warning (counted in
        ``unconverged_counts()``) when any candidate's step hit the
        iteration cap."""
        def fvm_simulate(params, q_t):
            f = self._batch_fields(params)
            cdt = f["cvol"] / dt
            diag = cdt + self._diag(f)

            def body(th, qt):
                rhs = cdt * th + jax.vmap(self._rhs)(f, qt.astype(
                    self.dtype))
                th, st = stencil_pcg(lambda x: cdt * x + self._apply(f, x),
                                     diag, rhs, th, self.cg_tol,
                                     self.cg_maxiter)
                return th, (jax.vmap(self._obs)(f, th), st)

            th0 = jnp.zeros((params.shape[0], *self.shape), self.dtype)
            _, (obs, st) = jax.lax.scan(body, th0, q_t)
            stats = CGStats(st.iterations.sum(0), st.residual.max(0),
                            st.converged.all(0))
            return (jnp.swapaxes(obs, 0, 1)
                    + f["t_ambient"][:, None, None]), stats

        obs, stats = self.exec.run(
            (f"{self._ns}:fvm_simulate", float(dt)), fvm_simulate,
            (params, q_traj), in_axes=(0, 1),
            pad_rows=(self._pad_param_row, None))
        if _concrete(stats):
            warn_unconverged(stats, "fvm family transient CG")
        return obs.swapaxes(0, 1)


@register_family_fidelity("fvm")
def build_fvm_family(family, dx_target: float = 0.5e-3,
                     dz_target: float = 0.15e-3, max_slabs: int = 6,
                     cg_tol: float = 1e-6, cg_maxiter: int = 400,
                     dtype=jnp.float32, solver: str = "cg",
                     **exec_opts) -> FVMFamilyModel:
    if solver == "dense":
        raise NotImplementedError(
            "the FVM family solver is natively matrix-free; "
            "solver='dense' exists only on the single-package "
            "build(pkg, 'fvm') validation path")
    if solver not in ("cg", "auto"):
        raise ValueError(f"unknown solver {solver!r}")
    return FVMFamilyModel(family, dx_target=dx_target, dz_target=dz_target,
                          max_slabs=max_slabs, cg_tol=cg_tol,
                          cg_maxiter=cg_maxiter, dtype=dtype, **exec_opts)
