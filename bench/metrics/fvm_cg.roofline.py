"""``fvm_cg.roofline``: the voxel fidelity's steady program's share of
the chip's roofline.

Work is the algorithm's, counted per live candidate-iteration of Jacobi
PCG on the 7-point operator ``-L x = b`` at the real grid of ``V``
voxels (``nz x ny x nx``, the configuration's ``shape``) with
``F = nz ny (nx-1) + nz (ny-1) nx + (nz-1) ny nx`` interior faces, in the
configuration's dtype of ``w`` bytes:

  FLOPs, 4F + 15V:
    q = -L p             per face a difference and a product, added to
                         one voxel and taken from the other       4F
                         the convection term, conv * p, added     2V
    p.q, r.z, r.r        three dot products                       6V
    x += a p, r -= a q, p = z + b p                               6V
    z = r / diag                                                  V
  bytes, w * (F + 8V):
    reads the three face-conductance fields and the convection
    field, the diagonal, x, r and p                           w(F + 5V)
    writes x, r, p                                            w * 3V

(``z`` and ``q`` need not leave the step.) Building a candidate's fields
and right-hand side is once per solve, not per iteration, and is not
counted; frozen rows and padded rows are no work, so a loop that stops
computing them reads higher.

The share is max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the
device seconds of the XLA program named ``fvm_steady`` (the family's
steady chunk: fields, right-hand side and the PCG loop), from the
trace's "XLA Modules" line; the record names the bound that set it.
Nothing is read when the trace holds no such program.
"""
from bench import program_spans as PS

PROGRAM = "fvm_steady"


def faces(nz: int, ny: int, nx: int) -> int:
    return nz * ny * (nx - 1) + nz * (ny - 1) * nx + (nz - 1) * ny * nx


def work(shape, itemsize: int) -> tuple:
    """(FLOPs, bytes) of one live candidate-iteration."""
    nz, ny, nx = shape
    v, f = nz * ny * nx, faces(nz, ny, nx)
    return 4 * f + 15 * v, itemsize * (f + 8 * v)


def read(ctx):
    counts = ctx["counts"]
    ps = PS.load(ctx)
    if ps is None or not counts.get("cg_iterations"):
        return None
    seconds = sum(s for name, s in ps.device_by_program.items()
                  if PROGRAM in name)
    if seconds <= 0:
        return None
    import numpy as np
    cfg = ctx["config"]
    flops, nbytes = work(cfg["shape"], np.dtype(cfg["dtype"]).itemsize)
    it = counts["cg_iterations"]
    peaks = ctx["peaks"]
    t_flops = flops * it / peaks["flops_per_s"]
    t_bytes = nbytes * it / peaks["hbm_bytes_per_s"]
    return {"value": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute",
            "program_s": seconds}
