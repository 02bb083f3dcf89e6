"""``fused_cg.roofline``: the fused CG step's share of the chip's roofline.

Work is the algorithm's, counted per live candidate-iteration of Jacobi
PCG on ``-G x = b`` at the real (unpadded) ``n`` nodes and ``E``
off-diagonal entries (both directions of every edge), in the
configuration's dtype of ``w`` bytes:

  FLOPs, 2E + 15n:
    q = diag*p - offdiag(g, p)  E multiplies and E adds over the edges,
                                n multiplies and n subtracts     2E + 2n
    p.q, r.z, r.r               three dot products               6n
    x += a p, r -= a q, p = z + b p                              6n
    z = r / diag                                                 n
  bytes, w * (E + 7n):
    reads the candidate's own E edge values and diag, x, r, p    w(E + 4n)
    writes x, r, p                                               w * 3n

(``z`` and ``q`` live only inside the step.) The shared edge pattern,
two int32 indices per entry, is read once per solve: 8E bytes per
(chunk, shard) solve. Frozen rows, padded rows and padded edges are no
work, so a kernel that stops computing them reads higher.

The share is max(FLOPs / peak FLOP/s, bytes / peak bytes/s) over the
summed device time of the ``fused_cg_step`` events in the trace; the
record names the bound that set it. Nothing is read when the trace holds
no such event.
"""
KERNEL = "fused_cg_step"


def work(n: int, e: int, itemsize: int) -> tuple:
    """(FLOPs, bytes) of one live candidate-iteration."""
    return 2 * e + 15 * n, itemsize * (e + 7 * n)


def pattern_bytes(e: int) -> int:
    return 8 * e


def read(ctx):
    seconds, events = ctx["trace"].seconds_matching(KERNEL)
    counts = ctx["counts"]
    if not events or seconds <= 0 or not counts.get("cg_iterations"):
        return None
    import numpy as np
    cfg = ctx["config"]
    flops, nbytes = work(cfg["nodes"], cfg["edges"],
                         np.dtype(cfg["dtype"]).itemsize)
    it = counts["cg_iterations"]
    flops *= it
    nbytes = nbytes * it + pattern_bytes(cfg["edges"]) * counts["cg_solves"]
    peaks = ctx["peaks"]
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"value": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "bandwidth" if t_bytes >= t_flops else "compute",
            "kernel_s": seconds, "events": events}
