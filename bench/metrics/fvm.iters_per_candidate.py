"""``fvm.iters_per_candidate``: mean Jacobi-PCG iterations a candidate of
the window's voxel sweeps spent live, from the program's per-row
``CGStats.iterations`` (``last_cg_stats`` of the family's steady
solve)."""


def read(ctx):
    counts = ctx["counts"]
    if not counts.get("candidates") or "cg_iterations" not in counts:
        return None
    return counts["cg_iterations"] / counts["candidates"]
