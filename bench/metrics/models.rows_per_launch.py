"""``models.rows_per_launch``: requests a device launch answers, on the
oracle path: the ``rows`` of every ``mfit.oracle.batch`` span in the
window over the ``mfit.*.dispatch`` spans inside them (one per launch of
a model's compiled program: ``dtpm.dispatch`` per DTPM request,
``rom.dispatch`` / ``dss.dispatch`` / ``exec.dispatch`` per batch).
``batcher.occupancy`` is the share of capacity grouped; this is the
batch width the device sees."""
from bench import program_spans as PS

BATCH = "mfit.oracle.batch"


def read(ctx):
    ps = PS.load(ctx)
    if ps is None:
        return None
    lo, hi = ps.window
    batches = {i for i, s in enumerate(ps.spans)
               if s.name == BATCH and lo <= s.start < hi}
    rows = sum(int(ps.spans[i].args.get("rows", 0)) for i in batches)
    launches = sum(1 for i, s in enumerate(ps.spans)
                   if s.name.endswith(".dispatch")
                   and ps.ancestor(i, BATCH) in batches)
    if not rows or not launches:
        return None
    return {"value": rows / launches, "rows": rows, "launches": launches}
