"""``oracle.worker_self_ms``: the worker's host time per answered row
that no inner span names: the self time of every ``mfit.oracle.batch``
span in the window (its duration less its children's: request sorting,
padding, the CG summary, status choice) over the rows those batches
answered (their ``rows`` arg)."""
from bench import program_spans as PS


def read(ctx):
    ps = PS.load(ctx)
    if ps is None:
        return None
    inner = [0.0] * len(ps.spans)
    for s in ps.spans:
        if s.parent is not None:
            inner[s.parent] += s.seconds
    lo, hi = ps.window
    self_s, rows = 0.0, 0
    for i, s in enumerate(ps.spans):
        if s.name == "mfit.oracle.batch" and lo <= s.start < hi:
            self_s += s.seconds - inner[i]
            rows += int(s.args.get("rows", 0))
    if not rows:
        return None
    return {"value": 1e3 * self_s / rows, "rows": rows}
