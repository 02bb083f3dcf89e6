"""``models.land_idle_ms``: device-idle time per answered row while the
oracle's worker lands results: idle time whose innermost program span is
an ``mfit.*.land`` span inside ``mfit.oracle.batch`` (``dtpm.land``,
``rom.land``, ``oracle.land``, ``exec.land``), over the rows the
window's batches answered. Beside it, ``idle_by_span``: the window's
idle seconds per innermost span and ``outside``;
``idle_beside_caller``: each worker span's idle seconds by the caller's
span open at the same instant (a land's idle time beside ``cache.key``
may be the worker waiting for the interpreter lock, not its reads);
``device_by_program``: device seconds per XLA program."""
from bench import program_spans as PS

BATCH = "mfit.oracle.batch"


def read(ctx):
    ps = PS.load(ctx)
    if ps is None or ps.n_devices == 0:
        return None
    rows = sum(int(s.args.get("rows", 0)) for s in ps.in_window(BATCH))
    if not rows:
        return None
    land = ps.idle_where(lambda root, name: root == BATCH
                         and name.endswith(".land"))
    return {"value": 1e3 * land / rows, "rows": rows,
            **PS.idle_record(ps),
            "idle_beside_caller": ps.idle_beside_caller}
