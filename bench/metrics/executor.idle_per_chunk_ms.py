"""``executor.idle_per_chunk_ms``: device-idle time per chunk launch of
the family executor: idle time whose innermost program span is an
``mfit.exec.*`` or ``mfit.rc.*`` span (padding, dispatch, each chunk's
landing, concatenation, the convergence check) over the window's
``mfit.exec.dispatch`` spans. Beside it, ``idle_by_span``: the window's
idle seconds per innermost span and ``outside`` (the traffic generator's
own code between sweeps); ``device_by_program``: device seconds per XLA
program."""
from bench import program_spans as PS


def read(ctx):
    ps = PS.load(ctx)
    if ps is None or ps.n_devices == 0:
        return None
    chunks = len(ps.in_window("mfit.exec.dispatch"))
    if not chunks:
        return None
    idle = ps.idle_where(lambda root, name: name.startswith(
        ("mfit.exec.", "mfit.rc.")))
    return {"value": 1e3 * idle / chunks, "chunks": chunks,
            **PS.idle_record(ps)}
