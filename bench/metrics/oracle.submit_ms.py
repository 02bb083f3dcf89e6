"""``oracle.submit_ms``: median host time of one ``submit_*`` call on the
caller's thread (the program's ``mfit.oracle.submit`` span: payload
check, cache key, enqueue), over the calls that start in the window.
Beside it, ``cache_key_ms``: the median of its child ``mfit.cache.key``,
the hash of the request's package."""
from bench import program_spans as PS


def read(ctx):
    ps = PS.load(ctx)
    submits = ps.in_window("mfit.oracle.submit") if ps else []
    if not submits:
        return None
    import numpy as np
    keys = ps.in_window("mfit.cache.key")
    return {"value": 1e3 * float(np.median([s.seconds for s in submits])),
            "calls": len(submits),
            **({"cache_key_ms": 1e3 * float(np.median(
                [s.seconds for s in keys]))} if keys else {})}
