"""``batcher.queue_wait_ms``: median time an answered request waited in
the oracle's queue before its batch started (``OracleResponse.queue_s``,
stamped by the program on the host's monotonic clock)."""


def read(ctx):
    waits = ctx["counts"].get("queue_s")
    if not waits:
        return None
    import numpy as np
    return 1e3 * float(np.median(waits))
