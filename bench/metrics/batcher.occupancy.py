"""``batcher.occupancy``: mean share of the batch capacity that answered
batches filled (``OracleResponse.occupancy``; one batch is the set of
responses the oracle answered at one instant)."""


def read(ctx):
    occ = ctx["counts"].get("batch_occupancy")
    if not occ:
        return None
    return 100.0 * sum(occ) / len(occ)
