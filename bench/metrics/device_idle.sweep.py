"""``device_idle.sweep``: the share of the traced window in which no
operation ran on the device (1 - union of op intervals / window),
averaged over the chips, in the sweep cells."""


def read(ctx):
    t = ctx["trace"]
    return None if t.n_devices == 0 else 100.0 * t.idle_share
