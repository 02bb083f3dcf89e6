"""Power traces for the traffic generators (paper section 5.2.1, Table 7).

A copy of the program's workload generators, kept with the benchmark so
that no later change to the program can change the traffic. WL1 is the
synthetic stress -> PRBS -> cooldown trace; WL2-WL6 are the paper's
AI/ML job mixes scheduled greedily onto chiplets.
"""
from __future__ import annotations

import numpy as np

_NN = {
    "ResNet18": (1, 0.8, 0.85), "ResNet34": (2, 1.2, 0.88),
    "ResNet50": (3, 1.6, 0.90), "ResNet101": (5, 2.5, 0.92),
    "ResNet110": (5, 2.6, 0.92), "ResNet150": (7, 3.2, 0.93),
    "ResNet152": (7, 3.2, 0.93), "VGG16": (4, 2.0, 0.95),
    "VGG19": (5, 2.2, 0.95), "DenseNet40": (1, 0.9, 0.82),
    "DenseNet169": (6, 2.8, 0.90),
}


def _rep(n, name, ds):
    c, t, u = _NN[name]
    if ds == "C":
        c, t = max(1, c // 2), t * 0.6
    return [(c, t, u)] * n


_MIXES = {
    "WL2": (_rep(16, "ResNet34", "C") + _rep(1, "VGG19", "C")
            + _rep(5, "ResNet50", "C") + _rep(3, "DenseNet40", "C")
            + _rep(1, "ResNet152", "C") + _rep(1, "VGG19", "I")
            + _rep(4, "ResNet34", "I") + _rep(1, "ResNet18", "I")
            + _rep(1, "ResNet50", "I") + _rep(1, "VGG16", "I")),
    "WL3": (_rep(16, "ResNet34", "I") + _rep(1, "VGG19", "I")
            + _rep(5, "ResNet50", "I") + _rep(3, "DenseNet169", "I")
            + _rep(1, "ResNet110", "I") + _rep(1, "VGG19", "I")
            + _rep(4, "ResNet101", "I") + _rep(1, "ResNet152", "I")
            + _rep(1, "ResNet18", "I") + _rep(1, "ResNet50", "I")
            + _rep(1, "ResNet152", "I")),
    "WL4": (_rep(16, "ResNet34", "C") + _rep(2, "VGG19", "I")
            + _rep(4, "DenseNet169", "I") + _rep(3, "DenseNet40", "C")
            + _rep(5, "ResNet50", "C") + _rep(3, "ResNet101", "I")
            + _rep(7, "ResNet150", "I") + _rep(2, "VGG19", "I")
            + _rep(4, "ResNet101", "I") + _rep(1, "VGG19", "C")),
    "WL5": (_rep(16, "ResNet34", "I") + _rep(1, "ResNet152", "I")
            + _rep(1, "ResNet110", "I") + _rep(3, "ResNet101", "I")
            + _rep(9, "DenseNet169", "I") + _rep(4, "ResNet34", "I")
            + _rep(12, "ResNet18", "I") + _rep(5, "ResNet50", "I")
            + _rep(1, "ResNet152", "I")),
    "WL6": (_rep(3, "DenseNet169", "I") + _rep(4, "ResNet34", "I")
            + _rep(12, "ResNet18", "I") + _rep(4, "ResNet101", "I")
            + _rep(2, "VGG19", "I") + _rep(4, "ResNet101", "I")
            + _rep(1, "VGG19", "C") + _rep(3, "DenseNet40", "C")),
}


def wl1_prbs_rows(n_src: int, dt: float, p_max: float, seed: int,
                  t_prbs: float = 20.0, bit_s: float = 0.5) -> np.ndarray:
    """The PRBS phase of one WL1 trace (the phase between the full-power
    stress and the cooldown): (t_prbs / dt, n_src) watts."""
    rng = np.random.default_rng(seed)
    n_prbs = int(round(t_prbs / dt))
    bit_len = max(1, int(round(bit_s / dt)))
    bits = rng.integers(0, 2, size=(-(-n_prbs // bit_len), n_src))
    prbs = np.repeat(bits.astype(np.float64), bit_len, axis=0)[:n_prbs]
    p_lo = 0.25 * p_max
    return p_lo + prbs * (p_max - p_lo)


def nn_trace(name: str, n_src: int, dt: float, p_max: float,
             p_idle: float, seed: int) -> np.ndarray:
    """WL2-WL6: greedy first-fit job schedule -> (T, n_src) watts."""
    rng = np.random.default_rng(seed)
    free_at = np.zeros(n_src)
    events, t = [], 0.0
    for need, dur, util in _MIXES[name]:
        need = min(need, n_src)
        order = np.argsort(free_at)
        start = max(t, float(free_at[order[need - 1]]))
        chosen = order[:need]
        free_at[chosen] = start + dur
        events.append((start, start + dur, chosen,
                       util * float(rng.uniform(0.92, 1.0))))
        t = start
    out = np.full((int(np.ceil((float(free_at.max()) + 0.5) / dt)), n_src),
                  p_idle)
    for start, end, chosen, u in events:
        out[int(start / dt):int(end / dt), chosen] = \
            p_idle + u * (p_max - p_idle)
    return out
