"""Paper §4.2 abstraction experiments (Tables 2, 3, 4).

Fine-grained FEM -> abstracted FEM, reproduced with our FVM reference:

  Table 2: a u-bump sub-block resolved bump-by-bump vs a homogenized block
           whose effective k comes from Eq. 2 — interface temperatures and
           the temperature drop across the layer must match.
  Table 3/4: a two-chiplet package with an explicit copper link in the
           interposer, vs an abstracted (averaged) link block, vs no link —
           receiving-chiplet temperature error and execution time.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import Block, Layer, Package, build
from repro.core.materials import (COPPER, INTERPOSER, SILICON, UNDERFILL,
                                  Material, iso)
from repro.runtime import use_compile_cache

SOLDER = iso("solder", 57.0, 7400.0, 230.0)


# ---------------------------------------------------------------------------
# Table 2: u-bump layer abstraction
# ---------------------------------------------------------------------------
def ubump_subblock(detailed: bool, k_eff: float = None,
                   side: float = 0.4e-3, pitch: float = 50e-6,
                   bump_d: float = 25e-6):
    """0.4x0.4 mm sub-block: silicon / u-bump layer / silicon.
    Heater on top face, convection at the bottom."""
    blocks = []
    if detailed:
        n = int(side / pitch)
        for i in range(n):
            for j in range(n):
                cx, cy = (i + 0.5) * pitch, (j + 0.5) * pitch
                h = bump_d / 2
                blocks.append(Block(cx - h, cy - h, cx + h, cy + h, SOLDER))
        mat = UNDERFILL
    else:
        mat = Material("ubump_eff", k_eff, k_eff, k_eff, 4600.0, 460.0)
    heater = Block(0, 0, side, side, SILICON, power_name="heat",
                   tag="heater")
    layers = (
        Layer("si_bottom", 0.05e-3, SILICON, 4, 4),
        Layer("bumps", 0.03e-3, mat, 4, 4, tuple(blocks)),
        Layer("si_top", 0.05e-3, SILICON, 4, 4,
              blocks=(heater,)),
    )
    return Package("ubump_block", side, side, layers, htc_top=0.0,
                   htc_bottom=20000.0, t_ambient=25.0)


def run_table2(power: float = 0.08, dx: float = 12.5e-6):
    out = {}
    t0 = time.time()
    pkg_d = ubump_subblock(detailed=True)
    fvm_d = build(pkg_d, "fvm", dx_target=dx, dz_target=10e-6, cg_tol=1e-8)
    ss = fvm_d.steady_state(np.array([power]))
    upper_d = fvm_d.slab_mean_temp(ss, 2)
    lower_d = fvm_d.slab_mean_temp(ss, 0)
    t_detailed = time.time() - t0

    # Eq. 2: k = q*l / (A * dT) from the detailed simulation
    top_bump = fvm_d.slab_mean_temp(ss, 1, "top")
    bot_bump = fvm_d.slab_mean_temp(ss, 1, "bottom")
    side = pkg_d.length
    l_bump = 0.03e-3
    k_eff = power * l_bump / (side * side * max(top_bump - bot_bump, 1e-9))

    t0 = time.time()
    pkg_a = ubump_subblock(detailed=False, k_eff=k_eff)
    fvm_a = build(pkg_a, "fvm", dx_target=dx, dz_target=10e-6, cg_tol=1e-8)
    ss_a = fvm_a.steady_state(np.array([power]))
    upper_a = fvm_a.slab_mean_temp(ss_a, 2)
    lower_a = fvm_a.slab_mean_temp(ss_a, 0)
    t_abstract = time.time() - t0

    out["k_eff_W_mK"] = k_eff
    out["detailed"] = {"upper_C": upper_d, "lower_C": lower_d,
                       "drop_C": upper_d - lower_d, "time_s": t_detailed}
    out["abstracted"] = {"upper_C": upper_a, "lower_C": lower_a,
                         "drop_C": upper_a - lower_a, "time_s": t_abstract}
    out["drop_err_C"] = abs(out["detailed"]["drop_C"]
                            - out["abstracted"]["drop_C"])
    out["speedup"] = t_detailed / max(t_abstract, 1e-9)
    return out


# ---------------------------------------------------------------------------
# Tables 3/4: link abstraction in a two-chiplet package
# ---------------------------------------------------------------------------
def two_chiplet_pkg(link: str):
    """link in {'detailed', 'abstract', 'none'}."""
    L, W = 8e-3, 4e-3
    cs = 1.5e-3
    c1x, c2x = 2e-3, 6e-3
    cy = W / 2
    chips = [Block(c1x - cs / 2, cy - cs / 2, c1x + cs / 2, cy + cs / 2,
                   SILICON, 2, 2, power_name="tx", tag="tx"),
             Block(c2x - cs / 2, cy - cs / 2, c2x + cs / 2, cy + cs / 2,
                   SILICON, 2, 2, power_name="rx", tag="rx")]
    link_blocks = ()
    if link == "detailed":
        # 16 copper wires, 20 um wide, between the chiplets
        wires = []
        for i in range(16):
            y = cy - 0.64e-3 + i * 80e-6
            wires.append(Block(c1x, y, c2x, y + 20e-6, COPPER))
        link_blocks = tuple(wires)
    elif link == "abstract":
        frac = 16 * 20e-6 / 1.28e-3  # metal fill fraction
        k_lat = COPPER.kx * frac + INTERPOSER.kx * (1 - frac)
        mat = Material("link_eff", k_lat, INTERPOSER.ky, INTERPOSER.kz,
                       INTERPOSER.rho, INTERPOSER.cp)
        link_blocks = (Block(c1x, cy - 0.64e-3, c2x, cy + 0.64e-3, mat),)
    layers = (
        Layer("substrate", 0.3e-3, INTERPOSER, 4, 2),
        Layer("interposer_links", 0.05e-3, INTERPOSER, 4, 2, link_blocks),
        Layer("chiplets", 0.1e-3, UNDERFILL, 4, 2, tuple(chips)),
    )
    return Package(f"two_chip_{link}", L, W, layers, htc_top=1500.0,
                   htc_bottom=12.0, t_ambient=25.0)


def run_tables34(dx: float = 0.1e-3):
    res = {}
    q_steady = np.array([3.0, 0.0])  # tx powered, rx observed
    n_t = 120
    rng = np.random.default_rng(0)
    q_trans = np.zeros((n_t, 2))
    q_trans[:, 0] = 3.0 * (rng.integers(0, 2, n_t // 10)
                           .repeat(10)[:n_t])
    for kind in ("detailed", "abstract", "none"):
        pkg = two_chiplet_pkg(kind)
        t0 = time.time()
        fvm = build(pkg, "fvm", dx_target=dx, dz_target=30e-6, cg_tol=1e-7)
        idx = fvm.tags.index("rx")
        ss = fvm.steady_state(q_steady)
        rx_steady = float(np.asarray(fvm.observe(ss))[idx])
        sim = fvm.make_simulator(0.05)
        obs = sim(fvm.zero_state(), q_trans)
        rx_trans = np.asarray(obs)[:, idx]
        res[kind] = {"rx_steady_C": rx_steady, "rx_trans": rx_trans,
                     "time_s": time.time() - t0}
    out = {"steady_mae_abstract":
           abs(res["abstract"]["rx_steady_C"]
               - res["detailed"]["rx_steady_C"]),
           "steady_mae_none":
           abs(res["none"]["rx_steady_C"]
               - res["detailed"]["rx_steady_C"]),
           "trans_mae_abstract":
           float(np.abs(res["abstract"]["rx_trans"]
                        - res["detailed"]["rx_trans"]).mean()),
           "trans_mae_none":
           float(np.abs(res["none"]["rx_trans"]
                        - res["detailed"]["rx_trans"]).mean()),
           "time_detailed_s": res["detailed"]["time_s"],
           "time_abstract_s": res["abstract"]["time_s"],
           "time_none_s": res["none"]["time_s"]}
    return out


def main(fast: bool = True):
    use_compile_cache()
    rows = []
    t2 = run_table2(dx=12.5e-6 if fast else 6.25e-6)
    rows.append(("table2_ubump_drop_err_C", t2["drop_err_C"],
                 f"k_eff={t2['k_eff_W_mK']:.2f}"))
    rows.append(("table2_speedup", t2["speedup"], ""))
    t34 = run_tables34(dx=0.2e-3 if fast else 0.1e-3)
    rows.append(("table3_steady_mae_abstract_C",
                 t34["steady_mae_abstract"], ""))
    rows.append(("table3_steady_mae_none_C", t34["steady_mae_none"], ""))
    rows.append(("table3_trans_mae_abstract_C",
                 t34["trans_mae_abstract"], ""))
    rows.append(("table3_trans_mae_none_C", t34["trans_mae_none"], ""))
    rows.append(("table4_time_detailed_s", t34["time_detailed_s"], ""))
    rows.append(("table4_time_abstract_s", t34["time_abstract_s"], ""))
    rows.append(("table4_time_none_s", t34["time_none_s"], ""))
    for name, val, extra in rows:
        print(f"{name},{val:.4f},{extra}")
    return rows


if __name__ == "__main__":
    main()
