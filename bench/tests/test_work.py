"""Work counts, peaks and configuration sizes of the yardstick."""
import pytest

from bench import harness as H
from bench.reference import network as rn
from bench.reference import package as rp


def test_fused_cg_work_hand_count_2p5d_64():
    work = H.reader("fused_cg.roofline").work
    n, e = 2116, 15206
    # FLOPs: matvec 2E + 2n, three dots 6n, three axpys 6n, Jacobi n
    flops = 2 * e + 2 * n + 6 * n + 6 * n + n
    # bytes (f32): read E edge values + diag, x, r, p; write x, r, p
    nbytes = 4 * e + 4 * 4 * n + 3 * 4 * n
    assert work(n, e, 4) == (flops, nbytes) == (62152, 120072)


def test_roofline_reader_reads_bound_and_is_silent_without_kernel():
    read = H.reader("fused_cg.roofline").read

    class Trace:
        def __init__(self, s, k):
            self.s, self.k = s, k

        def seconds_matching(self, frag):
            return (self.s, self.k) if frag == "fused_cg_step" else (0.0, 0)

    ctx = {"config": {"nodes": 2116, "edges": 15206, "dtype": "float32"},
           "counts": {"cg_iterations": 1000, "cg_solves": 2},
           "peaks": H.peaks("TPU v5 lite")}
    got = read({**ctx, "trace": Trace(1e-3, 10)})
    want = (1000 * 120072 + 2 * 8 * 15206) / 819e9 / 1e-3 * 100
    assert got["bound"] == "bandwidth"
    assert got["value"] == pytest.approx(want)
    assert read({**ctx, "trace": Trace(0.0, 0)}) is None


def test_peaks_unknown_device_is_an_error():
    assert H.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(H.BenchError):
        H.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("config", ["mfit_2p5d_64", "mfit_3d_16x3"])
def test_config_sizes_match_the_reference(config):
    cfg = H.load_json(H.BENCH / "configs" / f"{config}.json")
    net = rn.build(rp.make_package(cfg["preset"]))
    assert (net.n, net.rows.size, net.p.shape[1], len(net.tags)) == (
        cfg["nodes"], cfg["edges"], cfg["sources"], cfg["observations"])
    assert rp.param_names(rp.make_package(cfg["preset"])) == \
        cfg["param_names"]
    assert len(cfg["sweep_box"]) == len(cfg["param_names"])
