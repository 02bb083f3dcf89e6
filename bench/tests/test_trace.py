"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e by ``bench/tools/record_trace.py``."""
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    dev0 = [("a", 0, 4 * MS), ("b", 2 * MS, 4 * MS),   # overlap: 0-6 ms
            ("a", 10 * MS, 5 * MS),                    # 10-15 ms
            ("c", 18 * MS, 10 * MS)]                   # clipped at 20 ms
    dev1 = [("a", 1 * MS, 9 * MS)]                     # 1-10 ms
    host = [("bench.sweep", 0, 16 * MS), ("bench.wait", 15 * MS, 5 * MS)]
    s = T.reduce_events([dev0, dev1], host, (0, 20 * MS))
    assert s.window_s == pytest.approx(0.020)
    assert s.busy_s == pytest.approx((0.013 + 0.009) / 2)
    assert s.idle_share == pytest.approx(1 - 0.011 / 0.020)
    assert s.op_seconds["a"] == pytest.approx(0.004 + 0.005 + 0.009)
    assert s.op_seconds["c"] == pytest.approx(0.002)
    assert s.op_counts["a"] == 3
    # device 0's gaps: 6-10 ms under bench.sweep, 15-18 ms under wait
    assert s.gaps == [("bench.sweep", pytest.approx(0.004)),
                      ("bench.wait", pytest.approx(0.003))]
    assert s.top_ops(1) == [("a", pytest.approx(0.018))]
    assert s.seconds_matching("a") == (pytest.approx(0.018), 3)


def test_gap_with_no_annotation_is_untraced_host():
    s = T.reduce_events([[("a", 0, MS)]], [], (0, 3 * MS))
    assert s.gaps == [("untraced host", pytest.approx(0.002))]


def test_recorded_trace():
    s = T.summarize(T.find_xplane(str(DATA / "trace_small")))
    assert s.n_devices == 1
    # the solve, then 30 + 50 ms of host sleep
    assert 0.08 < s.window_s < 1.0
    assert 0 < s.busy_s < s.window_s - 0.075
    kernel_s, launches = s.seconds_matching("fused_cg_step")
    assert launches > 50 and 0 < kernel_s <= s.busy_s
    (first, t1), (second, t2) = s.gaps[:2]
    assert (first, second) == ("bench.wait", "untraced host")
    assert 0.045 < t1 < 0.1 and 0.025 < t2 < 0.045
