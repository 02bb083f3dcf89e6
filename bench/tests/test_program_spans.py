"""The program-span reduction and its five readers, on hand-made events,
and on the trace recorded on a TPU v5e (which holds no program span)."""
from pathlib import Path

import pytest

from bench import harness as H
from bench import program_spans as PS

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
WORKER, CALLER = 1, 2


def _ev(name, start_ms, end_ms, thread, **args):
    return (f"mfit.{name}", start_ms * MS, (end_ms - start_ms) * MS, thread,
            args)


def _fleet(devices=None):
    """A 100 ms window: device busy 0-10, 30-40 and 60-100 ms. The worker
    answers one DTPM batch of 2 rows (5-50 ms), the caller submits twice
    (20-28 and 52-58 ms)."""
    devices = devices if devices is not None else [
        [("op", 0, 10 * MS), ("op", 30 * MS, 10 * MS),
         ("op", 60 * MS, 40 * MS)]]
    program = [
        _ev("oracle.batch", 5, 50, WORKER, rows=2),
        _ev("cache.get", 6, 7, WORKER),
        _ev("dtpm.serve", 8, 21, WORKER),
        _ev("dtpm.dispatch", 8, 9, WORKER, first=1),
        _ev("dtpm.land", 12, 20, WORKER),
        _ev("dtpm.serve", 30, 44, WORKER),
        _ev("dtpm.dispatch", 30, 31, WORKER, first=0),
        _ev("dtpm.land", 41, 44, WORKER),
        _ev("oracle.respond", 45, 50, WORKER),
        _ev("oracle.submit", 20, 28, CALLER),
        _ev("cache.key", 21, 27, CALLER),
        _ev("oracle.submit", 52, 58, CALLER),
        ("bench.sweep", 0, MS, CALLER, {}),          # not the program's
    ]
    host = [("bench.window", 0, 100 * MS), ("bench.submit", 0, 100 * MS)]
    modules = [("jit_dtpm_scan", 30 * MS, 10 * MS),
               ("jit_dtpm_scan", 95 * MS, 10 * MS)]    # clipped at 100
    return PS.summarize(devices, host, program, modules)


def test_spans_nest_per_thread_with_parent_and_root():
    ps = _fleet()
    names = [s.name for s in ps.spans]
    assert "bench.sweep" not in names and len(names) == 12
    by = {(s.name, s.start): i for i, s in enumerate(ps.spans)}
    land = by[("mfit.dtpm.land", 12 * MS)]
    batch = by[("mfit.oracle.batch", 5 * MS)]
    serve = by[("mfit.dtpm.serve", 8 * MS)]
    assert ps.spans[land].parent == serve
    assert ps.spans[serve].parent == batch
    assert ps.spans[by[("mfit.dtpm.serve", 30 * MS)]].parent == batch
    assert ps.spans[land].root == batch
    assert ps.ancestor(land, "mfit.oracle.batch") == batch
    key = by[("mfit.cache.key", 21 * MS)]
    assert ps.spans[ps.spans[key].parent].name == "mfit.oracle.submit"
    assert ps.spans[key].thread == CALLER
    assert ps.spans[batch].args == {"rows": 2}
    assert ps.spans[batch].seconds == pytest.approx(0.045)


def test_idle_goes_to_the_innermost_span_worker_first_else_outside():
    ps = _fleet()
    # idle 10-30 and 40-60 ms. While the caller submits (20-28 ms) the
    # worker is in its batch, which takes that time; 50-52 and 58-60 ms
    # lie under no span.
    assert ps.idle_s == pytest.approx(0.040)
    assert ps.idle_by_span == {
        "mfit.dtpm.serve": pytest.approx(0.004),     # 10-12, 20-21, 40-41
        "mfit.dtpm.land": pytest.approx(0.011),      # 12-20, 41-44
        "mfit.oracle.batch": pytest.approx(0.010),   # 21-30, 44-45
        "mfit.oracle.respond": pytest.approx(0.005),
        "mfit.oracle.submit": pytest.approx(0.006),
        PS.OUTSIDE: pytest.approx(0.004)}
    assert sum(ps.idle_by_span.values()) == pytest.approx(ps.idle_s)
    assert ps.idle_by_path[("mfit.oracle.batch", "mfit.dtpm.land")] == \
        pytest.approx(0.011)
    assert ps.idle_by_path[(PS.OUTSIDE, PS.OUTSIDE)] == pytest.approx(0.004)
    assert ps.device_by_program == {"jit_dtpm_scan": pytest.approx(0.015)}


def test_worker_idle_split_by_the_callers_span_beside_it():
    ps = _fleet()
    # the worker's idle instants (all but 50-52 and 58-60 ms, outside,
    # and none of the caller's alone), by what the caller had open:
    # cache.key 21-27 ms, oracle.submit 20-21 and 27-28 ms
    assert ps.idle_beside_caller == {
        "mfit.dtpm.serve": {PS.OUTSIDE: pytest.approx(0.003),
                            "mfit.oracle.submit": pytest.approx(0.001)},
        "mfit.dtpm.land": {PS.OUTSIDE: pytest.approx(0.011)},
        "mfit.oracle.batch": {"mfit.cache.key": pytest.approx(0.006),
                              "mfit.oracle.submit": pytest.approx(0.001),
                              PS.OUTSIDE: pytest.approx(0.003)},
        "mfit.oracle.respond": {PS.OUTSIDE: pytest.approx(0.005)}}
    worker_idle = ps.idle_s - ps.idle_by_span[PS.OUTSIDE] \
        - ps.idle_by_span["mfit.oracle.submit"]
    assert sum(sum(d.values()) for d in ps.idle_beside_caller.values()) \
        == pytest.approx(worker_idle)
    # a sweep has no caller: every worker instant pairs with outside
    sweep = PS.summarize([[("op", 0, MS)]], [("bench.window", 0, 10 * MS)],
                         [_ev("exec.land", 1, 4, WORKER)])
    assert sweep.idle_beside_caller == {
        "mfit.exec.land": {PS.OUTSIDE: pytest.approx(0.003)}}


def test_idle_is_the_mean_over_devices():
    dev0 = [("op", 0, 100 * MS)]                    # never idle
    dev1 = [("op", 0, 10 * MS)]                     # idle 10-100 ms
    ps = _fleet([dev0, dev1])
    assert ps.n_devices == 2
    assert ps.idle_s == pytest.approx(0.045)
    assert sum(ps.idle_by_span.values()) == pytest.approx(0.045)
    assert ps.idle_by_span["mfit.dtpm.land"] == pytest.approx(0.0055)
    assert ps.idle_by_span[PS.OUTSIDE] == pytest.approx((0.050 - 0.006)
                                                        / 2)


def test_overlapping_ops_and_a_window_with_no_op():
    gs, ge = PS._idle([("a", 0, 4 * MS), ("b", 2 * MS, 4 * MS),
                       ("c", 5 * MS, 1 * MS), ("d", 9 * MS, 5 * MS)],
                      0, 12 * MS)
    assert list(zip(gs, ge)) == [(6 * MS, 9 * MS)]
    gs, ge = PS._idle([], 0, 5 * MS)
    assert list(zip(gs, ge)) == [(0, 5 * MS)]


def test_sweep_gaps_split_between_executor_and_generator():
    """One thread: a chunk's landing and the next dispatch fall under the
    executor's spans, the generator's time between sweeps outside them."""
    program = [_ev("rc.steady", 0, 40, WORKER),
               _ev("exec.run", 0, 38, WORKER),
               _ev("exec.dispatch", 1, 2, WORKER, first=0),
               _ev("exec.land", 2, 18, WORKER),
               _ev("exec.dispatch", 18, 20, WORKER, first=0),
               _ev("exec.land", 20, 36, WORKER),
               _ev("rc.check", 38, 40, WORKER),
               _ev("rc.observe", 40, 50, WORKER),
               _ev("exec.run", 40, 50, WORKER),
               _ev("exec.dispatch", 40, 42, WORKER, first=0)]
    devices = [[("k", 2 * MS, 14 * MS), ("k", 20 * MS, 14 * MS),
                ("o", 43 * MS, 1 * MS)]]
    ps = PS.summarize(devices, [("bench.window", 0, 60 * MS)], program)
    # idle 0-2, 16-20, 34-43, 44-60 ms
    assert ps.idle_s == pytest.approx(0.031)
    assert ps.idle_by_span == {
        "mfit.exec.run": pytest.approx(0.010),  # 0-1, 36-38, 42-43, 44-50
        "mfit.exec.dispatch": pytest.approx(0.005),
        "mfit.exec.land": pytest.approx(0.004),
        "mfit.rc.check": pytest.approx(0.002),
        PS.OUTSIDE: pytest.approx(0.010)}
    got = _read(H.reader("executor.idle_per_chunk_ms"), ps)
    assert got["chunks"] == 3
    assert got["value"] == pytest.approx(1e3 * 0.021 / 3)
    assert got["idle_by_span"] == ps.idle_by_span


def _read(reader, ps):
    real = PS.load
    PS.load = lambda ctx: ps
    try:
        return reader.read({})
    finally:
        PS.load = real


def test_oracle_readers():
    ps = _fleet()
    got = _read(H.reader("oracle.submit_ms"), ps)
    assert got == {"value": pytest.approx(7.0), "calls": 2,
                   "cache_key_ms": pytest.approx(6.0)}
    got = _read(H.reader("oracle.worker_self_ms"), ps)
    # 45 ms of batch less cache.get 1, the serves 13 + 14, respond 5
    assert got == {"value": pytest.approx(12.0 / 2), "rows": 2}
    got = _read(H.reader("models.rows_per_launch"), ps)
    assert got == {"value": 1.0, "rows": 2, "launches": 2}
    got = _read(H.reader("models.land_idle_ms"), ps)
    assert got["value"] == pytest.approx(11.0 / 2)
    assert got["rows"] == 2
    assert got["idle_by_span"] == ps.idle_by_span
    assert got["idle_beside_caller"] == ps.idle_beside_caller
    assert got["device_by_program"] == ps.device_by_program


@pytest.mark.parametrize("metric", [
    "oracle.submit_ms", "oracle.worker_self_ms", "models.rows_per_launch",
    "models.land_idle_ms", "executor.idle_per_chunk_ms"])
def test_readers_find_nothing_without_program_spans(metric):
    reader = H.reader(metric)
    assert _read(reader, None) is None
    # a program that writes spans but not these: the sweep's for the
    # oracle readers, the oracle's for the executor's
    other = _fleet() if metric.startswith("executor") else PS.summarize(
        [[("op", 0, MS)]], [("bench.window", 0, 10 * MS)],
        [_ev("rc.observe", 1, 2, WORKER)])
    assert _read(reader, other) is None


def test_device_readers_need_a_device():
    ps = _fleet(devices=[])
    assert ps.n_devices == 0
    for metric in ("models.land_idle_ms", "executor.idle_per_chunk_ms"):
        assert _read(H.reader(metric), ps) is None


def test_recorded_tpu_trace_has_programs_and_no_spans(monkeypatch):
    ps = PS.read(str(next((DATA / "trace_small").glob("*.xplane.pb"))))
    assert ps.spans == [] and ps.n_devices == 1
    assert ps.device_by_program["jit__steady"] > 0
    # a run of a program without spans: load() gives readers nothing
    monkeypatch.setattr(PS, "trace_dir", lambda ctx: DATA / "trace_small")
    monkeypatch.setattr(PS, "_READ", {})
    assert PS.load({}) is None


def test_trace_dir_matches_the_cell_by_config_and_traffic(tmp_path,
                                                          monkeypatch):
    spec = H.spec()
    c = H.cell(spec, "dtpm_fleet.2p5d_64")
    monkeypatch.setattr(H, "spec", lambda: spec)
    monkeypatch.setattr(H, "ROOT", tmp_path)
    assert PS.trace_dir(c) is None                 # no trace written
    (tmp_path / ".bench_trace" / "dtpm_fleet.2p5d_64").mkdir(parents=True)
    assert PS.trace_dir(c) == tmp_path / ".bench_trace" / \
        "dtpm_fleet.2p5d_64"
    c["traffic"] = {**c["traffic"], "clients": 1}  # another mix
    assert PS.trace_dir(c) is None


def test_trace_dir_refuses_two_cells_of_one_config_and_traffic(
        monkeypatch):
    spec = H.spec()
    c = H.cell(spec, "dtpm_fleet.2p5d_64")
    twin = {**c["workload"], "name": "dtpm_fleet.2p5d_64.x4", "chips": 4}
    monkeypatch.setattr(H, "spec", lambda: {
        **spec, "workloads": spec["workloads"] + [twin]})
    with pytest.raises(ValueError, match="share a configuration"):
        PS.trace_dir(c)
