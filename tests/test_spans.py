"""Program spans on the profiler's clock (``repro.runtime.span``).

Under ``jax.profiler`` the oracle, the DTPM and ROM models and the family
executor write ``mfit.*`` spans with their counters as args: the trace
holds each stage, nested on its thread, and one ``*.dispatch`` span
around each launch of a compiled program. With the profiler off the
spans change no answer and read no device value.
"""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import dss as dss_mod
from repro.core import dtpm as dtpm_mod
from repro.core import rc_model as rc_mod
from repro.core import rom as rom_mod
from repro.core.family import PackageFamily
from repro.core.fidelity import build_family
from repro.core.geometry import make_2p5d_package
from repro.distribution import family_exec as exec_mod
from repro.serving import ThermalOracle
from repro.serving import batcher as batcher_mod
from repro.serving import oracle as oracle_mod

DT = 0.01
ROM = {"ts": DT, "n_moments": 2}
SPAN_MODULES = (oracle_mod, batcher_mod, dtpm_mod, rom_mod, dss_mod,
                exec_mod, rc_mod)
#: the programs each dispatch span launches, by their jitted names
PROGRAMS = ("dtpm_scan", "rom_rollout", "_steady", "one")


def _calls():
    """One oracle DTPM group of two requests, one ROM transient, and a
    family steady + observe of three candidates in chunks of two."""
    pkg = make_2p5d_package(4)
    oracle = ThermalOracle(capacity=4, autostart=False)
    oracle.warm(pkg, fidelity="dss", ts=DT)
    oracle.warm(pkg, fidelity="rom", **ROM)
    fam = PackageFamily(pkg, params=("grid_offsets", "htc_top"))
    sim = build_family(fam, "rc", chunk_size=2)
    powers = np.random.default_rng(0).uniform(0.5, 3.0, (24, 4))
    params = np.vstack([fam.base_params(), fam.sample_params(2, seed=1)])
    q = np.full((3, 4), 3.0)

    def go():
        pending = [oracle.submit_dtpm(pkg, powers, fidelity="dss",
                                      opts={"ts": DT}),
                   oracle.submit_dtpm(pkg, 0.5 * powers, fidelity="dss",
                                      opts={"ts": DT}),
                   oracle.submit_transient(pkg, powers, DT,
                                           fidelity="rom", opts=ROM)]
        oracle.start()          # all three queued: two groups, in order
        out = [p.result(120) for p in pending]
        oracle.shutdown()
        temps = sim.observe_batch(sim.steady_state_batch(params, q), params)
        return out, temps

    return go


def _events(path):
    """Per host thread, its ``mfit.*`` and jitted-call events, nested:
    dicts with name, start, end, args, parent (an index)."""
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = sorted(((ev.name, ev.start_ns, ev.start_ns
                           + ev.duration_ns, dict(ev.stats))
                          for ev in line.events
                          if ev.name.startswith(("mfit.", "PjitFunction"))),
                         key=lambda e: (e[1], -e[2]))
            out, stack = [], []
            for name, start, end, args in evs:
                while stack and (out[stack[-1]]["end"] < end
                                 or out[stack[-1]]["end"] <= start):
                    stack.pop()
                out.append({"name": name, "start": start, "end": end,
                            "args": args,
                            "parent": stack[-1] if stack else None})
                stack.append(len(out) - 1)
            if any(e["name"].startswith("mfit.") for e in out):
                threads.append(out)
    return threads


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    go = _calls()
    where = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(where))
    try:
        go()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(where / "**" / "*.xplane.pb"), recursive=True)
    return _events(path)


def _named(threads, name):
    return [(t, i) for t in threads for i, e in enumerate(t)
            if e["name"] == f"mfit.{name}"]


def _parent(thread, i):
    p = thread[i]["parent"]
    while p is not None and not thread[p]["name"].startswith("mfit."):
        p = thread[p]["parent"]
    return None if p is None else thread[p]["name"][len("mfit."):]


def test_oracle_spans_nest_with_their_counters(traced):
    submits = _named(traced, "oracle.submit")
    assert len(submits) == 3
    assert all(_parent(t, i) == "oracle.submit"
               for t, i in _named(traced, "cache.key"))
    batches = _named(traced, "oracle.batch")
    assert [t[i]["args"] for t, i in batches] == [{"rows": 2}, {"rows": 1}]
    worker = batches[0][0]
    assert all(t is worker for t, _ in batches)          # one worker
    assert all(t is not worker for t, _ in submits)      # caller's thread
    assert len(_named([worker], "batcher.collect")) >= 2
    assert all(_parent(t, i) is None
               for t, i in _named(traced, "batcher.collect"))
    for name in ("cache.get", "oracle.respond"):
        assert [_parent(t, i) for t, i in _named(traced, name)] == \
            ["oracle.batch"] * 2
    assert [_parent(t, i) for t, i in _named(traced, "dtpm.serve")] == \
        ["oracle.batch"] * 2
    assert [(_parent(t, i), t[i]["args"]) for t, i in
            _named(traced, "dtpm.dispatch")] == [
        ("dtpm.serve", {"first": 1}), ("dtpm.serve", {"first": 0})]
    assert [_parent(t, i) for t, i in _named(traced, "dtpm.land")] == \
        ["dtpm.serve"] * 2
    assert [(_parent(t, i), t[i]["args"]) for t, i in
            _named(traced, "rom.dispatch")] == [
        ("oracle.batch", {"first": 1})]
    for name in ("rom.land", "oracle.land"):
        assert [_parent(t, i) for t, i in _named(traced, name)] == \
            ["oracle.batch"]


def test_executor_spans_one_land_per_chunk(traced):
    runs = _named(traced, "exec.run")
    assert [_parent(t, i) for t, i in runs] == ["rc.steady", "rc.observe"]
    for name in ("exec.pad", "exec.concat"):
        assert [_parent(t, i) for t, i in _named(traced, name)] == \
            ["exec.run"] * 2
    assert [(_parent(t, i), t[i]["args"]) for t, i in
            _named(traced, "exec.dispatch")] == [
        ("exec.run", {"first": 1}), ("exec.run", {"first": 0})] * 2
    # two chunks a run: each lands once, after its dispatch
    lands = _named(traced, "exec.land")
    assert [_parent(t, i) for t, i in lands] == ["exec.run"] * 4
    order = sorted(_named(traced, "exec.dispatch") + lands,
                   key=lambda ti: ti[0][ti[1]]["start"])
    assert [t[i]["name"] for t, i in order] == \
        ["mfit.exec.dispatch", "mfit.exec.land"] * 4
    assert [_parent(t, i) for t, i in _named(traced, "rc.check")] == \
        ["rc.steady"]


def test_one_dispatch_span_per_launch(traced):
    """Every outermost call of a model's compiled program sits in a
    ``*.dispatch`` span, one call per span."""
    launches = 0
    for thread in traced:
        for i, e in enumerate(thread):
            if not e["name"].endswith(".dispatch"):
                continue
            calls = [c for c in thread if c["parent"] == i
                     and c["name"].startswith("PjitFunction")]
            assert len(calls) == 1, (e["name"], calls)
            launches += 1
        outer = [c for c in thread if c["name"] in
                 {f"PjitFunction({p})" for p in PROGRAMS}
                 and c["parent"] is not None
                 and thread[c["parent"]]["name"].startswith("mfit.")]
        assert all(thread[c["parent"]]["name"].endswith(".dispatch")
                   for c in outer)
    assert launches == 2 + 1 + 4      # DTPM x2, ROM, 2 chunks x 2 calls


def _count_reads(monkeypatch, reads):
    """Count every conversion of a device array to a host value, under
    the program function that asks for it."""
    import sys

    def note():
        f = sys._getframe(2)
        while f is not None and "/repro/" not in f.f_code.co_filename:
            f = f.f_back
        if f is not None:
            reads[(f.f_code.co_filename.rsplit("/", 1)[-1],
                   f.f_code.co_name)] += 1

    def host(fn):
        def wrapped(a, *args, **kw):
            if isinstance(a, jax.Array):
                note()
            return fn(a, *args, **kw)
        return wrapped

    def method(fn):
        def wrapped(self, *args, **kw):
            note()
            return fn(self, *args, **kw)
        return wrapped

    for name in ("asarray", "array"):
        monkeypatch.setattr(np, name, host(getattr(np, name)))
    monkeypatch.setattr(jax, "device_get", host(jax.device_get))
    concrete = type(jnp.zeros(()))      # the class of a device array
    for name in ("__int__", "__float__", "__bool__", "__index__", "item",
                 "tolist"):
        monkeypatch.setattr(concrete, name,
                            method(getattr(concrete, name)))


def test_profiler_off_same_answers_no_extra_reads(monkeypatch):
    """With the profiler off the spans leave every answer bitwise the
    same and the device-to-host reads as they were."""
    from collections import Counter

    def run(stub):
        reads = Counter()
        with monkeypatch.context() as m:
            if stub:
                for mod in SPAN_MODULES:
                    m.setattr(mod, "span", _no_span)
            go = _calls()
            _count_reads(m, reads)
            out, temps = go()
        return out, temps, dict(reads)

    spanned, temps_s, reads_spanned = run(stub=False)
    bare, temps_b, reads_bare = run(stub=True)
    assert reads_spanned == reads_bare
    assert sum(reads_bare.values()) > 0        # the counter sees reads
    np.testing.assert_array_equal(temps_s, temps_b)
    for a, b in zip(spanned, bare):
        assert a.status == b.status == "ok"
        np.testing.assert_array_equal(a.value, b.value)


class _NoSpan:
    """What a span would be if the program wrote none."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **_):
        pass


def _no_span(name, **args):
    return _NoSpan()
