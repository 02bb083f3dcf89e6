"""``BENCHMARK.json`` resolves to files, and keeps the contract's shape."""
import re

import pytest

from bench import harness as H

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = H.spec()


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(H.ROOT.joinpath("BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("w", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves(w):
    c = H.cell(SPEC, w["name"])
    assert NAME.match(w["name"]) and w["chips"] in (1, 4)
    assert len(w["why"]) <= 200
    assert (H.BENCH / "drivers" / f"{c['traffic']['kind']}.py").is_file()
    assert hasattr(H.driver(c["traffic"]["kind"]), "Run")
    assert c["config"]["name"] == w["config"]
    e2e = H.metrics_for(SPEC, w["name"], False)
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    assert H.metrics_for(SPEC, w["name"], True)


def test_configs_and_pairs_are_unique():
    names = [c["name"] for c in SPEC["configs"]]
    assert len(set(names)) == len(names)
    assert len({c["file"] for c in SPEC["configs"]}) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == set(names)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")


@pytest.mark.parametrize("m", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        e2e = {x["name"] for x in SPEC["end_to_end"]}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(H.reader(m["name"]).read)
        for w in m["workloads"]:
            assert m["moves"] in [x["name"] for x in
                                  H.metrics_for(SPEC, w, False)]
