"""Shared helpers for the benchmark's own tests (run on the CPU:
``JAX_PLATFORMS=cpu python -m pytest bench/tests``)."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


@pytest.fixture
def run_cell(monkeypatch, capsys):
    """Drive ``bench/run.py``'s whole run in this process on the CPU:
    the harness's look for a chip is skipped and the cell's traffic is
    shrunk by ``traffic`` overrides. Returns the parsed result line."""
    from bench import harness as H
    import bench.run as R

    def fake_devices(chips, platform="tpu"):
        import jax
        return {"platform": jax.devices()[0].platform, "kind": "TPU v5 lite",
                "count": len(jax.devices())}

    def go(workload, traffic=None, config=None, seed=20241009, seconds=1.0,
           control=False):
        real_cell, real_driver = H.cell, H.driver

        def cell(spec, name):
            c = real_cell(spec, name)
            c["traffic"].update(traffic or {})
            c["config"].update(config or {})
            return c

        def driver(kind):
            mod = real_driver(kind)
            run = mod.Run
            mod.Run = lambda *a: run(*a, control=control)
            return mod

        monkeypatch.setattr(H, "devices", fake_devices)
        monkeypatch.setattr(H, "cell", cell)
        monkeypatch.setattr(H, "driver", driver)
        capsys.readouterr()
        assert R.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds)]) == 0
        out = capsys.readouterr().out.strip().splitlines()
        monkeypatch.setattr(H, "cell", real_cell)
        monkeypatch.setattr(H, "driver", real_driver)
        return json.loads(out[-1])

    return go
