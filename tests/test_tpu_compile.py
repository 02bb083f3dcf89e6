"""Compile the Pallas kernels of the main path for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached. These tests catch what
interpret mode cannot — block shapes the Mosaic lowering refuses,
unaligned slices, scoped-VMEM overflows — at the Table-6 and 2p5d_256
sizes, in f32. The topology is described inside a fixture, never at
import, so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import make_2p5d_package
from repro.core.rc_model import build_network
from repro.kernels.backend import resolve_backend
from repro.kernels.coo_matvec.ops import coo_matvec, coo_plan
from repro.kernels.dss_step.ops import dss_step
from repro.kernels.fused_cg.ops import fused_cg_plan, fused_cg_solve


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back from the persistent
    # cache without a chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def nets():
    return {n: build_network(make_2p5d_package(n)) for n in (64, 256)}


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled, name: str) -> bool:
    text = compiled.as_text()
    return "tpu_custom_call" in text and name in text


@pytest.mark.parametrize("chiplets,batch", [(64, 1), (64, 64), (256, 8)])
def test_fused_cg_compiles_for_v5e(one_chip, nets, chiplets, batch):
    net = nets[chiplets]
    plan = fused_cg_plan(net.rows, net.cols, net.n)

    def solve(diag, gvals, rhs):
        return fused_cg_solve(plan, diag, gvals, rhs, tol=1e-5,
                              maxiter=1000, backend="pallas")

    args = [_spec((batch, net.n), jnp.float32, one_chip),
            _spec((batch, plan.n_edges), jnp.float32, one_chip),
            _spec((batch, net.n), jnp.float32, one_chip)]
    compiled = jax.jit(solve).lower(*args).compile()
    assert _has_kernel(compiled, "fused_cg_step")


def test_coo_matvec_compiles_for_v5e(one_chip, nets):
    net = nets[64]
    plan = coo_plan(net.rows, net.cols, net.n)
    compiled = jax.jit(
        lambda g, x: coo_matvec(plan, g, x, backend="pallas")).lower(
        _spec((8, net.rows.size), jnp.float32, one_chip),
        _spec((8, net.n), jnp.float32, one_chip)).compile()
    assert _has_kernel(compiled, "coo_segment_sum")


def test_dss_step_compiles_for_v5e(one_chip, nets):
    n, s, b = nets[64].n, 64, 8
    compiled = jax.jit(
        lambda th, q, a, bd: dss_step(th, q, a, bd, backend="pallas")).lower(
        _spec((b, n), jnp.float32, one_chip),
        _spec((b, s), jnp.float32, one_chip),
        _spec((n, n), jnp.float32, one_chip),
        _spec((s, n), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_f64_takes_the_xla_form_on_tpu(one_chip, nets, monkeypatch):
    """The dispatch rule: on a TPU, 'auto' is Pallas for f32 only; f64
    (which Mosaic cannot lower) takes the XLA form, and that program
    compiles for the chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_backend("auto", jnp.float32) == "pallas"
    assert resolve_backend("auto", jnp.bfloat16) == "pallas"
    assert resolve_backend("auto", jnp.float64) == "xla"
    assert resolve_backend("interpret", jnp.float64) == "interpret"
    net = nets[64]
    plan = fused_cg_plan(net.rows, net.cols, net.n)
    with jax.enable_x64(True):
        compiled = jax.jit(lambda d, g, r: fused_cg_solve(
            plan, d, g, r, tol=1e-10, maxiter=1000)).lower(
            _spec((4, net.n), jnp.float64, one_chip),
            _spec((4, plan.n_edges), jnp.float64, one_chip),
            _spec((4, net.n), jnp.float64, one_chip)).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("gpu", np.float32)
