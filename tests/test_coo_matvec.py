"""COO matvec kernel package: Pallas (interpret mode) and xla fallback
vs the dense oracle, so the kernel is exercised even on CPU-only CI.

Sweeps cover f32/f64 (the latter under ``enable_x64``; the CI kernel-
parity step also runs this file with ``JAX_ENABLE_X64=1``), ragged edge
counts that don't divide the tile size, batched operands riding the
GEMM sublane axis, and a real RC-network edge pattern.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_2p5d_package
from repro.core.rc_model import build_network
from repro.kernels.coo_matvec.ops import (coo_matvec, coo_plan,
                                          coo_segment_sum)
from repro.kernels.coo_matvec.ref import coo_matvec_ref, coo_segment_sum_ref

RNG = np.random.default_rng(11)


def _random_pattern(n, e):
    rows = RNG.integers(0, n, e).astype(np.int32)
    cols = RNG.integers(0, n, e).astype(np.int32)
    return rows, cols


def _tol(dtype):
    return 1e-4 if dtype == jnp.float32 else 1e-12


# ragged/padded edge counts: primes and off-by-one around the 512-edge
# tile, plus a multi-tile case
@pytest.mark.parametrize("n,e", [(17, 1), (37, 230), (129, 511),
                                 (129, 513), (300, 2048), (564, 5000)])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_segment_sum_parity(n, e, backend):
    rows, cols = _random_pattern(n, e)
    plan = coo_plan(rows, cols, n)
    vals = jnp.asarray(RNG.normal(size=e), jnp.float32)
    out = coo_segment_sum(plan, vals, backend=backend)
    ref = coo_segment_sum_ref(vals, jnp.asarray(rows), n)
    assert out.shape == (n,)
    assert float(jnp.abs(out - ref).max()) < _tol(jnp.float32)


@pytest.mark.parametrize("b", [1, 3, 8, 11])
@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_batched_matvec_parity(b, backend):
    n, e = 200, 1400
    rows, cols = _random_pattern(n, e)
    plan = coo_plan(rows, cols, n)
    gvals = jnp.asarray(RNG.normal(size=(b, e)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, n)), jnp.float32)
    out = coo_matvec(plan, gvals, x, backend=backend)
    ref = coo_matvec_ref(gvals, jnp.asarray(rows), jnp.asarray(cols), x, n)
    assert out.shape == (b, n)
    assert float(jnp.abs(out - ref).max()) < _tol(jnp.float32)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_broadcast_shared_gvals(backend):
    """One edge-value vector against a batch of states (family steady)."""
    n, e, b = 150, 900, 5
    rows, cols = _random_pattern(n, e)
    plan = coo_plan(rows, cols, n)
    gvals = jnp.asarray(RNG.normal(size=e), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, n)), jnp.float32)
    out = coo_matvec(plan, gvals, x, backend=backend)
    ref = coo_matvec_ref(gvals, jnp.asarray(rows), jnp.asarray(cols), x, n)
    assert out.shape == (b, n)
    assert float(jnp.abs(out - ref).max()) < _tol(jnp.float32)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_f64_parity(backend):
    n, e, b = 220, 1700, 4
    rows, cols = _random_pattern(n, e)
    with jax.enable_x64(True):
        plan = coo_plan(rows, cols, n)
        gvals = jnp.asarray(RNG.normal(size=(b, e)), jnp.float64)
        x = jnp.asarray(RNG.normal(size=(b, n)), jnp.float64)
        out = coo_matvec(plan, gvals, x, backend=backend)
        ref = coo_matvec_ref(gvals, jnp.asarray(rows), jnp.asarray(cols),
                             x, n)
        assert out.dtype == jnp.float64
        assert float(jnp.abs(out - ref).max()) < _tol(jnp.float64)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_real_network_pattern(backend):
    """The kernel on an actual Table-6 edge pattern reproduces the dense
    G matvec (off-diagonal part)."""
    net = build_network(make_2p5d_package(16))
    plan = coo_plan(net.rows, net.cols, net.n)
    gvals = jnp.asarray(net.gvals, jnp.float32)
    x = jnp.asarray(RNG.normal(size=net.n), jnp.float32)
    out = coo_matvec(plan, gvals, x, backend=backend)
    g_off = net.g_dense()
    np.fill_diagonal(g_off, 0.0)
    ref = jnp.asarray(g_off, jnp.float32) @ x
    # conductances span ~6 decades; compare relative to the row scale
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(out - ref).max()) / scale < 1e-6


def test_empty_pattern():
    plan = coo_plan(np.zeros(0, np.int32), np.zeros(0, np.int32), 12)
    out = coo_matvec(plan, jnp.zeros((0,)), jnp.ones(12),
                     backend="interpret")
    assert out.shape == (12,)
    assert float(jnp.abs(out).max()) == 0.0


def test_jit_and_grad_through_kernel():
    """The dispatch is jittable and differentiable w.r.t. edge values
    (the gradient-based-DSE roadmap item leans on this)."""
    n, e = 64, 300
    rows, cols = _random_pattern(n, e)
    plan = coo_plan(rows, cols, n)
    gvals = jnp.asarray(RNG.normal(size=e), jnp.float32)
    x = jnp.asarray(RNG.normal(size=n), jnp.float32)

    f = jax.jit(lambda g: coo_matvec(plan, g, x, backend="xla").sum())
    g1 = jax.grad(f)(gvals)
    g0 = jax.grad(lambda g: coo_matvec_ref(
        g, jnp.asarray(rows), jnp.asarray(cols), x, n).sum())(gvals)
    assert float(jnp.abs(g1 - g0).max()) < 1e-5
