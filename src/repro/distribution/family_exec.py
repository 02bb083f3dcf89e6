"""Family execution backbone: mesh-parallel, chunk-streamed DSE sweeps.

Every family model of the fidelity ladder (``RCFamilyModel`` /
``DSSFamilyModel`` / ``FVMFamilyModel`` / ``ROMFamilyModel``) evaluates a
``(B, P)`` candidate batch as a device batch axis. Before PR 5 each model
hand-rolled its own ``jax.jit(jax.vmap(...))`` plumbing and the whole
batch lived on ONE device — a 10k-candidate placement sweep was
memory-bound and serial. :class:`FamilyExecutor` is the shared execution
layer those models now delegate their batch axis to; the models express
only their per-candidate (or natively batched) math. It owns two
orthogonal concerns:

**Mesh sharding.** When built with ``mesh=`` (a ``jax.sharding.Mesh``, or
an int meaning "the first k host devices via
``launch.mesh.make_host_mesh``"), the candidate axis is partitioned over
the mesh's ``data`` axis with ``shard_map``: every device runs the
unmodified single-device batched program on its ``B/k`` slice of the
batch. There is deliberately NO GSPMD auto-partitioning here — candidates
are independent, so the right layout is fully data-parallel with zero
cross-device collectives, and ``shard_map`` makes that a structural
guarantee rather than a compiler outcome. In particular the
``kernels/coo_matvec`` segment-sum kernel composes unchanged: its COO
plan is a closure constant (replicated to every shard) and the local
batch rides the kernel's leading/GEMM-sublane axis, so every shard runs
per-shard kernel launches over its own candidates and no edge ever
crosses a device boundary. ``B`` is padded up to the shard count with a
caller-provided pad row (family models pad with the template's
``base_params()``, a valid candidate, so padding can never produce
degenerate geometry) and the tail is sliced off the result.

**Chunk streaming.** Sweeps larger than memory run as a host-side scan
over fixed-size candidate chunks (``chunk_size=``): one compiled
executable is reused for every chunk, each chunk's result is pulled to
host memory before the next chunk is dispatched (device footprint is one
chunk, not one sweep), and call sites that solve iteratively can thread a
carry between chunks — the RC family's steady CG warm-starts each chunk
from the previous chunk's converged states, which is what makes a B=10k
steady sweep both bounded-memory and cheaper than 20 cold B=512 sweeps.

The two compose: ``chunk_size`` must be a multiple of the shard count and
each chunk is itself mesh-sharded. CPU CI exercises the mesh path with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (see
``tests/test_family_exec.py`` and the ``sharded_dse`` benchmark section).

Typical use goes through ``build_family``::

    sim = build_family(fam, "rc", mesh=8, chunk_size=512)
    temps = sim.observe_batch(sim.steady_state_batch(params, q), params)

but the executor is model-agnostic: ``run()`` takes any jax-traceable
batched callable plus a declaration of which argument/output axes carry
the candidate batch.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ..runtime import span

# dt-keyed jit entries (one XLA executable per sampling period) are
# bounded to this many per key prefix, mirroring fidelity.evict_stale_jits
_KEEP_JITS = 8


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False``: the family solvers
    carry ``lax.while_loop``s around a ``pallas_call`` (batched CG),
    which the varying-manual-axes checker has no rule for — replication
    is trivially correct here since the executor never closes over
    sharded values."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


class FamilyExecutor:
    """Executes batched family callables over a (possibly sharded,
    possibly chunk-streamed) candidate axis.

    mesh:        None (single device) | ``jax.sharding.Mesh`` | int k
                 (the first k host devices, ``launch.mesh.make_host_mesh``).
                 The candidate axis shards over ``batch_axis``.
    chunk_size:  None (whole batch in one device call) | int: sweeps with
                 ``B > chunk_size`` stream over fixed-size chunks, results
                 land in host memory chunk by chunk. Must be a multiple of
                 the shard count.
    batch_axis:  name of the mesh axis carrying the candidate batch.
    """

    def __init__(self, mesh: Optional[object] = None,
                 chunk_size: Optional[int] = None,
                 batch_axis: str = "data"):
        if isinstance(mesh, int):
            from ..launch.mesh import make_host_mesh
            if mesh > len(jax.devices()):
                raise ValueError(
                    f"mesh={mesh} devices requested but only "
                    f"{len(jax.devices())} present (CPU hosts can "
                    f"simulate more via XLA_FLAGS="
                    f"--xla_force_host_platform_device_count=N)")
            mesh = make_host_mesh(data=mesh) if mesh > 1 else None
        if mesh is not None and batch_axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {batch_axis!r}; "
                             f"axes: {mesh.axis_names}")
        self.mesh: Optional[Mesh] = mesh
        self.batch_axis = batch_axis
        self.n_shards = int(mesh.shape[batch_axis]) if mesh is not None \
            else 1
        if chunk_size is not None and (
                chunk_size <= 0 or chunk_size % self.n_shards):
            raise ValueError(
                f"chunk_size={chunk_size} must be a positive multiple of "
                f"the shard count ({self.n_shards}) so every chunk "
                f"splits evenly over the mesh")
        self.chunk_size = chunk_size
        self._jits: dict = {}
        # Guards the _jits check-then-insert: the serving oracle runs
        # models from a worker thread while clients may drive the same
        # executor directly, so compilation must be re-entrant. Traced
        # compilation itself happens OUTSIDE jax.jit (which is lazy), so
        # holding the lock across _compile costs only dict bookkeeping.
        self._jits_lock = threading.Lock()
        self._n_owners = 0

    def register(self) -> str:
        """Claim a jit-cache namespace for one owning model.

        An executor may be SHARED between models (the DSS/ROM rungs ride
        their embedded RC family's executor; callers can pass
        ``executor=`` to co-locate several sweeps). Cache keys are
        call-site strings like ``"rc_steady"``, so two peer models with
        identical call sites would otherwise silently serve each other's
        compiled closures — every model prefixes its keys with the token
        returned here instead."""
        with self._jits_lock:
            self._n_owners += 1
            return f"m{self._n_owners}"

    def describe(self) -> dict:
        """Benchmark/telemetry summary of the execution layout."""
        return {"devices": self.n_shards,
                "chunk_size": self.chunk_size,
                "batch_axis": self.batch_axis if self.mesh is not None
                else None}

    # ------------------------------------------------------------------
    # padding / slicing helpers
    # ------------------------------------------------------------------
    def _plan_batch(self, b: int) -> Tuple[int, int]:
        """(padded B, chunk length). Chunks are uniform so ONE compiled
        executable serves the whole stream."""
        if self.chunk_size is not None and b > self.chunk_size:
            chunk = self.chunk_size
        else:
            chunk = -(-b // self.n_shards) * self.n_shards
        b_pad = -(-b // chunk) * chunk
        return b_pad, chunk

    @staticmethod
    def _pad(arr, axis: int, b: int, b_pad: int, pad_row):
        """Pad ``arr`` along ``axis`` from b to b_pad rows (host numpy).

        ``pad_row`` is one batch element with the batch axis removed
        (e.g. the family's ``base_params()``) repeated into the tail, or
        None for zeros. Device arrays are padded with device ops —
        pulling e.g. a whole (B, N) sharded state to the host just to
        append a few pad rows would cost a D2H+H2D round-trip of the
        entire batch on every call."""
        if b_pad == b:
            return arr  # no pad: hand through (device arrays stay put)
        xp = jnp if isinstance(arr, jax.Array) else np
        arr = xp.asarray(arr)
        shape = list(arr.shape)
        shape[axis] = b_pad - b
        if pad_row is None:
            tail = xp.zeros(shape, arr.dtype)
        else:
            tail = xp.broadcast_to(
                xp.expand_dims(xp.asarray(pad_row, arr.dtype), axis),
                shape)
        return xp.concatenate([arr, tail], axis=axis)

    @staticmethod
    def _slice(arr, axis: int, start: int, length: int):
        sl = (slice(None),) * axis + (slice(start, start + length),)
        return arr[sl]

    def _spec(self, axis: Optional[int]) -> P:
        if axis is None:
            return P()
        return P(*((None,) * axis), self.batch_axis)

    # ------------------------------------------------------------------
    # jit cache
    # ------------------------------------------------------------------
    def _evict(self, key) -> None:
        if not isinstance(key, tuple):
            return
        stale = [k for k in self._jits
                 if isinstance(k, tuple) and k[0] == key[0]]
        while len(stale) >= _KEEP_JITS:
            self._jits.pop(stale.pop(0))

    def _compile(self, key, fn: Callable, in_axes: Sequence[Optional[int]],
                 out_axis: int, per_candidate: bool,
                 with_carry: bool) -> Callable:
        with self._jits_lock:
            if key in self._jits:
                return self._jits[key]
            self._evict(key)
            f = fn
            if per_candidate:
                if with_carry:
                    raise ValueError("carry is only supported for "
                                     "natively batched callables")
                f = jax.vmap(fn, in_axes=tuple(in_axes),
                             out_axes=out_axis)
            if self.mesh is not None:
                arg_specs = tuple(self._spec(a) for a in in_axes)
                out_spec = self._spec(out_axis)
                if with_carry:
                    # carry rides batch axis 0 (chunk-shaped CG states)
                    f = _shard_map(f, self.mesh,
                                   in_specs=(self._spec(0),) + arg_specs,
                                   out_specs=(out_spec, self._spec(0)))
                else:
                    f = _shard_map(f, self.mesh, in_specs=arg_specs,
                                   out_specs=out_spec)
            self._jits[key] = jax.jit(f)
            return self._jits[key]

    # ------------------------------------------------------------------
    # the execution entry point
    # ------------------------------------------------------------------
    def run(self, key, fn: Callable, args: Sequence,
            in_axes: Sequence[Optional[int]], out_axis: int = 0,
            per_candidate: bool = False,
            pad_rows: Optional[Sequence] = None,
            make_carry: Optional[Callable[[int], object]] = None):
        """Execute ``fn`` over the candidate batch.

        key:           jit-cache key (unique per call site; include dt for
                       per-sampling-period traces — old dt entries are
                       evicted past a bound).
        fn:            jax-traceable callable over ``args``. With
                       ``per_candidate=True`` it maps ONE candidate and
                       the executor vmaps it; otherwise it is natively
                       batched. With ``make_carry`` its signature is
                       ``fn(carry, *args) -> (out, carry)`` and the carry
                       (batch axis 0) threads across chunks — the RC
                       steady CG warm start.
        in_axes:       per-arg candidate axis (None = not batched).
        out_axis:      candidate axis of the output. The output may be a
                       PYTREE (e.g. ``(theta, CGStats)``); every leaf
                       must carry the candidate batch on ``out_axis``
                       (padding is sliced off, chunk streaming
                       concatenates, and mesh sharding broadcasts the
                       out spec, per leaf).
        pad_rows:      per-arg pad element used when B is padded up to
                       the shard/chunk grain (None = zeros). Family
                       models pass their template ``base_params()`` so
                       pad candidates stay valid geometry.
        make_carry:    chunk length -> initial carry.

        Returns the output with the pad tail sliced off: a device array
        for single-chunk runs, a host numpy array when chunk-streamed
        (that host landing is what bounds device memory to one chunk).

        Spans: ``exec.run`` around ``exec.pad``, then per chunk
        ``exec.dispatch`` (``first=1`` when this call built the jitted
        closure) and, when streamed, ``exec.land``; then ``exec.concat``.
        """
        # coerce plain Python containers (lists/tuples) to host arrays;
        # real arrays pass through untouched so device arrays stay on
        # device (padding/slicing handles them with device ops)
        args = [a if isinstance(a, (np.ndarray, jax.Array))
                else np.asarray(a) for a in args]
        if pad_rows is None:
            pad_rows = [None] * len(args)
        b = None
        for a, ax in zip(args, in_axes):
            if ax is not None:
                b = int(np.shape(a)[ax])
                break
        if b is None or b == 0:
            raise ValueError("run() needs at least one batched argument "
                             "with a non-empty candidate axis")
        b_pad, chunk = self._plan_batch(b)
        n_chunks = b_pad // chunk
        with span("exec.run"):
            with span("exec.pad"):
                padded = [a if ax is None
                          else self._pad(a, ax, b, b_pad, row)
                          for a, ax, row in zip(args, in_axes, pad_rows)]
            built = key not in self._jits
            jfn = self._compile(key, fn, in_axes, out_axis, per_candidate,
                                make_carry is not None)

            carry = make_carry(chunk) if make_carry is not None else None
            outs = []
            for c in range(n_chunks):
                chunk_args = [a if ax is None
                              else self._slice(a, ax, c * chunk, chunk)
                              for a, ax in zip(padded, in_axes)]
                with span("exec.dispatch", first=int(built and c == 0)):
                    if carry is not None:
                        out, carry = jfn(carry, *chunk_args)
                    else:
                        out = jfn(*chunk_args)
                if n_chunks > 1:
                    # stream: device holds ONE chunk (leaf-wise for
                    # pytrees)
                    with span("exec.land"):
                        out = jax.tree_util.tree_map(np.asarray, out)
                outs.append(out)

            def unpad(tree):
                return jax.tree_util.tree_map(
                    lambda leaf: self._slice(leaf, out_axis, 0, b), tree)

            if n_chunks == 1:
                out = outs[0]
                return out if b_pad == b else unpad(out)
            with span("exec.concat"):
                out = jax.tree_util.tree_map(
                    lambda *leaves: np.concatenate(leaves, axis=out_axis),
                    *outs)
                return out if b_pad == b else unpad(out)

    def run_value_and_grad(self, key, fn: Callable, args: Sequence,
                           in_axes: Sequence[Optional[int]],
                           pad_rows: Optional[Sequence] = None,
                           argnums: int = 0):
        """Pad-aware per-candidate value-and-grad (the gradient-DSE path).

        ``fn`` maps ONE candidate to a scalar objective; this evaluates
        ``jax.value_and_grad(fn, argnums)`` vmapped over the candidate
        batch through the same machinery as :meth:`run` — mesh sharding,
        chunk streaming, jit caching — returning ``(values, grads)`` with
        the candidate axis leading on both (``grads`` matches the
        ``argnums`` argument's trailing shape). Padding is MASKED by
        construction: pad rows evaluate the caller's ``pad_rows`` element
        (family models pass the template's always-valid ``base_params()``),
        each row's value/grad is independent of every other row, and the
        pad tail is sliced off before returning — a padded start can never
        contaminate a real candidate's objective or gradient. Chunked
        batches land on the host per chunk exactly like :meth:`run`
        (optimizer loops consume host values anyway)."""
        vg = jax.value_and_grad(fn, argnums=argnums)
        return self.run(key, vg, args, in_axes=in_axes, out_axis=0,
                        per_candidate=True, pad_rows=pad_rows)
