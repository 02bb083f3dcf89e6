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

**Chunk streaming.** Sweeps run as a host-side scan over fixed-size
candidate chunks (``chunk_size=``): one compiled executable is reused for
every chunk, and call sites that solve iteratively can thread a carry
between chunks — the RC family's steady CG warm-starts each chunk from the
previous chunk's converged states, which is what makes a B=10k steady
sweep cheaper than 20 cold B=512 sweeps. Where the chunks' results live
follows one fit rule, decided before the first chunk is dispatched: when
the whole padded output takes at most ``_RESIDENT_SHARE`` of one device's
memory (its bytes per device, from the chunk program's output shapes, ×
chunks ÷ shards, against ``memory_stats()["bytes_limit"]``; a backend that
reports no memory, as the CPU, always fits), every chunk's output stays on
the device, the dispatches queue back to back with no host sync between
them, and the chunks are joined on the device. A device array passed back
in (the observe's ``theta``) is padded and cut into chunks on the device
too, so a steady solve's states never cross to the host between the solve
and the observe. An output that does not fit lands on the host chunk by
chunk, so the device holds one chunk of it at a time: sweeps larger than
memory keep that bound.

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

import functools
import threading
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..runtime import span

# dt-keyed jit entries (one XLA executable per sampling period) are
# bounded to this many per key prefix, mirroring fidelity.evict_stale_jits
_KEEP_JITS = 8

# share of one device's memory a streamed call's joined output may take
# and still stay on the device: a quarter leaves room for the next call's
# inputs and a chunk program's temporaries (an FVM chunk of 64 candidates
# at 0.25 mm takes 555 MiB of them)
_RESIDENT_SHARE = 0.25


# The device-side helpers of a streamed call are module-level jits: one
# program per (helper, shapes, static arguments), shared by every
# executor; none is keyed on a chunk index. ``sharding`` (None off the
# mesh) places each chunk batch-sharded over the mesh, as the chunk
# programs take and give their arrays.
def _placed(x, sharding):
    return x if sharding is None else \
        lax.with_sharding_constraint(x, sharding)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _split(arr, pad_row, axis: int, n_chunks: int, chunk: int, sharding):
    """``arr`` padded along ``axis`` to ``n_chunks * chunk`` rows with
    ``pad_row`` (one batch element, broadcast) and cut into its chunks,
    all in one program. On the mesh XLA then moves only the rows each
    chunk's shards lack (collective permutes); a chunk cut from the
    batch-sharded array by its own call would all-gather the array."""
    b = arr.shape[axis]
    if n_chunks * chunk > b:
        shape = list(arr.shape)
        shape[axis] = n_chunks * chunk - b
        if pad_row.ndim:
            pad_row = jnp.expand_dims(pad_row, axis)
        arr = jnp.concatenate([arr, jnp.broadcast_to(pad_row, shape)],
                              axis=axis)
    return tuple(_placed(lax.slice_in_dim(arr, c * chunk, (c + 1) * chunk,
                                          axis=axis), sharding)
                 for c in range(n_chunks))


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _join(outs, axis: int, b: int, sharding):
    """Chunk outputs (a list of like pytrees) joined leaf-wise along
    ``axis``, with the pad tail past row ``b`` sliced off."""
    def leaf(*chunks):
        return _placed(lax.slice_in_dim(jnp.concatenate(chunks, axis), 0,
                                        b, axis=axis), sharding)
    return jax.tree_util.tree_map(leaf, *outs)


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
                 ``B > chunk_size`` stream over fixed-size chunks, joined
                 on the device when the output fits, else landed in host
                 memory chunk by chunk. Must be a multiple of the shard
                 count.
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

    def _pad(self, arr, axis: Optional[int], pad_row, b: int,
             n_chunks: int, chunk: int):
        """``arr`` made ready to cut into ``n_chunks`` chunks of ``chunk``
        rows along ``axis``. ``pad_row`` is one batch element with the
        batch axis removed (e.g. the family's ``base_params()``) repeated
        into the tail, or None for zeros. Host arrays are padded on the
        host. Device arrays are padded and cut on the device by
        ``_split`` (a tuple of chunks): pulling a whole (B, N) state to
        the host just to append a few pad rows would cost a round-trip
        of the batch."""
        b_pad = n_chunks * chunk
        if axis is None or (b_pad == b and n_chunks == 1):
            return arr
        row = np.asarray(pad_row if pad_row is not None else 0, arr.dtype)
        if isinstance(arr, jax.Array):
            return _split(arr, row, axis, n_chunks, chunk,
                          self._sharding(axis))
        if b_pad == b:
            return arr
        shape = list(arr.shape)
        shape[axis] = b_pad - b
        tail = np.broadcast_to(np.expand_dims(row, axis) if row.ndim
                               else row, shape)
        return np.concatenate([arr, tail], axis=axis)

    @staticmethod
    def _slice(arr, axis: int, start: int, length: int):
        sl = (slice(None),) * axis + (slice(start, start + length),)
        return arr[sl]

    def _chunk(self, arr, axis: Optional[int], c: int, chunk: int):
        """Chunk ``c`` of an argument ``_pad`` made ready."""
        if isinstance(arr, tuple):
            return arr[c]               # cut on the device
        if axis is None or isinstance(arr, jax.Array):
            return arr                  # not batched, or one whole chunk
        return self._slice(arr, axis, c * chunk, chunk)

    def _spec(self, axis: Optional[int]) -> P:
        if axis is None:
            return P()
        return P(*((None,) * axis), self.batch_axis)

    def _sharding(self, axis: int) -> Optional[NamedSharding]:
        """Batch-sharded placement over the mesh; None off the mesh."""
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self._spec(axis))

    # ------------------------------------------------------------------
    # the fit rule
    # ------------------------------------------------------------------
    def _budget(self) -> Optional[float]:
        """Bytes of one device's memory a streamed call's joined output
        may take on the device: ``_RESIDENT_SHARE`` of its
        ``bytes_limit``. None where the backend reports no memory (the
        CPU, whose device memory is the host's)."""
        dev = self.mesh.devices.flat[0] if self.mesh is not None \
            else jax.devices()[0]
        stats = dev.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return None
        return _RESIDENT_SHARE * stats["bytes_limit"]

    def _fits(self, jfn, args, in_axes, chunk: int, n_chunks: int,
              make_carry) -> bool:
        """Whether the call's padded output, per device, fits the budget:
        the chunk program's output bytes (traced shapes, no run) × chunks
        ÷ shards."""
        budget = self._budget()
        if budget is None:
            return True

        def at_chunk(a, ax):
            if ax is None:
                return a
            shape = list(np.shape(a))
            shape[ax] = chunk
            return jax.ShapeDtypeStruct(
                shape, jax.dtypes.canonicalize_dtype(a.dtype))

        structs = [at_chunk(a, ax) for a, ax in zip(args, in_axes)]
        if make_carry is not None:
            carry = jax.eval_shape(lambda: make_carry(chunk))
            out = jfn.eval_shape(carry, *structs)[0]
        else:
            out = jfn.eval_shape(*structs)
        nbytes = sum(leaf.size * leaf.dtype.itemsize
                     for leaf in jax.tree_util.tree_leaves(out))
        return nbytes * n_chunks / self.n_shards <= budget

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

        Returns the output with the pad tail sliced off. It stays on the
        device (batch-sharded on the mesh) for single-chunk runs and for
        streamed runs whose padded output fits ``_RESIDENT_SHARE`` of a
        device's memory (:meth:`_fits`); a streamed output that does not
        fit lands on the host chunk by chunk and returns as numpy, which
        bounds the device's share of it to one chunk.

        Spans: ``exec.run`` [``chunks``, ``resident``: the chunk count and
        whether the output stays on the device, both decided before the
        span opens] around ``exec.pad`` (host arguments padded, device
        ones padded and cut), then per chunk ``exec.dispatch``
        (``first=1`` when this call built the jitted closure) and, when
        landed, ``exec.land``; then, when streamed, ``exec.concat`` (the
        join, on the device or on the host).
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
        built = key not in self._jits
        jfn = self._compile(key, fn, in_axes, out_axis, per_candidate,
                            make_carry is not None)
        resident = n_chunks == 1 or self._fits(jfn, args, in_axes, chunk,
                                               n_chunks, make_carry)
        with span("exec.run", chunks=n_chunks, resident=int(resident)):
            with span("exec.pad"):
                padded = [self._pad(a, ax, row, b, n_chunks, chunk)
                          for a, ax, row in zip(args, in_axes, pad_rows)]

            carry = make_carry(chunk) if make_carry is not None else None
            outs = []
            for c in range(n_chunks):
                chunk_args = [self._chunk(a, ax, c, chunk)
                              for a, ax in zip(padded, in_axes)]
                with span("exec.dispatch", first=int(built and c == 0)):
                    if carry is not None:
                        out, carry = jfn(carry, *chunk_args)
                    else:
                        out = jfn(*chunk_args)
                if not resident:
                    # the device holds ONE chunk (leaf-wise for pytrees)
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
                if resident:
                    return _join(outs, out_axis, b,
                                 self._sharding(out_axis))
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
        batches follow :meth:`run`'s fit rule: joined on the device when
        they fit, else landed on the host per chunk."""
        vg = jax.value_and_grad(fn, argnums=argnums)
        return self.run(key, vg, args, in_axes=in_axes, out_axis=0,
                        per_candidate=True, pad_rows=pad_rows)
