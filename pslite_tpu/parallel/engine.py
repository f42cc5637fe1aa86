"""CollectiveEngine — the ICI data plane for dense KV push/pull.

This is the TPU-native replacement for the reference's RDMA/UCX hot path
(SURVEY §2.4, §3.2-3.4), re-architected rather than translated:

- Workers and server shards are the *same* devices of one SPMD mesh (the
  colocated/JOINT deployment, reference ``ps.h:59-76``): the ``kv`` mesh
  axis is simultaneously the worker fan-in axis and the server key-range
  sharding axis.
- ``push`` of a dense bucket is a jit-compiled ``psum_scatter`` (the
  bandwidth-optimal half of an all-reduce): each device receives the
  cross-worker **sum** of its own key range — the server-side aggregation of
  ``KVServerDefaultHandle`` (kv_app.h:430-452) executed *inside* the
  collective, on ICI, at line rate.
- The server handler (sum / assign / SGD / custom jittable fn) is fused
  between the reduce-scatter and the ``all_gather`` that implements
  ``pull`` — one XLA program per (bucket shape, dtype, op), cached exactly
  like the reference caches rendezvous addresses per (key, push, recver)
  (rdma_van.h:250-325): first touch compiles, steady state replays.
- Store shards are donated on every step, so the server state never
  double-buffers in HBM.

Zero-copy parity: ``RegisterRecvBuffer``'s "payload lands at this exact
address" contract (test_benchmark.cc:169-181) maps to donated device buffers
— the pulled array aliases the donated input's memory, no host round trip.
"""

from __future__ import annotations

import contextlib
import os
import threading
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, Optional, Union

import numpy as np

from ..utils import logging as log
from ..utils.profiling import (ENGINE_OP, LAUNCH, LAUNCH_SHIFT, launched,
                               stage_clock, stamp)
from .placement import staging_xp


@dataclass
class DenseBucket:
    """A registered dense key bucket: the unit of collective push/pull.

    Mirrors the reference benchmark's layout of ``NUM_KEY_PER_SERVER`` keys
    of ``len`` bytes each (test_benchmark.cc:407-414): ``keys[i]`` owns
    ``val_len`` consecutive values in the flat bucket vector.  A bucket
    registered with ``lens`` (the reference's ``KVPairs.lens``) keeps each
    key's own length instead, ``keys[i]`` owning ``lens[i]`` values from
    ``starts[i]``, and a flag word a key (``KEY_NO_DECAY``,
    ``KEY_NO_ADAPT``, ``KEY_ELEMENTWISE``) for a handle that treats keys
    apart (``lamb``, ``muon``); with ``shapes`` besides, each key's
    ``(rows, cols)`` for a handle that works on whole matrices (``muon``):
    over several shards such a handle has the bucket sharded on its keys'
    borders (``owned``), every matrix whole on one shard, where any other
    handle finds it cut at any element.

    ``dtype`` is the store's, the optimizer state's and every norm's;
    ``job_dtype`` what the job pushes and is handed back (one field: a
    job's gradients and parameters are of one dtype), the store's unless
    registered narrower (``mixed``: bf16 gradients in, f32 master
    parameters and moments, bf16 parameters out).
    """

    name: str
    keys: np.ndarray
    val_len: int  # 0 where the keys have lengths of their own
    dtype: object
    total_len: int  # len(keys) * val_len, or sum(lens)
    padded_len: int  # see _padded_len
    lens: Optional[np.ndarray] = None  # int64 a key
    flags: Optional[np.ndarray] = None  # int32 a key, with ``lens``
    shapes: Optional[np.ndarray] = None  # int64 (rows, cols) a key
    job_dtype: object = None  # np.dtype; None: the store's
    # Application bytes one push (or one pull) moves, in the job's dtype:
    # the byte counters' unit.
    nbytes: int = field(init=False)
    # The job's dtype is not the store's.
    mixed: bool = field(init=False, default=False)
    # Where each key begins in the flat vector (one more entry closes the
    # last), and what tells these segments from any other bucket's.
    starts: Optional[np.ndarray] = field(init=False, default=None)
    segments_key: Optional[bytes] = field(init=False, default=None)
    # ((padded_len, shards), fused_update.LambPlan) once ``lamb`` has bound
    # the bucket: ``CollectiveEngine._lamb_plan``.
    lamb_plan: Optional[tuple] = field(init=False, default=None)
    # ops.muon.MuonPlan once ``muon`` has bound the bucket (or its state
    # was asked for): ``CollectiveEngine._muon_plan``.
    muon_plan: Optional[object] = field(init=False, default=None)
    # (shards, ops.muon.OwnerPlan) of a bucket with ``shapes`` on a mesh of
    # several shards, made at registration: which key would lie whole on
    # which shard (``CollectiveEngine._owner_plan``).
    owner_plan: Optional[tuple] = field(init=False, default=None)
    # That plan once the store IS laid by it, every matrix key whole on its
    # owner (``CollectiveEngine._lay_by_owners``: when ``muon`` takes the
    # bucket); None while the store lies in key order, cut at any element.
    # ``padded_len`` is the current layout's.
    owned: Optional[object] = field(init=False, default=None)

    def __post_init__(self):
        self.job_dtype = np.dtype(
            self.dtype if self.job_dtype is None else self.job_dtype)
        self.mixed = self.job_dtype != np.dtype(self.dtype)
        self.nbytes = self.total_len * self.job_dtype.itemsize
        if self.lens is not None:
            self.starts = np.concatenate(
                [[0], np.cumsum(self.lens)]).astype(np.int32)
            self.segments_key = (self.starts.tobytes()
                                 + self.flags.tobytes())
            if self.shapes is not None:
                self.segments_key += self.shapes.tobytes()


# Flags of a key in a bucket registered with ``lens``: LAMB leaves the key
# out of the weight decay / gives it trust ratio 1; Muon leaves the key to
# AdamW (an embedding, an output head, a gain: no matrix to it).
KEY_NO_DECAY = 1
KEY_NO_ADAPT = 2
KEY_ELEMENTWISE = 4

# Optimizer state kinds whose LAST slot is the per-shard step counter
# (``adam`` and ``lamb``: m, v, the step; ``muon``: the momentum, AdamW's m
# and v over its own keys, the step).
STEP_SLOT_KINDS = ("adam", "lamb", "muon")


def step_slot(kind: str, n_slots: int) -> Optional[int]:
    """Which of a state's ``n_slots`` slots counts the steps; None where
    the kind counts none."""
    return n_slots - 1 if kind in STEP_SLOT_KINDS else None


def _lamb_ratios(sq, adapt):
    """LAMB's trust ratio a key from the keys' summed squares ``[K, 2]``
    (of p, of u): ``|p|/|u|``, and 1 for a key that is not adapted or has
    a zero norm."""
    import jax.numpy as jnp

    p_norm, u_norm = jnp.sqrt(sq[:, 0]), jnp.sqrt(sq[:, 1])
    return jnp.where(adapt & (p_norm > 0) & (u_norm > 0), p_norm / u_norm,
                     1.0)


def _padded_len(total: int, shards: int, segmented: bool) -> int:
    """A bucket's length on the devices: ``total`` rounded up to a whole
    number of elements a shard, and for a bucket with ``lens`` to whole
    tiles of the LAMB kernels a shard, so that a shard is tiled where it
    lies and no pass copies it to pad it.  The one place that knows it
    (registration and ``reshard`` ask here); no caller needs the answer:
    see :meth:`CollectiveEngine._stateful_program`."""
    unit = shards
    if segmented:
        from ..ops.fused_update import LAMB_TILE

        unit = shards * LAMB_TILE
    return -(-total // unit) * unit


ServerHandle = Union[str, Callable]


@dataclass(eq=False, slots=True)
class _BoundOp:
    """What every ``push_pull`` (or ``push``) of one bucket under one
    handle works out the same way, worked out once by
    ``CollectiveEngine._bind``.  Invariants only: never a store, a state
    array or a gradient."""

    op: str  # "push_pull" or "push"
    bucket: DenseBucket
    lock: threading.Lock  # the bucket's write lock
    prog: Callable
    # One of _prep_grads / _prep_grads_whole / _prep_grads_flat.
    prep: Callable
    sharding: object  # what ``prep`` delivers (and passes through as is)
    state_kind: Optional[str]  # the optimizer state's kind; None: stateless
    zc: bool  # in-place pull delivery
    # The pulled array carries padding to slice off (a program shared by
    # every bucket of a length; a bucket's own cuts inside: _bind).
    cut: bool
    # The last integer of the op's LAUNCH note (``profiling.launched``): the
    # arrays ``prog`` takes and gives, over the op's kind.
    launched: int


# The same of the ops that are not bound: what they count themselves goes
# over these.
_PUSH_PULL, _PULL = launched("dense.push_pull", 0), launched("dense.pull", 0)


def _aggregate(grads_l, axis, worker_axis=None):
    """Worker-reduction of a local grads block — psum_scatter on the 1-D
    colocated layout (reduce+shard in one hop), psum over the worker axis
    on a 2-D layout (the kv sharding is already in the data layout).

    The three phases of a program carry ``jax.named_scope``s
    (``ps.push.reduce`` here, ``ps.update``, ``ps.pull.gather``): metadata
    on the operations, by which a device trace is read."""
    import jax
    from jax import lax

    with jax.named_scope("ps.push.reduce"):
        if worker_axis is None:
            return lax.psum_scatter(
                grads_l[0], axis, scatter_dimension=0, tiled=True
            )
        return lax.psum(grads_l[0], worker_axis)


def _aggregate_whole(rows_l, shard_len: int, shards: int, axis,
                     worker_axis=None):
    """The worker reduction in a bucket's own program
    (:meth:`CollectiveEngine._stateful_program`): ``rows_l`` is the local
    ``[1, total]`` block of the gradient as the job has it.  Returns this
    shard's part as a row: ``[1, shard_len]`` of the gradient padded with
    zeros where the bucket lies over several shards, and on one shard the
    row as it came, ``[1, total]``, since nothing has to be cut and a pad
    is a copy of the whole gradient.  A VECTOR ``[shards * shard_len]``
    (a bucket that lies by its owner plan hands its row over laid out and
    flat: :meth:`CollectiveEngine._stateful_program`) is summed and cut as
    the vector it is: as a row the chip keeps a third copy of the tree
    through the sum."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    with jax.named_scope("ps.push.reduce"):
        if rows_l.ndim == 1:
            if worker_axis is None:
                return lax.psum_scatter(rows_l, axis, scatter_dimension=0,
                                        tiled=True).reshape(1, -1)
            rows_l = rows_l.reshape(1, -1)
        if shards * shard_len > rows_l.shape[1] and shards > 1:
            rows_l = jnp.pad(
                rows_l,
                ((0, 0), (0, shards * shard_len - rows_l.shape[1])))
        if worker_axis is None:
            return lax.psum_scatter(rows_l, axis, scatter_dimension=1,
                                    tiled=True)
        if shards > 1:
            # Every kv position of a worker holds the worker's whole row.
            rows_l = lax.dynamic_slice_in_dim(
                rows_l, lax.axis_index(axis) * shard_len, shard_len, axis=1)
        return lax.psum(rows_l, worker_axis)


def _zero_filled(sfn):
    """An element-wise stateful handle in a bucket's own program: it takes
    the shard whole, so the row of :func:`_aggregate_whole` is filled with
    zeros to the shard's length (a zero gradient leaves the padding's p, m
    and v as they are)."""
    import jax.numpy as jnp

    def fn(store_l, state_l, row):
        pad = store_l.shape[0] - row.shape[1]
        return sfn(store_l, state_l, jnp.pad(row, ((0, 0), (0, pad)))[0])

    return fn


def _widened(rows_l, dtype, width: int):
    """The gradient of a mixed bucket, widened outside a kernel: ``rows_l``
    is one worker's whole gradient, the row ``[1, total]`` of the job's
    dtype, widened exactly to the store's ``dtype`` and filled with zeros
    to ``width`` (what :func:`_aggregate_whole` would pad it to: one pass,
    and the trace tells whose) before it is summed: the sum over W is
    taken in the store's precision, never in the job's.  (Where there is
    nothing to sum or to cut, one worker on one shard, the row goes to the
    handle as it came and its kernel widens it in VMEM.)  Under
    ``ps.push.widen``."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("ps.push.widen"):
        return jnp.pad(rows_l.astype(dtype),
                       ((0, 0), (0, width - rows_l.shape[1])))


def _narrowed(store_l, dtype):
    """A shard of the store rounded to the job's ``dtype`` (to nearest,
    ties to even: ``astype``), before it is gathered: what a mixed bucket
    pulls where no kernel wrote the pulled values itself.  Under
    ``ps.pull.narrow``."""
    import jax

    with jax.named_scope("ps.pull.narrow"):
        return store_l.astype(dtype)


def _update(handle, *args):
    """The server handle (stateless ``handle(store, agg)`` or stateful
    ``sfn(store, state, agg)``) under its ``ps.update`` scope."""
    import jax

    with jax.named_scope("ps.update"):
        return handle(*args)


def _gather(store_l, axis):
    """The pull: all-gather of the store shards, under ``ps.pull.gather``."""
    import jax
    from jax import lax

    with jax.named_scope("ps.pull.gather"):
        return lax.all_gather(store_l, axis, tiled=True)


def _rs_update_ag(store_l, grads_l, handle, axis, worker_axis=None):
    """The core per-bucket aggregation semantics shared by the single and
    grouped programs: reduce(-scatter) across workers, apply the server
    handle to this shard, all-gather the updated store (push=aggregate,
    update, pull — kv_app.h:430-452 fused into the collectives).

    See :func:`_aggregate` for the 1-D vs 2-D reduction shapes."""
    agg = _aggregate(grads_l, axis, worker_axis)
    new_store = _update(handle, store_l, agg)
    return new_store, _gather(new_store, axis)


class CollectiveEngine:
    """Dense KV push/pull over one mesh axis.

    ``grads`` arguments are globally shaped ``[W, total_len]`` (row w = the
    gradient contributed by worker shard w), sharded ``P(axis, None)``; the
    store is ``[padded_len]`` sharded ``P(axis)``.  All ops are async
    (jax dispatch); ``block()`` or Customer wait-hooks give ZPush/Wait
    semantics.
    """

    def __init__(
        self,
        mesh=None,
        axis_name: str = "kv",
        server_handle: ServerHandle = "sum",
        worker_axis: Optional[str] = None,
    ):
        """``worker_axis``: optional second mesh axis carrying the worker
        fan-in, decoupling worker count from server-shard count (the
        reference's W workers vs S servers asymmetry, on the collective
        path).  With a 2-D mesh ``(dp, kv)``: gradients are summed over
        ``dp`` (the worker reduction) and scattered over ``kv`` (the
        server key-range sharding); stores live sharded over ``kv``,
        replicated over ``dp``.  Default None = the 1-D colocated layout
        where the one axis is both."""
        import jax

        from .mesh import default_mesh

        from .placement import local_shard_count, mesh_is_multiprocess

        if isinstance(axis_name, (tuple, list)):
            # MULTI-AXIS kv plane (>=3-D torus with worker_axis): the
            # store shards over the PRODUCT of these axes
            # (P(("kv1","kv2"))) and the pulled broadcast gathers over
            # both: with the psum over the worker axis, one push_pull
            # drives all three torus axes' links (the reference's 32
            # ports/devices per node, message.h:66-134, ucx_van.h:938-
            # 1006; v5p pods are 3-D tori).
            axis_name = tuple(axis_name)
            log.check(len(axis_name) >= 1, "empty kv axis tuple")
            for a in axis_name:
                log.check(a in (mesh.axis_names if mesh is not None
                                else ()),
                          f"kv axis {a!r} not in mesh (tuple axes "
                          f"require an explicit mesh)")
        self.mesh = mesh if mesh is not None else default_mesh(axis_name)
        self.axis = axis_name
        self.worker_axis = worker_axis
        kv_axes = (
            axis_name if isinstance(axis_name, tuple) else (axis_name,)
        )
        if worker_axis is not None:
            log.check(worker_axis in self.mesh.axis_names,
                      f"worker axis {worker_axis!r} not in mesh")
            log.check(worker_axis not in kv_axes,
                      "worker_axis must differ from the kv axis (leave it "
                      "None for the 1-D colocated layout)")
        self.num_shards = int(
            np.prod([self.mesh.shape[a] for a in kv_axes])
        )
        # Worker fan-in rows of the grads array.
        self.num_workers = (
            self.mesh.shape[worker_axis] if worker_axis is not None
            else self.num_shards
        )
        # Fixed at construction; cached off the hot path.
        self._multiprocess = mesh_is_multiprocess(self.mesh)
        self._mesh_platform = next(
            iter(self.mesh.devices.flat)
        ).platform
        # THE interpret rule for every Pallas kernel this engine builds
        # (the fused optimizer handles): a TPU mesh gets
        # compiled Mosaic, always — including an AOT topology mesh built
        # from a CPU-default process — and only a mesh that is not TPU
        # runs the Pallas interpreter.  The process default backend is
        # never consulted: a process whose TPU init failed must not
        # interpret its way to a passing run.
        self._interpret = self._mesh_platform != "tpu"
        self._local_shard_count = (
            local_shard_count(self.mesh) if self._multiprocess
            else self.num_shards
        )
        # Per-step payload threshold for the flat replay slab layout
        # (see _flat_replay); tunable for tests / unusual chips.
        self.replay_flat_min_bytes = int(
            os.environ.get("PS_REPLAY_FLAT_MIN_BYTES", 1 << 20)
        )
        self._server_handle = server_handle
        self._buckets: Dict[str, DenseBucket] = {}
        self._stores: Dict[str, jax.Array] = {}
        # Optimizer state for stateful server handles (sgd_momentum: mom;
        # adam: m, v, step), sharded like the store and donated each step.
        self._opt_states: Dict[str, tuple] = {}
        self._opt_kinds: Dict[str, str] = {}
        # Pinned pull-output buffers (PinMemory / w_pool_ analog,
        # ucx_van.h:603-623): pulls for a registered bucket land in the
        # same HBM buffer every time via donation of the previous output.
        self._pinned_pulls: Dict[str, object] = {}
        self._programs: Dict[tuple, Callable] = {}
        # (name, handle, zero_copy; None: push) -> see _bind.  Dropped
        # wherever _programs is, and for a name that is registered again.
        self._bound: Dict[tuple, _BoundOp] = {}
        self._mu = threading.Lock()
        # Per-bucket write locks: the jitted programs donate the store
        # buffer, so the load-run-store sequence must be atomic per bucket
        # (two concurrent pushes of one bucket would otherwise hand the
        # same donated buffer to two programs).  Per-bucket rather than
        # engine-wide so different buckets still dispatch concurrently.
        self._bucket_mu: Dict[str, threading.Lock] = {}
        # Observability (reference: van.h:183-184 byte counters):
        # application-payload bytes moved through the collective data
        # plane, surfaced next to Van.send_bytes/recv_bytes; host time per
        # stage of an op goes to the process's StageClock.
        self._clock = stage_clock()
        # An op notes (ENGINE_OP, t_end, select ns, prep ns, launch ns) and,
        # before it, what its launch was made of (LAUNCH): a C call each,
        # whatever the op groups or replays (see StageClock).
        self._note = self._clock.note
        self.push_bytes = 0
        self.pull_bytes = 0
        self._counter_mu = threading.Lock()
        # Ops whose program applied LAMB (``engine.update.lamb``).
        self.lamb_updates = 0
        # ... whose pulled values ``lamb_apply`` wrote
        # (``engine.pull.from_kernel``).
        self.kernel_pulls = 0
        # The elements the last of them updated in one pass
        # (``engine.update.lamb.one_pass``).
        self.lamb_one_pass = 0
        # Ops on a bucket whose job dtype is narrower than its store's
        # (``engine.dense.narrow``).
        self.narrow_ops = 0
        # Ops whose program applied Muon (``engine.update.muon``), and
        # what a step of the last of them holds by its plan: matrices, the
        # keys whose gradient a kernel takes from the row, the keys whose
        # new values a kernel writes, and Newton-Schulz FLOPs
        # (``engine.update.muon.matrices``, ``.row_keys``, ``.apply_keys``,
        # ``.ns_flops``).
        self.muon_updates = 0
        self.muon_matrices = 0
        self.muon_row_keys = 0
        self.muon_apply_keys = 0
        self.muon_ns_flops = 0.0
        # ... and over several shards, by the owner plan: the shards that
        # own a matrix, and the fullest owner's Newton-Schulz FLOPs over
        # the owners' mean, x1000 (``engine.update.muon.owners``,
        # ``.owner_flops``; 1 and 1000 on one shard).
        self.muon_owners = 0
        self.muon_owner_flops = 0

    # -- registration --------------------------------------------------------

    def register_dense(
        self,
        name: str,
        keys,
        val_len: Optional[int] = None,
        dtype=None,
        init: Optional[np.ndarray] = None,
        lens=None,
        flags=None,
        job_dtype=None,
        shapes=None,
    ) -> DenseBucket:
        """Register a dense bucket and allocate its sharded store.

        This is the moment the reference performs rendezvous + memory
        registration (rdma_van.h:520-548); here it allocates the sharded
        HBM store and (lazily) compiles the bucket's programs.

        ``val_len`` values a key, or ``lens``: a length for each key (the
        reference's ``KVPairs.lens``), and with them ``flags``, a word a
        key of ``KEY_NO_DECAY`` / ``KEY_NO_ADAPT`` / ``KEY_ELEMENTWISE``
        (default 0), and ``shapes``, ``(rows, cols)`` a key with
        ``rows * cols == len`` (a vector: ``(1, len)``), which a handle
        that works on whole matrices reads (``muon``).

        ``job_dtype`` (default ``dtype``): what the job pushes and what
        ``push_pull`` and ``pull`` hand back, where that is narrower than
        the store (``dtype=float32, job_dtype=bfloat16``: mixed-precision
        training with the master copy on the server).  The store, the
        optimizer state, the sum over W and every norm stay ``dtype``; a
        gradient is widened exactly; a pulled value is the stored one
        rounded to nearest-even, ``store[:total].astype(job_dtype)`` bit
        for bit.  Served by ``push_pull``, ``push`` and ``pull`` on the
        bucket's own program: a bucket with ``lens`` under a stateful
        handle (:meth:`_mixed_refusal` names what else asks for it).
        """
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if dtype is None:
            dtype = jnp.float32
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        log.check((val_len is None) != (lens is None),
                  f"bucket {name!r}: give val_len (one length for every "
                  f"key) or lens (a length a key), not both or neither")
        if lens is None:
            log.check(flags is None,
                      f"bucket {name!r}: per-key flags need per-key lens")
            total = len(keys) * val_len
        else:
            lens = np.ascontiguousarray(np.asarray(lens, dtype=np.int64))
            log.check(lens.shape == keys.shape and (lens >= 0).all(),
                      f"bucket {name!r}: lens must give one length >= 0 "
                      f"for each of the {len(keys)} keys, got shape "
                      f"{lens.shape}")
            flags = np.ascontiguousarray(np.asarray(
                np.zeros(len(keys)) if flags is None else flags,
                dtype=np.int32))
            log.check(flags.shape == keys.shape,
                      f"bucket {name!r}: flags must give one word for each "
                      f"of the {len(keys)} keys, got shape {flags.shape}")
            val_len, total = 0, int(lens.sum())
            log.check(total < 2 ** 31,
                      f"bucket {name!r}: {total:,} values; the keys' "
                      f"borders are kept as int32")
        if shapes is not None:
            log.check(lens is not None,
                      f"bucket {name!r}: per-key shapes need per-key lens")
            shapes = np.ascontiguousarray(np.asarray(shapes, dtype=np.int64))
            log.check(shapes.shape == (len(keys), 2) and (shapes >= 0).all(),
                      f"bucket {name!r}: shapes must give (rows, cols) for "
                      f"each of the {len(keys)} keys, got shape "
                      f"{shapes.shape}")
            off = np.flatnonzero(shapes[:, 0] * shapes[:, 1] != lens)
            log.check(off.size == 0,
                      f"bucket {name!r}: rows * cols must be the key's len; "
                      f"key {off[:1].tolist()} has shape "
                      f"{shapes[off[:1]].tolist()} and len "
                      f"{lens[off[:1]].tolist()}")
        if job_dtype is not None and np.dtype(job_dtype) != np.dtype(dtype):
            job, own = np.dtype(job_dtype), np.dtype(dtype)
            log.check(
                jnp.issubdtype(job, jnp.floating)
                and jnp.issubdtype(own, jnp.floating)
                and job.itemsize < own.itemsize,
                f"bucket {name!r}: job_dtype {job} over a {own} store: the "
                f"job's dtype is the store's or a narrower float (gradients "
                f"are widened exactly, parameters rounded on the way out)")
            log.check(lens is not None, self._mixed_refusal(
                name, job, own,
                "a bucket registered with one val_len for all its keys "
                "(its programs are shared by every bucket of a length)",
                "register it with lens="))
        if init is not None:
            log.check_eq(int(np.size(init)), total,
                         f"bucket {name!r}: init must hold one value for "
                         f"each of the keys' values")
        padded = _padded_len(total, self.num_shards, lens is not None)
        bucket = DenseBucket(
            name=name,
            keys=keys,
            val_len=val_len,
            dtype=dtype,
            total_len=total,
            padded_len=padded,
            lens=lens,
            flags=flags,
            job_dtype=job_dtype,
            shapes=shapes,
        )
        if shapes is not None:
            self._owner_plan(bucket)
        sharding = NamedSharding(self.mesh, P(self.axis))
        if init is not None:
            flat = np.zeros(padded, dtype=np.dtype(dtype))
            flat[:total] = np.asarray(init).reshape(-1)
            store = self._place(flat, sharding)
        elif self._is_multiprocess():
            store = self._place(np.zeros(padded, np.dtype(dtype)), sharding)
        else:
            # Each device zero-fills its own shard; the whole store never
            # exists on one device.
            store = jnp.zeros(padded, dtype=dtype, device=sharding)
        with self._mu:
            self._buckets[name] = bucket
            self._stores[name] = store
            self._bucket_mu.setdefault(name, threading.Lock())
            for key in [k for k in self._bound if k[0] == name]:
                del self._bound[key]
        return bucket

    def bucket(self, name: str) -> DenseBucket:
        return self._buckets[name]

    # -- compiled programs ---------------------------------------------------

    def _resolved_handle_fn(self, handle_key) -> Callable:
        """The handle fn for a program cache key ("_default" resolves to
        the engine's configured server handle) — the one definition of
        that sentinel rule."""
        return self._handle_fn(
            self._server_handle if handle_key == "_default" else handle_key
        )

    def _handle_fn(self, handle: ServerHandle) -> Callable:
        """Server-side update applied to (store_shard, aggregated_grads)."""
        if callable(handle):
            return handle
        if handle == "sum":
            return lambda store, agg: store + agg
        if handle == "assign":
            return lambda store, agg: agg
        if self._is_stateful(handle):
            raise ValueError(
                f"{handle!r} is stateful — resolved via _stateful_handle"
            )
        if handle.startswith("sgd"):
            lr = float(handle.split(":", 1)[1]) if ":" in handle else 0.01
            return lambda store, agg: store - lr * agg
        raise ValueError(f"unknown server handle {handle!r}")

    @staticmethod
    def _handle_params(handle: str, defaults):
        parts = handle.split(":", 1)
        vals = list(defaults)
        if len(parts) == 2 and parts[1]:
            toks = parts[1].split(",")
            log.check(
                len(toks) <= len(vals),
                f"handle {handle!r} has {len(toks)} parameters but at "
                f"most {len(vals)} are supported",
            )
            for i, tok in enumerate(toks):
                vals[i] = float(tok)
        return vals

    def _stateful_handle(self, handle: str,
                         bucket: Optional[DenseBucket] = None):
        """(n_state, fn) for the fused-kernel server handles.

        ``fn(store_l, state_l, agg) -> (new_store_l, new_state_l)`` runs
        per shard inside shard_map, applying the whole optimizer step as
        one Pallas pass over the shard (the aggregation hot loop of
        kv_app.h:430-452 fused with the reduce-scatter's output).
        ``lamb`` and ``muon`` are told the ``bucket`` whose keys' borders
        (and shapes) they need; ``muon``'s state is laid out by its plan
        (:meth:`_state_shapes`), not as slots of the store's shape.
        """
        from ..ops import fused_update

        interp = self._interpret
        if self._needs_segments(handle):
            log.check(bucket is not None and bucket.lens is not None,
                      self._segments_refusal(handle, bucket))
            if handle.startswith("muon"):
                fn = self._muon_fn(handle, bucket)
                return self._n_state(handle, bucket), fn
            return 3, self._lamb_fn(handle, bucket)
        if handle.startswith("sgd_momentum"):
            lr, momentum = self._handle_params(handle, (0.01, 0.9))

            def fn(store_l, state_l, agg):
                new_store, new_mom = fused_update.sgd_update(
                    store_l, state_l[0], agg, lr=lr, momentum=momentum,
                    interpret=interp,
                )
                return new_store, (new_mom,)

            return 1, fn
        if handle.startswith("adam"):
            lr, b1, b2, eps = self._handle_params(
                handle, (1e-3, 0.9, 0.999, 1e-8)
            )

            def fn(store_l, state_l, agg):
                m_l, v_l, step_l = state_l
                step = step_l[0] + 1.0
                new_store, new_m, new_v = fused_update.adam_update(
                    store_l, m_l, v_l, agg, step, lr=lr,
                    beta1=b1, beta2=b2, eps=eps, interpret=interp,
                )
                return new_store, (new_m, new_v, step_l + 1.0)

            return 3, fn
        if handle.startswith("adagrad"):
            lr, eps = self._handle_params(handle, (0.01, 1e-8))

            def fn(store_l, state_l, agg):
                new_store, new_acc = fused_update.adagrad_update(
                    store_l, state_l[0], agg, lr=lr, eps=eps,
                    interpret=interp,
                )
                return new_store, (new_acc,)

            return 1, fn
        raise ValueError(f"not a stateful handle: {handle!r}")

    def _lamb_fn(self, handle: str, bucket: DenseBucket) -> Callable:
        """``lamb:lr,b1,b2,eps,wd`` on one shard of ``bucket`` (You et
        al. 2020, with Adam's bias correction), for key k::

            m = b1*m + (1-b1)*g;  v = b2*v + (1-b2)*g*g
            u = mh/(sqrt(vh)+eps) + wd_k*p     (wd_k 0 for KEY_NO_DECAY)
            r_k = |p|/|u|, or 1 for KEY_NO_ADAPT or where a norm is 0
            p = p - lr*r_k*u

        No element of a key may be written before every element's ``u``
        is known, on every shard.  Over several shards that is two passes
        with a reduction between them (``lamb_moments``, a ``psum`` of the
        keys' sums, ``lamb_apply``).  Where one shard holds the bucket a
        norm needs no ``psum``, and every key that VMEM can hold between
        its first element and its last (``fused_update.lamb_plan``; what
        decides is the keys' lengths, known here) is updated in one pass
        by ``lamb_one_pass``, which takes ``lamb_apply``'s place and name
        in the program, reads the gradient besides and makes that key's
        ratio itself; ``lamb_moments`` then walks the tiles of the larger
        keys alone, and is left out where there are none.  The norms are over the key's own elements wherever they lie
        (shard and tile borders are not the keys') and never over the
        padding.  ``agg`` is a row, as :func:`_aggregate_whole` leaves it,
        of the store's dtype or, where a mixed bucket's gradient passes
        (:func:`_widened`), of the job's: the kernel that reads it widens
        it.

        ``fn`` takes one argument more, ``pulled_len``: with it
        (``total_len``, where this shard holds the whole bucket:
        :meth:`_kernel_pulls`) that kernel writes the new parameters
        twice, in place and as a vector ``[pulled_len]`` of its own, and
        ``fn`` returns that as a third value: the pulled values, which a
        cut of the store after the kernel would read and write once
        more.  They are of the bucket's job dtype: on a mixed bucket the
        kernel rounds each value it has just stored."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        from ..ops import fused_update

        lr, b1, b2, eps, wd = self._handle_params(
            handle, (1e-3, 0.9, 0.999, 1e-6, 0.01))
        interp, axis = self._interpret, self.axis
        starts = bucket.starts
        decay = np.where(bucket.flags & KEY_NO_DECAY, 0.0, wd).astype(
            np.float32)
        adapt = (bucket.flags & KEY_NO_ADAPT) == 0
        n_keys = len(bucket.keys)
        plan = self._lamb_plan(bucket)
        kw = dict(beta1=b1, beta2=b2, eps=eps, interpret=interp)
        pulled_dtype = bucket.job_dtype if bucket.mixed else None

        def fn(store_l, state_l, agg, pulled_len=0):
            new_m, new_v, step_l = state_l
            step = step_l[0] + 1.0
            shard = lax.axis_index(axis)
            base = (shard * store_l.shape[0]).astype(jnp.int32).reshape(1)
            mine = lax.dynamic_index_in_dim(jnp.asarray(plan.blocks), shard,
                                            keepdims=False)
            keys = (step, starts, decay)
            scale = jnp.zeros(n_keys, jnp.float32)
            if plan.tiles.size:
                with jax.named_scope("ps.update.lamb.moments"):
                    new_m, new_v, sums = fused_update.lamb_moments(
                        store_l, new_m, new_v, agg, *keys, mine, base,
                        plan.tiles, **kw)
                with jax.named_scope("ps.update.lamb.norms"):
                    sq = lax.psum(sums.reshape(n_keys, 2), axis)
                    scale = (lr * _lamb_ratios(sq, adapt)).astype(
                        jnp.float32)
            kw_pulled = dict(kw, pulled_len=pulled_len,
                             pulled_dtype=pulled_dtype)
            with jax.named_scope("ps.update.lamb.apply"):
                if plan.one_pass_len:
                    new_store, new_m, new_v, pulled = (
                        fused_update.lamb_one_pass(
                            store_l, new_m, new_v, agg, *keys, scale, mine,
                            base, plan.held.astype(np.int32),
                            adapt.astype(np.int32), plan.walked,
                            plan.stepped, lr=lr, lag=plan.lag, **kw_pulled))
                else:
                    new_store, pulled = fused_update.lamb_apply(
                        store_l, new_m, new_v, *keys, scale, mine, base,
                        **kw_pulled)
            new_state = (new_m, new_v, step_l + 1.0)
            if pulled is None:
                return new_store, new_state
            return new_store, new_state, pulled

        return fn

    def _muon_fn(self, handle: str, bucket: DenseBucket) -> Callable:
        """``muon:lr,mu,wd,b1,b2,eps`` on a shard of ``bucket``
        (``ops/muon.py`` has the recurrence): a matrix key is
        updated by its orthogonalised momentum, five Newton-Schulz steps
        in bfloat16 with Nesterov and the published coefficients (the
        optimizer's, no parameters), a key flagged ``KEY_ELEMENTWISE`` by
        AdamW.  It needs what no flat bucket says, each key's shape, and
        every matrix whole where its products run; where it cannot run it
        says so by name (:meth:`_muon_refusal`) and nothing falls back to
        an element-wise update.  Over several shards the bucket lies by
        its owner plan (``bucket.owned``, :meth:`_lay_by_owners`): every
        shard runs the one update over its own slots, and what the shape
        classes leave over runs in the branch of the owner that has it
        (``ops.muon.owner_plan``).  The new parameters are written where
        they lie in the store, and with ``pulled_len`` (as
        :meth:`_lamb_fn` takes it, where :meth:`_kernel_pulls` says so)
        the kernels that write them leave them once more as a vector of
        their own, which ``fn`` hands back third: the pulled values.
        What ``fn`` traces is found by the handle's numbers and the keys'
        shapes and flags (``call_traced``)."""
        from ..ops import muon
        from ..utils.compile_cache import call_traced

        refusal = self._muon_refusal(handle, bucket)
        log.check(refusal is None, refusal)
        lr, mu, wd, b1, b2, eps = self._handle_params(
            handle, (1e-3, 0.95, 0.1, 0.9, 0.95, 1e-8))
        interp, axis, owners = self._interpret, self.axis, bucket.owned
        if owners is None:
            plan = self._muon_plan(bucket)
            starts, shapes = bucket.starts, bucket.shapes
            elementwise = (bucket.flags & KEY_ELEMENTWISE) != 0
            layout = (np.asarray(shapes).tolist(), elementwise.tolist())
        else:
            plan, starts, shapes = owners.plan, owners.starts, owners.shapes
            layout = (starts.tolist(), shapes.tolist(), repr(plan.chunks),
                      repr(owners.branches))

        def fn(store_l, state_l, agg, pulled_len=0):
            import jax.numpy as jnp
            from jax import lax

            def update(store_l, agg, *state_l):
                rest = None
                if owners is not None and owners.rest:
                    *state_l, which = state_l
                    rest = (owners.branches, which[0])
                return muon.muon_update(
                    store_l, state_l, agg, starts, shapes, plan, lr=lr,
                    mu=mu, wd=wd, b1=b1, b2=b2, eps=eps,
                    pulled_len=pulled_len, interpret=interp, rest=rest)

            if owners is not None and owners.rest:
                # The branch this owner takes, as an array: ``update`` is
                # traced alone, outside the mesh.
                state_l = (*state_l, jnp.asarray(
                    owners.branch_of, jnp.int32)[lax.axis_index(axis)
                                                 ].reshape(1))
            if interp:
                return update(store_l, agg, *state_l)
            # Compiled for the chip, the step's trace is kept between
            # processes as its kernels' are in ``ops/row_add.py``: dozens
            # of Mosaic kernels are seconds of tracing on the chip's host,
            # each run, before the compile cache can be asked.
            return call_traced(
                update, muon.__file__, "tpu", store_l, agg, *state_l,
                static=(lr, mu, wd, b1, b2, eps, pulled_len, *layout))

        return fn

    def _muon_refusal(self, handle, bucket: DenseBucket) -> Optional[str]:
        """Why ``muon`` cannot run on ``bucket`` as it lies on this mesh,
        with what is missing; None where it can."""
        said = f"handle {handle!r} works on whole matrices"
        if bucket.shapes is None:
            return (f"{said} and needs each key's (rows, cols), which "
                    f"bucket {bucket.name!r}, registered without shapes=, "
                    f"does not say: register it with lens= and shapes=")
        if bucket.mixed:
            return (f"{said} in f32, and bucket {bucket.name!r} is pushed "
                    f"and pulled in {bucket.job_dtype} over its "
                    f"{np.dtype(bucket.dtype)} store, a contract "
                    f"(register_dense(..., job_dtype=)) that only the LAMB "
                    f"kernels carry: register it with job_dtype= left out")
        if np.dtype(bucket.dtype) != np.float32:
            return (f"{said} with f32 momentum and master weights, and "
                    f"bucket {bucket.name!r} is kept in "
                    f"{np.dtype(bucket.dtype)}: register it with "
                    f"dtype=float32")
        return None

    def _muon_plan(self, bucket: DenseBucket):
        """``ops.muon.muon_plan`` of ``bucket``, made once: what
        :meth:`_muon_fn` builds the program from, the state is laid out
        by and the ``engine.update.muon.*`` gauges read."""
        from ..ops.muon import muon_plan

        if bucket.muon_plan is None:
            bucket.muon_plan = muon_plan(
                bucket.shapes, (bucket.flags & KEY_ELEMENTWISE) != 0)
        return bucket.muon_plan

    def _owner_plan(self, bucket: DenseBucket):
        """``ops.muon.owner_plan`` of ``bucket`` over this mesh's shards,
        made once for a shard count (``reshard`` gives the mesh others):
        which key lies whole on which shard, at which offset, and the
        padded length, for a handle that needs its keys whole.  None on
        one shard, where the plan is the identity and the bucket lies in
        key order, and for a bucket without ``shapes``."""
        from ..ops.muon import owner_plan

        if self.num_shards == 1 or bucket.shapes is None:
            return None
        if (bucket.owner_plan is None
                or bucket.owner_plan[0] != self.num_shards):
            bucket.owner_plan = (self.num_shards, owner_plan(
                bucket.shapes, (bucket.flags & KEY_ELEMENTWISE) != 0,
                self.num_shards))
        return bucket.owner_plan[1]

    def _relaid(self, bucket: DenseBucket, store, owners):
        """``store`` (``bucket``'s, on the device, sharded, in key order
        with whatever lies behind ``total_len``) laid by ``owners``: every
        shard gathers the tree, lays it out and keeps its own part (a
        program of its own, run when a layout changes, never in a step)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..ops.muon import place

        axis = self.axis

        def body(store_l):
            tree = lax.all_gather(store_l, axis, tiled=True)
            # (What lies behind ``total_len`` is in no run of the plan.)
            placed = place(owners, tree, jnp)
            return lax.dynamic_slice_in_dim(
                placed, lax.axis_index(axis) * owners.shard_len,
                owners.shard_len)

        return jax.jit(jax.shard_map(
            body, mesh=self.mesh, in_specs=P(axis), out_specs=P(axis),
            check_vma=False))(store)

    def _in_key_order(self, bucket: DenseBucket, store):
        """The inverse: an owned bucket's store ``[total_len]`` in key
        order, on every device."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from ..ops.muon import unplace

        axis, owners = self.axis, bucket.owned
        return jax.jit(jax.shard_map(
            lambda store_l: unplace(
                owners, lax.all_gather(store_l, axis, tiled=True), jnp),
            mesh=self.mesh, in_specs=P(axis), out_specs=P(None),
            check_vma=False))(store)

    def _lay_by_owners(self, bucket: DenseBucket) -> None:
        """Lay ``bucket``'s store by its owner plan, once: a handle that
        needs its keys whole takes the bucket for good (a bucket has one
        kind of state), so from here on every matrix key lies whole on one
        shard and ``padded_len`` is the plan's.  What leaves the bucket
        (``store_array``, ``opt_state``, ``pull``, a checkpoint) stays in
        key order.  Nothing on one shard."""
        owners = self._owner_plan(bucket)
        if owners is None or bucket.owned is not None:
            return
        name = bucket.name
        with self._bucket_mu[name]:
            if bucket.owned is not None:
                return
            have = self._opt_kinds.get(name)
            log.check(have is None,
                      f"bucket {name!r} already has {have!r} state over a "
                      f"store cut at any element; cannot switch to a handle "
                      f"that has it sharded on its keys' borders")
            log.check(name not in self._pinned_pulls, self._owned_refusal(
                name, "a pinned pull buffer (a padded-length buffer in the "
                "store's own order)", "unregister it"))
            self._stores[name] = self._relaid(bucket, self._stores[name],
                                              owners)
            bucket.owned = owners
            bucket.padded_len = owners.padded_len
        with self._mu:
            for key in [k for k in self._bound if k[0] == name]:
                del self._bound[key]

    @staticmethod
    def _owned_refusal(name: str, what: str, instead: str) -> str:
        """Why ``what`` does not serve a bucket that lies by its owner
        plan, and what does."""
        return (f"bucket {name!r} is sharded on its keys' borders (a handle "
                f"that works on whole matrices has taken it over several "
                f"shards), which only the bucket's own programs serve "
                f"(push_pull and push under that handle, pull); {what} "
                f"would read it in key order: {instead}")

    @staticmethod
    def _is_stateful(handle) -> bool:
        return isinstance(handle, str) and (
            handle.startswith("sgd_momentum")
            or handle.startswith("adam")
            or handle.startswith("adagrad")
            or handle.startswith("lamb")
            or handle.startswith("muon")
        )

    @staticmethod
    def _needs_segments(handle) -> bool:
        """Whether the handle's update of an element depends on which key
        the element belongs to."""
        return isinstance(handle, str) and (handle.startswith("lamb")
                                            or handle.startswith("muon"))

    @staticmethod
    def _segments_refusal(handle, bucket: Optional[DenseBucket]) -> str:
        where = ("a call that carries no bucket (replay and the streams "
                 "compile one program for any bucket of a length)"
                 if bucket is None else
                 f"bucket {bucket.name!r}, registered with one val_len for "
                 f"all its keys")
        return (f"handle {handle!r} treats each key apart (a norm over the "
                f"key, the key as a matrix) and needs the keys' own "
                f"lengths, which {where} does not have: register the "
                f"bucket with lens= and use push_pull or push")

    @staticmethod
    def _mixed_refusal(name: str, job, own, what: str, instead: str) -> str:
        """Why ``what`` does not serve a bucket whose job dtype is narrower
        than its store's, and what does."""
        return (f"bucket {name!r} is pushed and pulled in {np.dtype(job)} "
                f"over a {np.dtype(own)} store, which only the bucket's own "
                f"program serves (lens= and a stateful handle, by push_pull, "
                f"push or pull); {what} would have to cast on the way: "
                f"{instead}")

    def _refuse_mixed(self, bucket: DenseBucket, what: str,
                      instead: str = "use push_pull, push or pull") -> None:
        log.check(not bucket.mixed, self._mixed_refusal(
            bucket.name, bucket.job_dtype, bucket.dtype, what, instead))

    @property
    def handle_is_stateful(self) -> bool:
        """Whether the engine's default server handle carries optimizer
        state (fused sgd_momentum/adam/adagrad) — such handles are
        unsupported by the grouped program (public predicate for
        callers)."""
        return self._is_stateful(self._server_handle)

    def _keep(self, key, prog) -> Callable:
        """Cache a program a lookup missed; the misses are counted here,
        off the hot path (the hits are the ops less the misses)."""
        with self._mu:
            self._programs[key] = prog
        self._clock.program_built()
        return prog

    def _program(self, op: str, padded_len: int, dtype, handle_key,
                 bucket: Optional[DenseBucket] = None) -> Callable:
        """Jitted SPMD program for (op, shape, dtype, handle) — the
        executable-cache analog of the reference's per-(key,push,recver)
        rendezvous cache.  Under a handle that treats keys apart
        (``bucket`` given: see :meth:`_bind`) also for the keys' segments
        and flags, which such a program holds, and for the job's dtype
        (``pull`` of a mixed bucket is the bucket's own too)."""
        key = (op, padded_len, str(dtype), handle_key)
        if bucket is not None:
            key += (bucket.segments_key, str(bucket.job_dtype))
        with self._mu:
            prog = self._programs.get(key)
        if prog is not None:
            return prog

        import jax
        from jax import lax
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self.axis
        mesh = self.mesh
        if op in ("push_st", "push_pull_st", "push_pull_st_zc"):
            return self._stateful_program(op, key, handle_key, bucket)
        if op in ("pull", "pull_pinned"):
            handle = None  # pull is read-only; no server update to fuse
        else:
            handle = self._handle_fn(
                self._server_handle if handle_key == "_default" else handle_key
            )
        waxis = self.worker_axis
        store_spec = P(axis)
        grads_spec = P(axis, None) if waxis is None else P(waxis, axis)
        repl_spec = P(None)

        def _push_pull(store_l, grads_l):
            # grads_l: [1, padded]; reduce-scatter across workers => my shard
            return _rs_update_ag(store_l, grads_l, handle, axis, waxis)

        # The degenerate 1-worker zero-copy program takes grads FLAT
        # [padded]: squeezing [1, padded] inside the program forces a
        # rank-changing relayout that runs at ~47 GB/s for packed
        # dtypes (bf16's (2,128)(2,1) tiling; measured 73% of the zc
        # step's device time) — f32 only escapes it by bitcast luck.
        flat_zc = self.num_shards == 1 and waxis is None

        def _push_pull_zc(store_l, grads_l):
            # In-place pull delivery (kv axis size 1: the gather is the
            # identity, so the updated store IS the pulled value).  The
            # copy-free analog of the reference's RegisterRecvBuffer
            # delivery (rdma_van.h:520-548): without it XLA must give the
            # second output its own buffer — a full read+write that was
            # 40% of the headline's device time (r03 verdict, weak #1).
            if flat_zc:
                return _update(handle, store_l, grads_l)
            agg = _aggregate(grads_l, axis, waxis)
            return _update(handle, store_l, agg)

        def _push(store_l, grads_l):
            agg = _aggregate(grads_l, axis, waxis)
            new = _update(handle, store_l, agg)
            # Tiny non-donated completion token: callers block on this
            # instead of the store (which the next push donates).
            return new, new[:1]

        def _pull(store_l):
            if bucket is not None and bucket.owned is not None:
                # Sharded on its keys' borders: gathered, then key order.
                import jax.numpy as jnp

                from ..ops.muon import unplace

                pulled = _gather(store_l, axis)
                with jax.named_scope("ps.pull.place"):
                    return unplace(bucket.owned, pulled, jnp)
            if bucket is not None:  # mixed: rounded, gathered, cut
                return _gather(_narrowed(store_l, bucket.job_dtype),
                               axis)[:bucket.total_len]
            return _gather(store_l, axis)

        def _pull_pinned(prev_l, store_l):
            # prev_l is the previous pinned output, passed to donate its
            # buffer: jit pairs it with the shape-identical output, so the
            # gather lands at the registered address.  The output must
            # *use* prev_l or jit prunes the arg and drops the alias; the
            # integer bitcast &0 keeps the dependence without float
            # arithmetic (prev*0 would resurrect NaNs from stale lanes).
            import jax.numpy as jnp

            pulled = _gather(store_l, axis)
            nbits = np.dtype(pulled.dtype).itemsize * 8
            idt = jnp.dtype(f"int{nbits}")
            dep = lax.bitcast_convert_type(prev_l, idt) & jnp.array(0, idt)
            return pulled + lax.bitcast_convert_type(dep, pulled.dtype)

        if op == "push_pull":
            fn = jax.shard_map(
                _push_pull,
                mesh=mesh,
                in_specs=(store_spec, grads_spec),
                out_specs=(store_spec, repl_spec),
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=(0,))
        elif op == "push_pull_zc":
            fn = jax.shard_map(
                _push_pull_zc,
                mesh=mesh,
                in_specs=(store_spec,
                          store_spec if flat_zc else grads_spec),
                out_specs=store_spec,
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=(0,))
        elif op == "push":
            fn = jax.shard_map(
                _push,
                mesh=mesh,
                in_specs=(store_spec, grads_spec),
                out_specs=(store_spec, store_spec),
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=(0,))
        elif op == "pull":
            fn = jax.shard_map(
                _pull, mesh=mesh, in_specs=(store_spec,), out_specs=repl_spec,
                check_vma=False,
            )
            jitted = jax.jit(fn)
        elif op == "pull_pinned":
            fn = jax.shard_map(
                _pull_pinned,
                mesh=mesh,
                in_specs=(repl_spec, store_spec),
                out_specs=repl_spec,
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=(0,))
        else:
            raise ValueError(op)
        return self._keep(key, jitted)

    def _stateful_program(self, op: str, key, handle_key: str,
                          bucket: Optional[DenseBucket] = None) -> Callable:
        """Program for the fused-kernel handles: the Pallas optimizer pass
        runs between the reduce-scatter and the all-gather, with store AND
        optimizer state donated (one HBM pass per step, no double
        buffering).  On a 2-D mesh the worker reduction is the psum over
        ``worker_axis`` and state lives sharded over kv / replicated over
        dp, exactly like the store.

        With ``bucket`` the program is that bucket's own (:meth:`_bind`:
        one that keeps its keys' lengths).  It takes the gradient as the
        job has it, ``[W, total_len]`` (a worker's row whole on each of
        the worker's devices: :meth:`_prep_grads_whole`), and returns the
        pulled values at ``total_len``: what lies behind the last key is
        the program's business and no caller's.  Before or after the
        program, a pad or a cut is a launch and a copy of its own, of a
        whole tree where the bucket is one.  A bucket that lies by its
        owner plan (``bucket.owned``: under ``muon`` over several shards)
        has the row laid into the owners' order before the sum
        (``ps.push.place``) and the gathered shards back into key order
        after (``ps.pull.place``), both inside.  Inside it the cut is that
        copy too where one shard holds the bucket (the all-gather is the
        identity): there a handle whose kernel can leave the pulled values
        itself is asked to (:meth:`_kernel_pulls`), and what its function
        hands back third is what the program returns.

        A mixed bucket (job dtype narrower than the store's) hands its
        rows over in the job's dtype: they go to the handle as they came
        (one worker, one shard: its kernel widens them) or are widened
        for the sum over W (:func:`_widened`), and it pulls its store
        rounded (:func:`_narrowed`, before the gather: half the bytes
        cross the chips) where no kernel wrote the pulled values in the
        job's dtype itself."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from ..ops import muon as muon_ops

        n_state, sfn = self._stateful_handle(handle_key, bucket)
        if self._kernel_pulls(op, handle_key, bucket):
            sfn = partial(sfn, pulled_len=bucket.total_len)
        axis = self.axis
        waxis = self.worker_axis
        store_spec = P(axis)
        repl_spec = P(None)
        if bucket is None:
            grads_spec = P(axis, None) if waxis is None else P(waxis, axis)

            def aggregate(grads_l):
                return _aggregate(grads_l, axis, waxis)

            def cut(pulled):
                return pulled
        else:
            mixed = bucket.mixed
            grads_spec = self._grads_sharding(False, True).spec
            shards = self.num_shards
            shard_len, total = bucket.padded_len // shards, bucket.total_len
            passes = shards == 1 and self.num_workers == 1
            owners = bucket.owned
            if not self._needs_segments(handle_key):
                sfn = _zero_filled(sfn)

            def aggregate(grads_l):
                if mixed:
                    if passes:
                        return grads_l
                    grads_l = _widened(
                        grads_l, bucket.dtype,
                        shards * shard_len if shards > 1 else total)
                if owners is not None:
                    # The job's row, in key order, laid as the owners lie:
                    # the scatter then hands each its own keys whole.
                    with jax.named_scope("ps.push.place"):
                        grads_l = muon_ops.place(owners, grads_l,
                                                 jnp).reshape(-1)
                return _aggregate_whole(grads_l, shard_len, shards, axis,
                                        waxis)

            def cut(pulled):
                if owners is not None:
                    # ... and the gathered tree back into key order.
                    with jax.named_scope("ps.pull.place"):
                        return muon_ops.unplace(owners, pulled, jnp)
                return pulled[:total]

        def narrow(store_l):
            if bucket is not None and bucket.mixed:
                return _narrowed(store_l, bucket.job_dtype)
            return store_l

        def _updated(store_l, rest):
            state_l, grads_l = rest[:-1], rest[-1]
            return _update(sfn, store_l, tuple(state_l), aggregate(grads_l))

        def _push(store_l, *rest):
            new_store, new_state = _updated(store_l, rest)
            return (new_store, *new_state, new_store[:1])  # token last

        def _push_pull(store_l, *rest):
            new_store, new_state, *pulled = _updated(store_l, rest)
            if not pulled:
                pulled = [cut(_gather(narrow(new_store), axis))]
            return (new_store, *new_state, *pulled)

        def _push_pull_zc(store_l, *rest):
            # In-place pull delivery: see _program's _push_pull_zc.
            new_store, new_state = _updated(store_l, rest)
            return (new_store, *new_state)

        if op == "push_st":
            body, tails = _push, (store_spec,)
        elif op == "push_pull_st_zc":
            body, tails = _push_pull_zc, ()
        else:
            body, tails = _push_pull, (repl_spec,)
        fn = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=(store_spec, *([store_spec] * n_state), grads_spec),
            out_specs=(store_spec, *([store_spec] * n_state), *tails),
            check_vma=False,
        )
        jitted = jax.jit(fn, donate_argnums=tuple(range(1 + n_state)))
        return self._keep(key, jitted)

    def _n_state(self, handle: str, bucket: DenseBucket) -> int:
        """How many arrays :meth:`_ensure_opt_state` keeps for ``bucket``
        under ``handle``, which its program takes and gives beside the
        store."""
        kind = handle.split(":", 1)[0]
        if kind == "muon":
            return len(self._muon_state_shapes(bucket)) + 1
        return 1 if kind in ("sgd_momentum", "adagrad") else 3

    def _muon_state_shapes(self, bucket: DenseBucket):
        """The shapes of ``muon``'s state over the mesh, the step slot
        apart: ``ops.muon.state_shapes`` on one shard; over several an
        owner's (``owner_state_shapes``), every owner's side by side."""
        from ..ops.muon import owner_state_shapes, state_shapes

        owners = self._owner_plan(bucket)
        if owners is None:
            return state_shapes(self._muon_plan(bucket))
        return tuple((self.num_shards * shape[0], *shape[1:])
                     for shape in owner_state_shapes(owners))

    def _ensure_opt_state(self, name: str, handle: str, bucket) -> None:
        """Allocate (or validate) the bucket's optimizer state for
        ``handle`` (or its kind alone).  Call with the bucket lock held."""
        kind = handle.split(":", 1)[0]
        have = self._opt_kinds.get(name)
        if have == kind:
            return
        log.check(have is None,
                  f"bucket {name!r} already has {have!r} state; cannot "
                  f"switch to {kind!r}")
        from jax.sharding import NamedSharding, PartitionSpec as P

        t0 = stamp()
        sharding = NamedSharding(self.mesh, P(self.axis))
        dt = np.dtype(bucket.dtype)
        if kind == "muon":
            # At its own size: a momentum a chunk of matrices, m and v over
            # the AdamW keys alone, the step.  Zero-filled where it lies.
            import jax.numpy as jnp

            state = (
                *(jnp.zeros(shape, dt, device=sharding)
                  for shape in self._muon_state_shapes(bucket)),
                self._place(np.zeros(self.num_shards, np.float32), sharding),
            )
        elif kind in ("sgd_momentum", "adagrad"):
            state = (self._place(np.zeros(bucket.padded_len, dt), sharding),)
        else:  # adam, lamb: m, v, the step
            state = (
                self._place(np.zeros(bucket.padded_len, dt), sharding),
                self._place(np.zeros(bucket.padded_len, dt), sharding),
                self._place(np.zeros(self.num_shards, np.float32), sharding),
            )
        self._opt_states[name] = state
        self._opt_kinds[name] = kind
        self._clock.state_created(stamp() - t0)

    def opt_state(self, name: str):
        """Snapshot of the bucket's optimizer state (checkpointing).
        Returns (kind, arrays) or None when the bucket has none.  Under
        ``muon`` the arrays are the state's logical form, whatever the
        chunks it is kept in and whichever owner keeps them: the momentum as one vector over the Muon
        keys in key order (a key's values as its matrix lies in the
        store), AdamW's m and v over its keys in key order, the step."""
        import jax.numpy as jnp

        with self._bucket_mu[name]:
            if name not in self._opt_states:
                return None
            kind, state = self._opt_kinds[name], self._opt_states[name]
            if kind == "muon":
                from ..ops.muon import momentum_vector, owner_momentum_vector

                owners = self._buckets[name].owned
                if owners is not None:
                    # Key order, whatever the owners' layout: an owner's
                    # stretch of m and v is full up to the last.
                    n = owners.elementwise_len
                    return kind, (
                        owner_momentum_vector(owners, state[:-3], jnp),
                        jnp.copy(state[-3][:n]), jnp.copy(state[-2][:n]),
                        jnp.copy(state[-1]))
                plan = self._muon_plan(self._buckets[name])
                n = len(plan.chunks)
                return kind, (momentum_vector(plan, state[:n], jnp),
                              *(jnp.copy(s) for s in state[n:]))
            return kind, tuple(jnp.copy(s) for s in state)

    def opt_state_nbytes(self, name: str) -> int:
        """Bytes the bucket's optimizer state holds on the devices (0:
        none yet), the step slot included."""
        with self._bucket_mu[name]:
            return sum(int(s.nbytes) for s in self._opt_states.get(name, ()))

    def set_opt_state(self, name: str, kind: str, values) -> None:
        """Restore optimizer state (checkpoint resume).

        Fleet-size portable: vector states may arrive de-padded
        (``total_len``, the v2 checkpoint layout) and are re-padded for
        THIS engine's shard count; the adam step counter may arrive as
        any length (a v2 scalar or an old per-shard vector) and is
        re-broadcast to ``num_shards`` entries — so state saved on an
        8-shard fleet restores onto 4 shards and vice versa."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        import jax

        log.check(name in self._buckets, f"bucket {name!r} not registered")
        bucket = self._buckets[name]
        sharding = NamedSharding(self.mesh, P(self.axis))
        if kind == "muon":
            self._set_muon_state(bucket, values, sharding)
            return
        norm = []
        placed_device = {}
        for i, v in enumerate(values):
            if isinstance(v, jax.Array) and i != step_slot(
                    kind, len(values)):
                # Fleet-portable DEVICE restore (orbax v2): logical
                # vectors pad+reshard on device, no host fetch.
                import jax.numpy as jnp

                # Mirror set_store_array's dense dtype check: a slot of
                # the wrong dtype (bucket re-registered differently than
                # at save time) must fail HERE, not steps later as an
                # opaque XLA dtype error inside the fused update.
                log.check_eq(
                    np.dtype(v.dtype), np.dtype(bucket.dtype),
                    f"bad opt restore dtype for bucket {name!r}",
                )
                log.check(
                    v.size in (bucket.total_len, bucket.padded_len),
                    f"bad optimizer state length {v.size} for bucket "
                    f"{name!r} (want {bucket.total_len} or "
                    f"{bucket.padded_len})",
                )
                if v.size == bucket.total_len != bucket.padded_len:
                    v = jnp.pad(
                        v.reshape(-1),
                        (0, bucket.padded_len - bucket.total_len),
                    )
                placed_device[i] = jax.device_put(
                    v.reshape(-1), sharding
                )
                norm.append(None)
                continue
            arr = np.ascontiguousarray(np.asarray(v))
            if i == step_slot(kind, len(values)):
                step = float(arr.reshape(-1)[0]) if arr.size else 0.0
                arr = np.full(self.num_shards, step, np.float32)
            else:
                # Reject mismatched vectors HERE, not steps later as an
                # opaque XLA shape error (e.g. a v1 checkpoint's
                # other-fleet padding: neither total nor this padded).
                log.check(
                    arr.size in (bucket.total_len, bucket.padded_len),
                    f"bad optimizer state length {arr.size} for bucket "
                    f"{name!r} (want {bucket.total_len} or "
                    f"{bucket.padded_len})",
                )
                if arr.size == bucket.total_len != bucket.padded_len:
                    out = np.zeros(bucket.padded_len, arr.dtype)
                    out[: bucket.total_len] = arr.reshape(-1)
                    arr = out
            norm.append(arr)
        placed = tuple(
            placed_device[i] if a is None else self._place(a, sharding)
            for i, a in enumerate(norm)
        )
        with self._bucket_mu[name]:
            self._opt_states[name] = placed
            self._opt_kinds[name] = kind

    def _set_muon_state(self, bucket: DenseBucket, values, sharding) -> None:
        """``set_opt_state`` under ``muon``: ``values`` in the logical
        form :meth:`opt_state` hands out (host or device arrays), laid
        into the plan's chunks on the device."""
        import jax
        import jax.numpy as jnp

        from ..ops.muon import momentum_chunks, owner_momentum_arrays

        refusal = self._muon_refusal("muon", bucket)
        log.check(refusal is None, refusal)
        self._lay_by_owners(bucket)
        plan, owners = self._muon_plan(bucket), bucket.owned
        log.check_eq(len(values), 4,
                     f"bucket {bucket.name!r}: muon's state is the momentum, "
                     f"AdamW's m and v and the step")
        dt = np.dtype(bucket.dtype)
        vectors = []
        for v, want, what in zip(
                values, (plan.muon_len, plan.adamw_len, plan.adamw_len),
                ("momentum (a value a Muon value)", "AdamW m", "AdamW v")):
            log.check_eq(np.dtype(v.dtype), dt,
                         f"bad opt restore dtype for bucket {bucket.name!r}")
            log.check_eq(int(np.size(v)), want,
                         f"bad optimizer state length for bucket "
                         f"{bucket.name!r}: {what}")
            vectors.append(jnp.asarray(v).reshape(-1))
        step = np.asarray(values[3]).reshape(-1)
        if owners is None:
            chunks = momentum_chunks(plan, vectors[0], jnp)
        else:
            chunks = owner_momentum_arrays(owners, vectors[0], jnp)
            fill = owners.shards * owners.stretch - plan.adamw_len
            vectors[1:] = [jnp.pad(v, (0, fill)) for v in vectors[1:]]
        placed = (
            *(jax.device_put(c, sharding) for c in chunks),
            jax.device_put(vectors[1], sharding),
            jax.device_put(vectors[2], sharding),
            self._place(np.full(self.num_shards,
                                float(step[0]) if step.size else 0.0,
                                np.float32), sharding),
        )
        with self._bucket_mu[bucket.name]:
            self._opt_states[bucket.name] = placed
            self._opt_kinds[bucket.name] = "muon"

    # -- data plane ops ------------------------------------------------------

    def _is_multiprocess(self) -> bool:
        return self._multiprocess

    def _place(self, host_arr, sharding):
        from .placement import place_host_array

        return place_host_array(
            self.mesh, host_arr, sharding, self._multiprocess
        )

    def _local_shards(self) -> int:
        """Worker rows owned by THIS process on a multi-process mesh."""
        return self._local_shard_count

    def _normalize_host_grads(self, grads, rows, bucket, xp,
                              steps: bool = False,
                              row_msg: str = "bad worker dim",
                              width: Optional[int] = None):
        """Coerce a grads array to ``[(T,)? rows, padded]``: dtype cast
        (to the job's dtype, the store's but on a mixed bucket, where
        another dtype is refused), broadcast a missing row dim to
        ``rows``, validate the row count, pad the value tail (to ``width``, where a program takes another
        than the bucket's ``padded_len``).  The one definition behind
        every host/device staging path (1-D/2-D x single/multi-process x
        single/replay); ``xp`` is np (host staging) or jnp (device
        staging) — see :func:`placement.staging_xp`."""
        if bucket.mixed:
            # Never cast: a narrowing here would round the job's gradient
            # behind its back.
            arr = xp.asarray(grads)
            if arr.dtype != bucket.job_dtype:
                self._refuse_grad_dtype(bucket, arr.dtype)
        else:
            arr = xp.asarray(grads, dtype=bucket.job_dtype)
        want = 3 if steps else 2
        log.check(arr.ndim in (want - 1, want), "bad grads rank")
        if arr.ndim == want - 1:
            if steps:
                arr = xp.broadcast_to(
                    arr[:, None, :], (arr.shape[0], rows, arr.shape[1])
                )
            else:
                arr = xp.broadcast_to(arr, (rows, arr.shape[0]))
        log.check_eq(int(arr.shape[-2]), rows, row_msg)
        if width is None:
            width = bucket.padded_len
        if arr.shape[-1] != width:
            log.check_eq(int(arr.shape[-1]), bucket.total_len,
                         "bad grad len")
            pad = width - bucket.total_len
            pads = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
            arr = xp.pad(arr, pads)
        return arr

    def _grads_sharding(self, flat: bool, whole: bool = False):
        """What a prep delivers: ``[W, padded]`` rows over the worker
        axis (``_prep_grads``), the FLAT ``[padded]`` of one worker
        (``_prep_grads_flat``), or ``whole`` rows ``[W, total]``
        (``_prep_grads_whole``: no row is cut over the kv axis, which
        ``total`` need not divide by).  A bound op hands its prep the one
        its record holds."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if flat:
            return NamedSharding(self.mesh, P(self.axis))
        if self.worker_axis is not None:
            return NamedSharding(self.mesh, P(
                self.worker_axis, None if whole else self.axis))
        return NamedSharding(self.mesh, P(self.axis, None))

    def _prep_grads_flat(self, bucket: DenseBucket, grads, sharding=None):
        """``[padded]`` FLAT grads for the degenerate 1-worker zero-copy
        program (see ``_push_pull_zc``'s flat_zc note): host arrays
        flatten for free; device ``[1, padded]`` arrays pay one reshape
        per call (a bitcast for f32, a relayout copy for packed dtypes
        — pass flat device arrays on the hot path)."""
        import jax

        if sharding is None:
            sharding = self._grads_sharding(True)
        if isinstance(grads, jax.Array):
            # Same worker-dim discipline as _prep_grads: a (2, N/2)
            # array must fail loud, not silently flatten into one
            # concatenated gradient.
            log.check(grads.ndim in (1, 2), "bad grads rank")
            if grads.ndim == 2:
                log.check_eq(int(grads.shape[0]), 1, "bad worker dim")
                g = grads.reshape(-1)
            else:
                g = grads
            if int(g.shape[0]) == bucket.padded_len:
                if g.sharding == sharding:
                    return g
                return jax.device_put(g, sharding)
            # Unpadded device arrays fall through to host normalization
            # (padded == total on every zc-eligible config, so this is
            # only reachable for malformed lengths, which it rejects).
        arr = self._normalize_host_grads(grads, 1, bucket, np)
        return jax.device_put(
            np.ascontiguousarray(arr).reshape(-1), sharding
        )

    def _prep_grads_whole(self, bucket: DenseBucket, grads, sharding=None):
        """``[W, total]`` for a program that is the bucket's own
        (:meth:`_stateful_program`): the gradient as the job has it.  A
        device array of that shape under ``sharding`` passes as it is,
        and nothing is padded here or anywhere before the program."""
        if sharding is None:
            sharding = self._grads_sharding(False, True)
        return self._prep_grads(bucket, grads, sharding, bucket.total_len)

    def _refuse_grad_dtype(self, bucket: DenseBucket, got) -> None:
        over = (f" over its {np.dtype(bucket.dtype)} store"
                if bucket.mixed else "")
        raise log.CheckError(
            f"bucket {bucket.name!r} takes gradients of "
            f"{bucket.job_dtype}{over} and was handed {np.dtype(got)}: "
            f"cast in the job, where the rounding is the job's to choose "
            f"(a device gradient is never cast here, and a program traced "
            f"for another dtype is not the bucket's)")

    def _prep_grads(self, bucket: DenseBucket, grads, sharding=None,
                    width: Optional[int] = None):
        """Accept [W, total] (or [total] broadcast) host/device arrays and
        deliver a [W, padded] device array sharded over the worker axis
        (``width``: another length than ``padded``, see
        :meth:`_prep_grads_whole`).

        Multi-process host-array contracts differ by layout:
        - 1-D mesh: the host array is this PROCESS's contribution —
          [total] broadcasts to the process's local worker rows,
          [local, total] maps row-for-row; the global array is assembled
          with make_array_from_process_local_data (device_put cannot
          target non-addressable devices).
        - 2-D (worker_axis) mesh: the host array is the GLOBAL
          [W, total] grads and must be IDENTICAL on every process — a
          process's devices span a rectangle of the (dp, kv) grid, so
          there is no per-process row ownership to map a local
          contribution onto."""
        import jax

        if sharding is None:
            sharding = self._grads_sharding(False)
        if width is None:
            width = bucket.padded_len
        if isinstance(grads, jax.Array) and grads.ndim == 2:
            shape = grads.shape
            if shape[1] == width:
                # Never cast, never traced anew for another dtype.
                if grads.dtype != bucket.job_dtype:
                    self._refuse_grad_dtype(bucket, grads.dtype)
                # Row count must match the worker fan-in exactly — a
                # silent reshard would drop rows (the shard body reads
                # one local row per device position).
                if shape[0] != self.num_workers:
                    log.check_eq(int(shape[0]), self.num_workers,
                                 "bad worker dim")
                have = grads.sharding
                if have is sharding or have == sharding:
                    return grads
                return jax.device_put(grads, sharding)
        if bucket.mixed and isinstance(grads, jax.Array):
            if grads.dtype != bucket.job_dtype:
                self._refuse_grad_dtype(bucket, grads.dtype)
            raise log.CheckError(
                f"bucket {bucket.name!r} takes a device gradient as rows "
                f"[{self.num_workers}, {width}] of {bucket.job_dtype} and "
                f"was handed {tuple(grads.shape)}: no other form is laid "
                f"out anew here (a pass over the tree on every call, "
                f"outside the bucket's program): hand the rows over")
        if self.worker_axis is not None:
            if self._is_multiprocess():
                arr = self._normalize_host_grads(
                    grads, self.num_workers, bucket, np, width=width
                )
                return self._place(np.ascontiguousarray(arr), sharding)
            arr = self._normalize_host_grads(
                grads, self.num_workers, bucket, staging_xp(grads),
                width=width
            )
            return jax.device_put(arr, sharding)
        if self._is_multiprocess():
            arr = self._normalize_host_grads(
                grads, self._local_shards(), bucket, np,
                row_msg="bad local worker dim (rows = this process's "
                        "devices on a multi-process mesh)",
                width=width,
            )
            return jax.make_array_from_process_local_data(
                sharding, np.ascontiguousarray(arr),
                (self.num_shards, width),
            )
        arr = self._normalize_host_grads(
            grads, self.num_shards, bucket, staging_xp(grads), width=width
        )
        return jax.device_put(arr, sharding)

    def _observe(self, op: str, bucket: DenseBucket, pushes: int = 1,
                 pulls: int = 1) -> None:
        """Account one data-plane op in the byte counters.  (Its host
        time is the StageClock's; device time is a ``jax.profiler``
        trace's, see ``utils.profiling.device_trace``.)"""
        payload = bucket.nbytes
        with self._counter_mu:
            if op in ("push", "push_pull"):
                self.push_bytes += payload * pushes
            if op in ("pull", "push_pull"):
                self.pull_bytes += payload * pulls

    def _resolve_handle(self, handle: Optional[ServerHandle]):
        resolved = self._server_handle if handle is None else handle
        if self._is_stateful(resolved):
            return resolved, resolved  # stateful handles key by full string
        return resolved, ("_default" if handle is None else handle)

    def _bind(self, name: str, handle: Optional[ServerHandle],
              zero_copy: Optional[bool]) -> _BoundOp:
        """Stage ``select`` of the first ``push_pull(name, ., handle,
        zero_copy)`` (``zero_copy`` None: of the first ``push``), and of
        the first after a ``reshard`` or a new registration of ``name``
        dropped the record: the handle resolved, zero-copy eligibility,
        the program and the prep that goes with it.

        In-place pull delivery serves a bucket whose kv axis has size 1:
        the all-gather is the identity, so the updated store IS the
        pulled value.  It mirrors the reference's RegisterRecvBuffer:
        in place where the transport allows it, copied elsewhere."""
        mesh, bucket = self.mesh, self._buckets[name]
        resolved, handle_key = self._resolve_handle(handle)
        push = zero_copy is None
        if isinstance(resolved, str) and resolved.startswith("muon"):
            # A handle that needs its keys whole: over several shards the
            # bucket is laid by its owner plan, once, before its program
            # is built for that layout.
            if bucket.shapes is not None:
                refusal = self._muon_refusal(resolved, bucket)
                log.check(refusal is None, refusal)
                self._lay_by_owners(bucket)
        else:
            log.check(bucket.owned is None, self._owned_refusal(
                name, f"handle {resolved!r}", "use the handle that took it"))
        # (A bucket with lens is kept in whole tiles on one shard too: its
        # store is not its pulled value.)
        zc = (bool(zero_copy)
              and self.num_shards == 1
              and bucket.padded_len == bucket.total_len
              and not bucket.mixed)  # the store is not the job's dtype
        stateful = self._is_stateful(resolved)
        if not stateful:
            self._refuse_mixed(
                bucket, f"the stateless handle {resolved!r} (on the "
                f"programs shared by length)",
                "use a stateful handle (lamb, adam, adagrad, sgd_momentum)")
        # A stateful program of a bucket that keeps its keys' lengths is
        # the bucket's own: it takes the gradient and gives the pulled
        # values at total_len (_stateful_program), and a handle that treats
        # keys apart finds their borders in it (one that needs them on a
        # bucket without is refused in _stateful_handle).
        own = stateful and (bucket.lens is not None
                            or self._needs_segments(resolved))
        prep = self._prep_grads
        if stateful:
            op = ("push_st" if push
                  else "push_pull_st_zc" if zc else "push_pull_st")
            prog = self._program(op, bucket.padded_len, bucket.dtype,
                                 handle_key, bucket if own else None)
            if own:
                prep = self._prep_grads_whole
            if self._needs_segments(resolved) or bucket.mixed:
                prog = self._counted(
                    prog, resolved.split(":", 1)[0], bucket,
                    self._kernel_pulls(op, resolved, bucket))
        else:
            # Flat [padded] grads: zc says the kv axis has size 1, this
            # branch that the handle is stateless.
            if zc and self.worker_axis is None:
                prep = self._prep_grads_flat
            op = "push" if push else "push_pull_zc" if zc else "push_pull"
            prog = self._program(op, bucket.padded_len, bucket.dtype,
                                 handle_key)
        # The program takes the store, the state and the gradient, and gives
        # the store, the state and, but in place, the pulled array or a token.
        held = 1 + (self._n_state(resolved, bucket) if stateful else 0)
        bound = _BoundOp(
            op="push" if push else "push_pull", bucket=bucket,
            lock=self._bucket_mu[name], prog=prog, prep=prep,
            sharding=self._grads_sharding(
                prep == self._prep_grads_flat, own),
            state_kind=resolved.split(":", 1)[0] if stateful else None,
            zc=zc, cut=not (push or zc or own
                            or bucket.padded_len == bucket.total_len),
            launched=launched("dense.push" if push else "dense.push_pull",
                              2 * held + 1 + (not zc)),
        )
        with self._mu:
            # A reshard or a new registration meanwhile: the next op binds.
            if self.mesh is mesh and self._buckets.get(name) is bucket:
                self._bound[(name, handle, zero_copy)] = bound
        self._clock.op_bound()
        return bound

    def _kernel_pulls(self, op: str, handle,
                      bucket: Optional[DenseBucket]) -> bool:
        """Whether the program of ``bucket`` under ``op`` and ``handle``
        takes its pulled values from the update kernel: it returns them
        (not the store in their place, and not nothing), one shard holds
        the whole bucket, and the handle is ``lamb`` (whose second pass
        can leave them, ``fused_update.lamb_apply``) or ``muon`` on a
        bucket whose every key's new values a kernel writes
        (``MuonPlan.pulls``).  Over several shards, and under every other
        handle, they are the all-gather of the shards, cut at
        ``total_len``."""
        from ..ops.fused_update import lamb_apply_pulls

        if (op != "push_pull_st" or self.num_shards != 1
                or bucket is None):
            return False
        if handle.startswith("muon"):
            return self._muon_plan(bucket).pulls
        return (handle.startswith("lamb")
                and lamb_apply_pulls(bucket.total_len))

    def _lamb_plan(self, bucket: DenseBucket):
        """``fused_update.lamb_plan`` of ``bucket`` as it lies on this
        mesh, made once for a layout (``reshard`` gives the bucket another
        ``padded_len`` or the mesh other shards): what ``_lamb_fn``
        builds the program from and ``engine.update.lamb.one_pass``
        reads."""
        from ..ops.fused_update import lamb_plan

        layout = (bucket.padded_len, self.num_shards)
        if bucket.lamb_plan is None or bucket.lamb_plan[0] != layout:
            bucket.lamb_plan = (layout, lamb_plan(bucket.starts, *layout))
        return bucket.lamb_plan[1]

    def _counted(self, prog: Callable, kind: str, bucket: DenseBucket,
                 kernel_pulls: bool) -> Callable:
        """``prog`` behind the counts of ``engine.update.lamb`` (with
        ``engine.update.lamb.one_pass``, the elements that the last such
        program updated in one pass) or ``engine.update.muon`` (with
        ``.matrices``, ``.row_keys``, ``.apply_keys``, ``.ns_flops``,
        ``.owners`` and ``.owner_flops``, a step's by the plan of the last
        such program), where the program
        takes its pulled values from the update's kernels
        (:meth:`_kernel_pulls`) of ``engine.pull.from_kernel``, and on a mixed
        bucket of ``engine.dense.narrow``: what a record of :meth:`_bind`
        knows is counted by the record's own program, and no other op pays
        for it."""
        lamb, muon, narrow = kind == "lamb", kind == "muon", bucket.mixed
        one_pass = self._lamb_plan(bucket).one_pass_len if lamb else 0
        plan = self._muon_plan(bucket) if muon else None
        owners, owned, spread = 1, bucket.owned if muon else None, 1000
        if owned is not None:
            # A key's way in and out is its slot's, on its owner: a
            # matrix's own, an element-wise key's the owners' stretch.
            slots = np.where(owned.where[:, 0] >= 0, owned.where[:, 3],
                             owned.plan.adamw_keys[:1].sum())
            plan = plan._replace(
                row_keys=slots[np.isin(slots, owned.plan.row_keys)],
                apply_keys=slots[np.isin(slots, owned.plan.apply_keys)])
            owners = int(np.count_nonzero(owned.flops))
            spread = int(round(1000 * owned.flops.max()
                               / owned.flops.mean()))

        def counted(*args):
            if lamb:
                self.lamb_updates += 1
                self.lamb_one_pass = one_pass
            elif muon:
                self.muon_updates += 1
                self.muon_matrices = plan.matrices
                self.muon_row_keys = len(plan.row_keys)
                self.muon_apply_keys = len(plan.apply_keys)
                self.muon_ns_flops = plan.ns_flops
                self.muon_owners = owners
                self.muon_owner_flops = spread
            self.kernel_pulls += kernel_pulls
            self.narrow_ops += narrow
            return prog(*args)

        return counted

    def export(self, registry) -> None:
        """Lazily sampled gauges in a node's ``Registry``, beside the
        stage clock's (``docs/observability.md``, "Engine path")."""
        registry.gauge("engine.update.lamb", fn=lambda: self.lamb_updates)
        registry.gauge("engine.update.lamb.one_pass",
                       fn=lambda: self.lamb_one_pass)
        registry.gauge("engine.update.muon", fn=lambda: self.muon_updates)
        registry.gauge("engine.update.muon.matrices",
                       fn=lambda: self.muon_matrices)
        registry.gauge("engine.update.muon.row_keys",
                       fn=lambda: self.muon_row_keys)
        registry.gauge("engine.update.muon.apply_keys",
                       fn=lambda: self.muon_apply_keys)
        registry.gauge("engine.update.muon.ns_flops",
                       fn=lambda: self.muon_ns_flops)
        registry.gauge("engine.update.muon.owners",
                       fn=lambda: self.muon_owners)
        registry.gauge("engine.update.muon.owner_flops",
                       fn=lambda: self.muon_owner_flops)
        registry.gauge(
            "engine.dense.owned.pad_bytes",
            fn=lambda: sum(
                (b.padded_len - b.total_len) * np.dtype(b.dtype).itemsize
                for b in list(self._buckets.values())
                if b.owned is not None))
        registry.gauge("engine.pull.from_kernel",
                       fn=lambda: self.kernel_pulls)
        registry.gauge("engine.dense.narrow", fn=lambda: self.narrow_ops)
        registry.gauge(
            "engine.dense.segments",
            fn=lambda: sum(len(b.keys) for b in list(self._buckets.values())
                           if b.lens is not None))

    def push_pull(self, name: str, grads, handle: Optional[ServerHandle] = None,
                  zero_copy: Optional[bool] = False):
        """Fused push+aggregate+update+pull; returns the replicated pulled
        array (async).  The benchmark hot path (SURVEY §3.2).

        ``zero_copy=True`` requests in-place pull delivery: where the
        topology allows it (see :meth:`_bind`) the returned
        array ALIASES the bucket store — zero extra HBM traffic, but it
        is invalidated by the bucket's next mutating op (the next push
        donates the buffer; stale holders raise on use rather than read
        torn data).  Same caller contract as the reference's
        RegisterRecvBuffer pulls (the next pull overwrites the registered
        buffer in place).  Configs the in-place path cannot serve fall
        back to the copying path transparently.

        Bound once, launched many times: what no two ops of ``(name,
        handle, zero_copy)`` differ in is a :class:`_BoundOp` that the
        first op builds (:meth:`_bind`) and the others look up; an op
        then checks the gradient it was handed, takes the bucket's lock,
        calls the program on the current store and state and rebinds
        them.  ``reshard`` drops every record, registering ``name`` again
        drops that bucket's.  (``zero_copy=None`` is :meth:`push`.)"""
        t0 = stamp()  # stage borders: see _note
        b = (self._bound.get((name, handle, zero_copy))
             or self._bind(name, handle, zero_copy))
        t1 = stamp()  # select | prep
        g = b.prep(b.bucket, grads, b.sharding)
        t2 = stamp()  # prep | launch
        with b.lock:
            if b.state_kind is not None:
                if self._opt_kinds.get(name) != b.state_kind:
                    self._ensure_opt_state(name, b.state_kind, b.bucket)
                c0 = stamp()  # the jitted call alone: see LAUNCH
                outs = b.prog(
                    self._stores[name], *self._opt_states[name], g
                )
                c1 = stamp()
                n_state = len(self._opt_states[name])
                self._stores[name] = outs[0]
                self._opt_states[name] = tuple(outs[1:1 + n_state])
                pulled = outs[0] if b.zc else outs[-1]
            elif b.zc:
                c0 = stamp()
                pulled = self._stores[name] = b.prog(self._stores[name], g)
                c1 = stamp()
            else:
                c0 = stamp()
                self._stores[name], pulled = b.prog(self._stores[name], g)
                c1 = stamp()
            if b.cut:
                pulled = pulled[: b.bucket.total_len]
        self._observe(b.op, b.bucket)
        t3 = stamp()
        self._note((LAUNCH, t3, c1 - c0, t3 - t2, b.launched))
        self._note((ENGINE_OP, t3, t1 - t0, t2 - t1, t3 - t2))
        return pulled

    def push(self, name: str, grads, handle: Optional[ServerHandle] = None):
        """Push alone: a ``push_pull`` whose programs return, where that
        one has the pulled array, a tiny non-donated token that becomes
        ready when the push completes — block on it freely (the store
        itself is donated by the next push, so it must not escape)."""
        return self.push_pull(name, grads, handle, None)

    def coalescer(self, handle: Optional[ServerHandle] = None, **kw):
        """A :class:`~pslite_tpu.parallel.coalesce.CoalescingDispatcher`
        over this engine: concurrently-issued per-op push_pulls
        micro-batch into grouped programs (the async ZPush/ZPull
        amortization — see the module docstring)."""
        from .coalesce import CoalescingDispatcher

        return CoalescingDispatcher(self, handle=handle, **kw)

    def push_pull_group(self, names, grads_list,
                        handle: Optional[ServerHandle] = None):
        """Fused push_pull over SEVERAL buckets in ONE jitted program —
        one dispatch instead of len(names) (the bucketed-gradient-stream
        pattern of a model step, e.g. the ResNet-50 trace's ~35 buckets).

        Stateless handles only (sum/assign/sgd/custom); returns the list
        of pulled arrays in ``names`` order.
        """
        log.check(len(names) == len(grads_list), "names/grads mismatch")
        log.check(len(set(names)) == len(names),
                  "duplicate bucket in group (stores are donated)")
        resolved, handle_key = self._resolve_handle(handle)
        log.check(not self._is_stateful(resolved),
                  "push_pull_group supports stateless handles only")
        t0 = stamp()  # stage borders: see _note
        buckets = [self._buckets[n] for n in names]
        for b in buckets:
            self._refuse_mixed(b, "a group of buckets (stateless handles, "
                               "one program for the group)")
        prog = self._group_program(
            tuple((b.padded_len, str(np.dtype(b.dtype)))
                  for b in buckets),
            handle_key,
        )
        t1 = stamp()  # select | prep
        gs = [self._prep_grads(b, g) for b, g in zip(buckets, grads_list)]
        t2 = stamp()  # prep | launch
        # Lock every bucket in sorted order (deadlock-free against
        # other group/single ops) for the whole load-run-store.
        ordered = sorted(set(names))
        for n in ordered:
            self._bucket_mu[n].acquire()
        try:
            c0 = stamp()  # the jitted call alone: see LAUNCH
            outs = prog(*[self._stores[n] for n in names], *gs)
            c1 = stamp()
            k = len(names)
            for i, n in enumerate(names):
                self._stores[n] = outs[i]
        finally:
            for n in reversed(ordered):
                self._bucket_mu[n].release()
        pulled = [p[: b.total_len] for p, b in zip(outs[k:], buckets)]
        for b in buckets:
            self._observe("push_pull", b)
        t3 = stamp()
        # A store and a gradient in, a store and a pulled array out, a bucket.
        self._note((LAUNCH, t3, c1 - c0, t3 - t2,
                    4 * k << LAUNCH_SHIFT | _PUSH_PULL))
        self._note((ENGINE_OP, t3, t1 - t0, t2 - t1, t3 - t2))
        return pulled

    def _group_program(self, shapes_key, handle_key) -> Callable:
        key = ("group_pp", shapes_key, handle_key, self.worker_axis)
        with self._mu:
            prog = self._programs.get(key)
        if prog is not None:
            return prog

        import jax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        waxis = self.worker_axis
        handle = self._resolved_handle_fn(handle_key)
        k = len(shapes_key)
        store_spec = P(axis)
        grads_spec = P(axis, None) if waxis is None else P(waxis, axis)
        repl_spec = P(None)

        def _body(*args):
            stores, grads = args[:k], args[k:]
            new_stores, pulled = [], []
            for store_l, grads_l in zip(stores, grads):
                new, out = _rs_update_ag(store_l, grads_l, handle, axis,
                                         waxis)
                new_stores.append(new)
                pulled.append(out)
            return (*new_stores, *pulled)

        fn = jax.shard_map(
            _body,
            mesh=self.mesh,
            in_specs=tuple([store_spec] * k + [grads_spec] * k),
            out_specs=tuple([store_spec] * k + [repl_spec] * k),
            check_vma=False,
        )
        jitted = jax.jit(fn, donate_argnums=tuple(range(k)))
        return self._keep(key, jitted)

    # -- fused multi-step replay --------------------------------------------

    def replay(self, name: str, grads_seq, handle: Optional[ServerHandle] = None,
               keep: str = "all", zero_copy: bool = False):
        """Run T consecutive ``push_pull`` steps as ONE jitted program —
        a ``lax.scan`` over the donated store (and optimizer state for
        stateful handles), so the per-op Python+dispatch cost (~50-100 µs,
        which dominates small buckets) is paid once for the whole
        sequence.  The steady-state analog of the reference's ns/key
        replay loop (test_benchmark.cc:388-396): first touch compiles,
        thereafter the whole T-step pipeline is device-resident.

        Args:
          grads_seq: ``[T, total]`` (each step's gradient broadcast to
            every worker) or ``[T, W, total]`` (row per worker per step);
            host arrays on single-process meshes, any layout of
            ``jax.Array``.  Multi-process host arrays follow
            ``_prep_grads``'s contracts: 1-D mesh = ``[T, local, total]``
            (this process's worker rows); 2-D mesh = the GLOBAL
            ``[T, W, total]``, identical on every process.
          keep: ``"all"`` materializes every step's pulled result
            (returns ``[T, total]``); ``"last"`` returns only the final
            pulled vector ``[total]`` — intermediate all-gathers are
            dead code XLA removes, making it the fused form of
            T×ZPush + one pull.
          zero_copy: with ``keep="last"`` on a zc-eligible config (see
            :meth:`push_pull`), skip the final gather and return the
            store itself — invalidated by the bucket's next mutating op.
        """
        log.check(keep in ("all", "last"), f"bad keep {keep!r}")
        t0 = stamp()  # stage borders: see _note
        bucket = self._buckets[name]
        self._refuse_mixed(bucket, "replay (one program for any bucket of "
                           "a length)")
        resolved, handle_key = self._resolve_handle(handle)
        stateful = self._is_stateful(resolved)
        zc = zero_copy and keep == "last" and self.num_shards == 1
        steps = int(np.shape(grads_seq)[0])
        flat = self._flat_replay(
            bucket.padded_len, bucket.dtype, stateful, steps
        )
        prog = self._replay_program(
            steps, bucket.padded_len, bucket.dtype, handle_key, keep,
            stateful=stateful, zero_copy=zc,
        )
        t1 = stamp()  # select | prep
        g = self._prep_grads_seq(bucket, grads_seq, flat=flat)
        t2 = stamp()  # prep | launch
        with self._bucket_mu[name]:
            n_state = 0
            if stateful:
                self._ensure_opt_state(name, resolved, bucket)
                c0 = stamp()  # the jitted call alone: see LAUNCH
                outs = prog(
                    self._stores[name], *self._opt_states[name], g
                )
                c1 = stamp()
                n_state = len(self._opt_states[name])
                self._stores[name] = outs[0]
                self._opt_states[name] = tuple(outs[1:1 + n_state])
                pulled = outs[0] if zc else outs[-1]
            elif zc:
                c0 = stamp()
                pulled = self._stores[name] = prog(self._stores[name], g)
                c1 = stamp()
            else:
                c0 = stamp()
                self._stores[name], pulled = prog(self._stores[name], g)
                c1 = stamp()
            # A zero-copy result aliases the store; padded == total on
            # zc configs.
            if not zc:
                pulled = (pulled[:, : bucket.total_len] if keep == "all"
                          else pulled[: bucket.total_len])
        self._observe("push_pull", bucket, pushes=steps,
                      pulls=steps if keep == "all" else 1)
        t3 = stamp()
        # As a bound push_pull's (_bind): the steps are one program's.
        self._note((LAUNCH, t3, c1 - c0, t3 - t2,
                    2 * n_state + 3 + (not zc) << LAUNCH_SHIFT | _PUSH_PULL))
        self._note((ENGINE_OP, t3, t1 - t0, t2 - t1, t3 - t2))
        return pulled

    def push_pull_stream(self, name: str, grads_iter,
                         handle: Optional[ServerHandle] = None,
                         depth: int = 2):
        """Generator over ``push_pull`` results with host->HBM staging
        pipelined against the collectives — the HOST-ORIGIN fast path
        for one bucket (see :meth:`push_pull_multi_stream`)."""
        return self.push_pull_multi_stream(
            ((name, g) for g in grads_iter), handle=handle, depth=depth
        )

    def push_pull_multi_stream(self, pairs_iter,
                               handle: Optional[ServerHandle] = None,
                               depth: int = 2):
        """Generator over ``push_pull`` results for ``(bucket_name,
        grads)`` pairs with host->HBM staging pipelined against the
        collectives.

        A background thread runs ``_prep_grads`` (the ``device_put``
        staging) up to ``depth`` batches ahead while the caller's thread
        dispatches the collective on the previously staged batch, so
        transfer(i+1) overlaps compute(i) even when the transport makes
        ``device_put`` effectively synchronous.  This is the collective
        analog of the reference's pinned-memory + async-RDMA overlap on
        its host path (CPU tensors staged into registered buffers while
        the NIC drains earlier ones); a bucketed gradient stream (e.g.
        the ResNet-50 trace) pipelines bucket i+1's transfer under
        bucket i's collective.

        The iterator is consumed on the stager thread; results yield in
        order.  A stager-side exception re-raises on the caller's
        thread; closing the generator early releases the stager.  Each
        yielded array follows the usual async-dispatch contract (block
        or np.asarray to materialize)."""
        import queue as _queue

        log.check(depth >= 1, "depth must be >= 1")
        q: "_queue.Queue" = _queue.Queue(maxsize=depth)
        _DONE = object()
        stop = threading.Event()

        def _put(item) -> bool:
            # Bounded put that notices an abandoned consumer (generator
            # closed early) instead of blocking forever on a full queue.
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def _stager():
            try:
                for name, g in pairs_iter:
                    self._refuse_mixed(
                        self._buckets[name], "a stream (it stages [W, "
                        "padded] rows of the store's dtype ahead)")
                    staged = self._prep_grads(self._buckets[name], g)
                    if not _put(("ok", name, staged)):
                        return
            except BaseException as exc:  # surfaced on the caller thread
                _put(("err", exc, None))
                return
            _put((_DONE, None, None))

        t = threading.Thread(target=_stager, name="engine-stager",
                             daemon=True)
        t.start()
        try:
            while True:
                kind, a, b = q.get()
                if kind is _DONE:
                    break
                if kind == "err":
                    raise a
                yield self.push_pull(a, b, handle=handle)
        finally:
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
            t.join(timeout=30)

    def _prep_grads_seq(self, bucket: DenseBucket, grads_seq,
                        flat: bool = False):
        """[T, W, padded] device array sharded like the grads of T
        stacked push calls (leading step axis replicated) — or, with
        ``flat=True`` (1-D layouts only, see :meth:`_flat_replay`), the
        slab layout ``[W, T*padded]`` where worker w's T steps are one
        contiguous run."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if flat:
            log.check(self.worker_axis is None,
                      "flat replay layout is 1-D only")
            sharding = NamedSharding(self.mesh, P(self.axis, None))

            def _to_slab(arr, xp):
                # [T, rows, padded] -> [rows, T*padded]; note shape[1] is
                # read from the PRE-swap array (the row count).  No
                # pre-slabbed fast path: a [W, T*padded] slab is
                # indistinguishable from a broadcast [T, total] whenever
                # T == W and total % padded == 0, and guessing wrong
                # silently collapses T steps into one.
                rows = arr.shape[1]
                arr = xp.swapaxes(arr, 0, 1)
                if xp is np:
                    arr = np.ascontiguousarray(arr)
                return arr.reshape(rows, -1)

            if self._is_multiprocess():
                arr = self._normalize_host_grads(
                    grads_seq, self._local_shards(), bucket, np, steps=True,
                    row_msg="bad local worker dim (rows = this process's "
                            "devices on a multi-process mesh)",
                )
                arr = _to_slab(arr, np)
                return jax.make_array_from_process_local_data(
                    sharding, arr,
                    (self.num_shards, arr.shape[1]),
                )
            if isinstance(grads_seq, jax.Array):
                # Device arrays must relayout on device (tiled 2-D rows
                # are physically interleaved; slabs need contiguity).
                arr = self._normalize_host_grads(
                    grads_seq, self.num_shards, bucket, jnp, steps=True
                )
                return jax.device_put(_to_slab(arr, jnp), sharding)
            # Host arrays: build the slab layout host-side (free views
            # for W=1, one transpose copy otherwise) so the device sees
            # ONE transfer and ZERO relayout copies — the relayouts were
            # ~68% of the replay's device time when done on device.
            arr = self._normalize_host_grads(
                grads_seq, self.num_shards, bucket, np, steps=True
            )
            return jax.device_put(_to_slab(arr, np), sharding)
        if self.worker_axis is not None:
            sharding = NamedSharding(
                self.mesh, P(None, self.worker_axis, self.axis)
            )
        else:
            sharding = NamedSharding(self.mesh, P(None, self.axis, None))
        if isinstance(grads_seq, jax.Array) and grads_seq.ndim == 3:
            if grads_seq.shape[1:] == (self.num_workers, bucket.padded_len):
                if grads_seq.sharding == sharding:
                    return grads_seq
                return jax.device_put(grads_seq, sharding)
        if self._is_multiprocess():
            if self.worker_axis is not None:
                # Same GLOBAL-array contract as _prep_grads' 2-D branch.
                arr = self._normalize_host_grads(
                    grads_seq, self.num_workers, bucket, np, steps=True
                )
                return self._place(np.ascontiguousarray(arr), sharding)
            arr = self._normalize_host_grads(
                grads_seq, self._local_shards(), bucket, np, steps=True,
                row_msg="bad local worker dim (rows = this process's "
                        "devices on a multi-process mesh)",
            )
            return jax.make_array_from_process_local_data(
                sharding, np.ascontiguousarray(arr),
                (arr.shape[0], self.num_shards, bucket.padded_len),
            )
        arr = self._normalize_host_grads(
            grads_seq, self.num_workers, bucket,
            staging_xp(grads_seq), steps=True,
        )
        return jax.device_put(arr, sharding)

    def _flat_replay(self, padded_len: int, dtype, stateful: bool,
                     steps: int) -> bool:
        """Whether the replay sequence uses the FLAT slab layout
        ``[W, T*padded]`` (each worker's T steps contiguous) instead of
        the stacked ``[T, W, padded]``.

        The stacked form makes XLA slice step t out of a sublane-tiled
        ``[T, padded]`` block — a strided read that measured ~190 GB/s on
        a 685 GB/s chip and caused the r03 16MB replay cliff (112 vs 314
        GB/s at 1MB) — plus two full relayout copies of the whole
        sequence on entry.  Flat slabs make each step an aligned
        contiguous ``dynamic_slice`` that fuses with the update (measured
        ~674 GB/s at 16MB).  Below ~1MB per step XLA's software pipelining
        of the stacked layout wins instead (it stages slices into VMEM
        ahead of use), so small buckets keep the stacked form."""
        return (
            not stateful
            and self.worker_axis is None
            and padded_len * np.dtype(dtype).itemsize
            >= self.replay_flat_min_bytes
            # Slab offsets are int32 inside the scan; a slab at or over
            # 2^31 elements would wrap (dynamic_slice clamps silently).
            and steps * padded_len < (1 << 31)
        )

    @staticmethod
    def _replay_unroll(padded_len: int, dtype, steps: int) -> int:
        """Inner unroll factor for the flat replay scan: the largest of
        16/8/4/2 no bigger than the step count that keeps the
        per-iteration slab read at or under 64MB (larger slabs regress —
        the 64MB-step sweep point measured 342 vs 454 GB/s with U=2).
        Step counts not divisible by U run a tail scan for the
        remainder, so odd T keeps the amortization for its bulk."""
        bytes_step = padded_len * np.dtype(dtype).itemsize
        cap = max(1, (64 << 20) // max(bytes_step, 1))
        for u in (16, 8, 4, 2):
            if u <= cap and u <= steps:
                return u
        return 1

    def _replay_program(self, steps: int, padded_len: int, dtype,
                        handle_key, keep: str, stateful: bool,
                        zero_copy: bool = False) -> Callable:
        """Jitted T-step scan program; cached per (T, shape, dtype,
        handle, keep) like every other engine executable.

        ``zero_copy`` (only meaningful with ``keep="last"`` on one
        shard, see :meth:`replay`) skips the final all-gather and returns
        the store as the pulled value."""
        flat = self._flat_replay(padded_len, dtype, stateful, steps)
        key = ("replay", steps, padded_len, str(dtype), handle_key, keep,
               stateful, flat, zero_copy)
        with self._mu:
            prog = self._programs.get(key)
        if prog is not None:
            return prog

        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        waxis = self.worker_axis
        store_spec = P(axis)
        grads_spec = (
            P(None, axis, None) if waxis is None else P(None, waxis, axis)
        )
        if stateful:
            n_state, sfn = self._stateful_handle(handle_key)

            def _body(store_l, *rest):
                state_l, grads_l = rest[:-1], rest[-1]

                def step(carry, g):
                    store_c, state_c = carry[0], carry[1:]
                    agg = _aggregate([g], axis, waxis)
                    new_store, new_state = _update(
                        sfn, store_c, tuple(state_c), agg)
                    out = _gather(new_store, axis) if keep == "all" else 0.0
                    return (new_store, *new_state), out

                carry, outs = lax.scan(
                    step, (store_l, *state_l), grads_l[:, 0]
                )
                if keep == "last":
                    if zero_copy:
                        return carry
                    outs = _gather(carry[0], axis)
                return (*carry, outs)

            tails = () if (keep == "last" and zero_copy) else (
                (P(None, None),) if keep == "all" else (P(None),)
            )
            fn = jax.shard_map(
                _body,
                mesh=self.mesh,
                in_specs=(store_spec, *([store_spec] * n_state), grads_spec),
                out_specs=(store_spec, *([store_spec] * n_state), *tails),
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=tuple(range(1 + n_state)))
        else:
            import jax.numpy as jnp

            handle = self._resolved_handle_fn(handle_key)

            def _step_out(new_store):
                if keep == "all":
                    return _gather(new_store, axis)
                return 0.0

            def _finish(new_store, outs):
                if keep == "last":
                    if zero_copy:
                        return new_store
                    outs = _gather(new_store, axis)
                return new_store, outs

            if flat:
                U = self._replay_unroll(padded_len, dtype, steps)

                def _body(store_l, grads_l):
                    # grads_l: [1, T*padded] — my T slabs, contiguous, so
                    # each step is an aligned dynamic_slice that fuses
                    # with the update (see _flat_replay).  The scan runs
                    # T//U outer iterations that each pull a U-step slab
                    # and apply U UNROLLED updates: the store carry stays
                    # resident across the inner steps, amortizing its
                    # read+write to 2P/U per step (traffic -> P + 2P/U).
                    # A non-divisible step count runs the remainder as an
                    # un-unrolled tail scan.
                    seq = grads_l[0]

                    def inner(carry, u_off):
                        g = lax.dynamic_slice(seq, (u_off,), (padded_len,))
                        new_store = _update(
                            handle, carry, _aggregate([g], axis, waxis)
                        )
                        return new_store, _step_out(new_store)

                    bulk = (steps // U) * U
                    if U == 1:
                        new_store, outs = lax.scan(
                            inner, store_l,
                            jnp.arange(steps, dtype=jnp.int32) * padded_len,
                        )
                    else:
                        def outer(carry, t):
                            offs = (t * (U * padded_len)
                                    + jnp.arange(U, dtype=jnp.int32)
                                    * padded_len)
                            return lax.scan(inner, carry, offs,
                                            unroll=True)

                        new_store, outs = lax.scan(
                            outer, store_l,
                            jnp.arange(steps // U, dtype=jnp.int32),
                        )
                        if keep == "all":
                            # [T//U, U, L] -> [bulk, L]
                            outs = outs.reshape(
                                (bulk,) + outs.shape[2:]
                            )
                        if bulk < steps:
                            tail_offs = (
                                jnp.arange(bulk, steps, dtype=jnp.int32)
                                * padded_len
                            )
                            new_store, tail_outs = lax.scan(
                                inner, new_store, tail_offs
                            )
                            if keep == "all":
                                outs = jnp.concatenate(
                                    [outs, tail_outs], axis=0
                                )
                    return _finish(new_store, outs)

                grads_in_spec = P(axis, None)
            else:
                def _body(store_l, grads_l):
                    # grads_l: [T, 1, padded] (my worker row per step).
                    def step(carry, g):
                        new_store = _update(
                            handle, carry, _aggregate([g], axis, waxis))
                        return new_store, _step_out(new_store)

                    new_store, outs = lax.scan(step, store_l, grads_l[:, 0])
                    return _finish(new_store, outs)

                grads_in_spec = grads_spec

            if keep == "last" and zero_copy:
                out_specs = store_spec
            elif keep == "all":
                out_specs = (store_spec, P(None, None))
            else:
                out_specs = (store_spec, P(None))
            fn = jax.shard_map(
                _body,
                mesh=self.mesh,
                in_specs=(store_spec, grads_in_spec),
                out_specs=out_specs,
                check_vma=False,
            )
            jitted = jax.jit(fn, donate_argnums=(0,))
        return self._keep(key, jitted)

    def pull(self, name: str):
        t0 = stamp()  # stage borders: see _note
        bucket = self._buckets[name]
        to_pinned = name in self._pinned_pulls
        # A mixed bucket's pull is its own program: the store rounded to
        # the job's dtype, gathered and cut at total_len inside; so is
        # that of a bucket sharded on its keys' borders: gathered and laid
        # back into key order.
        own = bucket if bucket.mixed or bucket.owned is not None else None
        prog = self._program(
            "pull_pinned" if to_pinned else "pull", bucket.padded_len,
            bucket.dtype, "_pull_pinned" if to_pinned else "_pull", own,
        )
        t1 = stamp()  # select | launch: a pull prepares nothing
        # Bucket lock: a concurrent push donates the store buffer; reading
        # it unlocked could hand an already-donated array to the pull
        # program.  Dispatch is async, so this only serializes enqueue.
        with self._bucket_mu[name]:
            # Re-fetch under the lock: a concurrent unregister may have
            # popped the entry since the unlocked check above.
            pinned = self._pinned_pulls.get(name) if to_pinned else None
            if pinned is not None:
                # Padded length: the caller registered the buffer and
                # owns its layout — slicing here would materialize a
                # copy and break the address-identity contract.
                c0 = stamp()  # the jitted call alone: see LAUNCH
                pulled = prog(pinned, self._stores[name])
                c1 = stamp()
                self._pinned_pulls[name] = pulled
                arrays = 3  # the buffer and the store in, the buffer out
            else:
                if to_pinned:
                    prog = self._program("pull", bucket.padded_len,
                                         bucket.dtype, "_pull")
                c0 = stamp()
                pulled = prog(self._stores[name])
                c1 = stamp()
                arrays = 2
                if own is None:
                    pulled = pulled[: bucket.total_len]
                elif bucket.mixed:
                    self.narrow_ops += 1
        self._observe("pull", bucket)
        t2 = stamp()
        self._note((LAUNCH, t2, c1 - c0, t2 - t1,
                    arrays << LAUNCH_SHIFT | _PULL))
        self._note((ENGINE_OP, t2, t1 - t0, 0, t2 - t1))
        return pulled

    def register_pull_buffer(self, name: str):
        """Pin a persistent pull-output buffer for ``name`` — the
        PinMemory / ``w_pool_`` contract of the reference's UCX van
        (ucx_van.h:603-623): after this, every ``pull(name)`` delivers the
        gathered store into the SAME device buffer (donation aliases the
        previous output to the next), the collective analog of responses
        RDMA-written to the worker's registered address
        (test_benchmark.cc:169-181).  Returns the initial (zeroed,
        padded-length, replicated) buffer.

        The usual registered-buffer contract applies: at most one
        outstanding pull per bucket, and the caller must not hold stale
        references across pulls (the old array's buffer is donated)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        bucket = self._buckets[name]
        self._refuse_mixed(bucket, "a pinned pull buffer (a padded-length "
                           "buffer of the store's dtype, donated from pull "
                           "to pull)", "pull into a fresh array")
        log.check(bucket.owned is None, self._owned_refusal(
            name, "a pinned pull buffer (a padded-length buffer in the "
            "store's own order)", "pull into a fresh array"))
        # _place handles multi-process meshes (device_put cannot target
        # non-addressable devices).
        buf = self._place(
            np.zeros(bucket.padded_len, dtype=np.dtype(bucket.dtype)),
            NamedSharding(self.mesh, P(None)),
        )
        with self._bucket_mu[name]:
            self._pinned_pulls[name] = buf
        return buf

    def unregister_pull_buffer(self, name: str) -> None:
        with self._bucket_mu[name]:
            self._pinned_pulls.pop(name, None)

    def pinned_pull_buffer(self, name: str):
        """The current pinned output (identity checks / zero-copy reads)."""
        with self._bucket_mu[name]:
            return self._pinned_pulls.get(name)

    def store_array(self, name: str):
        """A consistent snapshot of the sharded server state (for
        checkpointing).

        Copied under the bucket lock: the live buffer may be donated by
        the next push the moment the lock is released, so handing out the
        live reference would hand out a to-be-deleted array."""
        import jax.numpy as jnp

        with self._bucket_mu[name]:
            bucket = self._buckets[name]
            if bucket.owned is not None:
                # Key order at ``total_len``, whatever the owners' layout.
                return self._in_key_order(bucket, self._stores[name])
            return jnp.copy(self._stores[name])

    def store_spec(self, name: str):
        """Shape/dtype/sharding of a store without copying it (restore
        targets)."""
        import jax

        with self._bucket_mu[name]:
            arr = self._stores[name]
            return jax.ShapeDtypeStruct(
                arr.shape, arr.dtype, sharding=arr.sharding
            )

    def opt_state_specs(self, name: str):
        """Shape/dtype/sharding of each array the bucket's optimizer state
        is KEPT in, without copying any (``opt_state`` hands out the logical
        form); () where the bucket has none."""
        import jax

        with self._bucket_mu[name]:
            return tuple(
                jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)
                for a in self._opt_states.get(name, ()))

    def set_store_array(self, name: str, value) -> None:
        """Restore server state (checkpoint resume).

        Accepts a host array (placed onto the bucket's sharding) or a
        ``jax.Array`` already laid out for this store (multi-host orbax
        restores pass these through untouched — fetching them to host
        would fail across non-addressable devices).
        """
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        bucket = self._buckets[name]
        sharding = NamedSharding(self.mesh, P(self.axis))
        if (bucket.owned is not None
                and int(np.size(value)) == bucket.total_len):
            # Key order in, laid by the owners (what ``store_array`` and a
            # checkpoint hand out; an array of ``padded_len`` is taken as
            # laid out already, as for any bucket).
            from ..ops.muon import place

            if isinstance(value, jax.Array):
                log.check_eq(value.dtype, np.dtype(bucket.dtype),
                             "bad restore dtype")
                placed = self._relaid(bucket, value.reshape(-1),
                                      bucket.owned)
            else:
                placed = self._place(place(
                    bucket.owned, np.asarray(value, np.dtype(
                        bucket.dtype)).reshape(-1), np), sharding)
            with self._bucket_mu[name]:
                self._stores[name] = placed
            return
        if isinstance(value, jax.Array):
            if (tuple(value.shape) == (bucket.total_len,)
                    and bucket.total_len != bucket.padded_len):
                # Fleet-portable DEVICE restore (orbax v2): a global
                # LOGICAL array saved by any shard count — pad to THIS
                # engine's padded length and reshard, all device-side
                # (multi-host arrays are not host-fetchable).
                import jax.numpy as jnp

                value = jnp.pad(
                    value.astype(bucket.dtype),
                    (0, bucket.padded_len - bucket.total_len),
                )
            if tuple(value.shape) == (bucket.padded_len,):
                log.check_eq(value.dtype, np.dtype(bucket.dtype),
                             "bad restore dtype")
                placed = jax.device_put(value, sharding)
                with self._bucket_mu[name]:
                    self._stores[name] = placed
                return
        arr = np.zeros(bucket.padded_len, dtype=np.dtype(bucket.dtype))
        flat = np.asarray(value).reshape(-1)
        log.check(len(flat) in (bucket.total_len, bucket.padded_len),
                  "bad restore length")
        arr[: len(flat)] = flat
        placed = self._place(arr, sharding)
        with self._bucket_mu[name]:
            self._stores[name] = placed

    def reshard(self, mesh, axis_name: Optional[str] = None) -> None:
        """Re-lay every registered bucket (store + optimizer state) onto
        a new mesh — the engine-side ELASTIC tier.  See
        :meth:`reshard_staged` for the stage/commit split that
        coordinated multi-engine recuts use for pair atomicity.

        The reference's recovery path re-admits a node into the same
        roster under the dead node's id (van.cc:266-332); on the
        collective data plane the roster IS the mesh, so scaling the
        server fleet up/down means resharding the live state onto the
        new device set.  Key-range shards are recut for the new shard
        count (GetServerKeyRanges semantics, postoffice.cc:257-268),
        optimizer state moves with the stores, and compiled programs are
        dropped and rebuilt lazily on first touch — exactly like
        first-push rendezvous after a topology change.

        State moves via a host round trip on either kind of mesh.  On a
        multi-process mesh (old or new side) reshard is a COLLECTIVE:
        every participating process must call it with the same new mesh
        in the same order — the snapshot assembles non-addressable
        shards with process_allgather and the rebuild scatters through
        the callback placement path.  (Roster-level recovery keeps the
        mesh: a replacement inherits the dead node's id and devices, so
        no reshard fires; this is the SCALE-change tier the launcher or
        app invokes when the server fleet itself grows or shrinks.)

        A 2-D engine (``worker_axis``) reshards onto any new mesh
        carrying both its axes — worker fan-in and server-shard count
        both recut.  Callers' grads arrays must use the NEW worker
        fan-in after this returns.
        """
        with self.reshard_staged(mesh, axis_name) as commit:
            commit()

    @contextlib.contextmanager
    def reshard_staged(self, mesh, axis_name: Optional[str] = None):
        """Stage a recut and yield its zero-failure commit closure.

        The snapshot + new-mesh placements (everything that can fail,
        including the multi-process collectives) run on entry; the
        yielded ``commit()`` performs plain field/dict assignments only.
        A coordinated multi-engine recut stages EVERY engine first and
        only then commits them all, so a failure in any engine's staging
        aborts the whole group with every engine untouched — the
        pair-level crash-consistency contract of
        ``reshard_engines`` (tests/test_reshard_crash.py).  Bucket locks
        are held until the context exits."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .placement import (
            local_shard_count,
            mesh_is_multiprocess,
            to_host_global,
        )

        new_multiprocess = mesh_is_multiprocess(mesh)
        axis = axis_name or self.axis
        if isinstance(axis, (tuple, list)):
            axis = tuple(axis)
        kv_axes = axis if isinstance(axis, tuple) else (axis,)
        for a in kv_axes:
            log.check(a in mesh.axis_names,
                      f"kv axis {a!r} not in new mesh")
        if self.worker_axis is not None:
            log.check(
                self.worker_axis in mesh.axis_names,
                f"worker axis {self.worker_axis!r} not in new mesh "
                f"(a 2-D engine stays 2-D across reshards)",
            )
            log.check(self.worker_axis not in kv_axes,
                      "worker_axis must differ from the kv axis")
        with self._mu:
            names = list(self._buckets)
        ordered = sorted(names)
        for n in ordered:
            self._bucket_mu[n].acquire()
        try:
            # Snapshot all live state to host while every bucket is
            # quiesced (the donated buffers cannot be in flight).  On a
            # multi-process OLD mesh this is the collective gather leg:
            # iterate in SORTED order so every process issues the same
            # allgather sequence regardless of registration order (the
            # buckets themselves — and their opt-state presence — must
            # already be symmetric across processes, as all engine
            # collectives require).
            old_mp = self._multiprocess
            names = ordered
            from ..ops import muon as muon_ops

            snap = {}
            for n in names:
                b = self._buckets[n]
                store = to_host_global(self._stores[n], old_mp)
                # Key order between layouts, whichever this one is.
                store = (muon_ops.unplace(b.owned, store, np)
                         if b.owned is not None
                         else store[: b.total_len].copy())
                opt = None
                if n in self._opt_states:
                    kind = self._opt_kinds[n]
                    arrs = [to_host_global(a, old_mp).copy()
                            for a in self._opt_states[n]]
                    if kind == "muon":
                        # The logical form ``opt_state`` hands out.
                        if b.owned is not None:
                            mom = muon_ops.owner_momentum_vector(
                                b.owned, arrs[:-3], np)
                        else:
                            mom = muon_ops.momentum_vector(
                                self._muon_plan(b), arrs[:-3], np)
                        held = self._muon_plan(b).adamw_len
                        arrs = [mom, arrs[-3][:held], arrs[-2][:held],
                                arrs[-1]]
                    opt = (kind, arrs)
                snap[n] = (b, store, opt)

            # STAGE: build every new placement against the NEW mesh
            # without touching engine state.  Any failure in this block
            # aborts with the engine fully on the OLD mesh — a crashed
            # or failed recut must never leave torn stores (the
            # crash-consistency contract of the cluster-coordinated
            # reshard; reference analog: recovery tolerates death at
            # any moment, van.cc:266-332).
            from .placement import place_host_array

            new_num_shards = int(
                np.prod([mesh.shape[a] for a in kv_axes])
            )
            new_num_workers = (
                mesh.shape[self.worker_axis]
                if self.worker_axis is not None
                else new_num_shards
            )
            sharding = NamedSharding(mesh, P(axis))

            def _nplace(host_arr, shard_spec):
                return place_host_array(
                    mesh, host_arr, shard_spec, new_multiprocess
                )

            def _repad(flat_host, total, padded, dt):
                out = np.zeros(padded, dtype=np.dtype(dt))
                out[:total] = flat_host[:total]
                return _nplace(out, sharding)

            staged = {}
            for n in names:
                b, store, opt = snap[n]
                padded = _padded_len(b.total_len, new_num_shards,
                                     b.lens is not None)
                # A bucket that a handle has taken whole key by whole key
                # lies by the NEW mesh's owner plan (none on one shard).
                owners = None
                if new_num_shards > 1 and (
                        b.owned is not None
                        or (opt is not None and opt[0] == "muon")):
                    owners = muon_ops.owner_plan(
                        b.shapes, (b.flags & KEY_ELEMENTWISE) != 0,
                        new_num_shards)
                    padded = owners.padded_len
                    store = muon_ops.place(owners, store, np)
                entry = {
                    "padded": padded,
                    "owners": owners,
                    "store": (_repad(store, b.total_len, padded, b.dtype)
                              if owners is None else _nplace(store, sharding)),
                }
                if n in self._pinned_pulls:
                    # Re-pin on the new mesh: the old pinned buffer's
                    # devices/shape no longer match (a fresh address —
                    # same as re-registering after recovery).
                    entry["pinned"] = _nplace(
                        np.zeros(padded, dtype=np.dtype(b.dtype)),
                        NamedSharding(mesh, P(None)),
                    )
                if opt is not None:
                    kind, arrs = opt
                    if kind == "muon":
                        # From the logical form into the new layout's
                        # arrays: the chunks of one shard, or every
                        # owner's side by side.
                        mom, m, v, step = arrs
                        if owners is None:
                            chunks = muon_ops.momentum_chunks(
                                self._muon_plan(b), mom, np)
                        else:
                            chunks = muon_ops.owner_momentum_arrays(
                                owners, mom, np)
                            fill = (new_num_shards * owners.stretch
                                    - len(m))
                            m, v = np.pad(m, (0, fill)), np.pad(v, (0, fill))
                        step = float(step[0]) if len(step) else 0.0
                        state = (
                            *(_nplace(np.ascontiguousarray(a), sharding)
                              for a in (*chunks, m, v)),
                            _nplace(np.full(new_num_shards, step,
                                            np.float32), sharding),
                        )
                    elif kind in ("sgd_momentum", "adagrad"):
                        state = (
                            _repad(arrs[0], b.total_len, padded, b.dtype),
                        )
                    else:  # adam, lamb: m, v, per-shard step counter
                        step = float(arrs[2][0]) if len(arrs[2]) else 0.0
                        state = (
                            _repad(arrs[0], b.total_len, padded, b.dtype),
                            _repad(arrs[1], b.total_len, padded, b.dtype),
                            _nplace(
                                np.full(new_num_shards, step, np.float32),
                                sharding,
                            ),
                        )
                    entry["opt"] = state
                staged[n] = entry

            # COMMIT closure: plain field/dict assignments only —
            # cannot fail partway, so observers see the old mesh or the
            # new one, never a mixture.
            def commit() -> None:
                self.mesh = mesh
                self.axis = axis
                self.num_shards = new_num_shards
                self.num_workers = new_num_workers
                self._multiprocess = new_multiprocess
                self._mesh_platform = next(
                    iter(mesh.devices.flat)
                ).platform
                self._interpret = self._mesh_platform != "tpu"
                self._local_shard_count = (
                    local_shard_count(mesh) if new_multiprocess
                    else new_num_shards
                )
                with self._mu:
                    self._programs.clear()
                    self._bound.clear()
                for n in names:
                    b = snap[n][0]
                    entry = staged[n]
                    b.padded_len = entry["padded"]
                    b.owned = entry["owners"]
                    if b.owned is not None:
                        b.owner_plan = (new_num_shards, b.owned)
                    self._stores[n] = entry["store"]
                    if "pinned" in entry:
                        self._pinned_pulls[n] = entry["pinned"]
                    if "opt" in entry:
                        self._opt_states[n] = entry["opt"]
                    else:
                        self._opt_states.pop(n, None)
                        self._opt_kinds.pop(n, None)

            yield commit
        finally:
            for n in reversed(ordered):
                self._bucket_mu[n].release()

    def block(self, name: Optional[str] = None) -> None:
        """Wait for outstanding device work (ZPush/Wait semantics)."""
        if name is not None:
            names = [name]
        else:
            with self._mu:
                names = list(self._stores)
        for n in names:
            # Held across the wait so no concurrent push can donate the
            # array between the read and block_until_ready.
            with self._bucket_mu[n]:
                self._stores[n].block_until_ready()
