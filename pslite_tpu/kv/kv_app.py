"""KV app layer: KVPairs, KVWorker, KVServer, default server handle.

Capability parity with the reference's ``include/ps/kv_app.h``:

- ``KVWorker.push/pull`` (aka ``ZPush/ZPull``) allocate a Customer timestamp,
  slice the sorted key array across server key ranges (``DefaultSlicer``,
  kv_app.h:566-636 — empty slices are skipped and pre-credited as responses),
  and send one message per server group; with instance groups, worker
  instance *i* only talks to server instance *i* of each group
  (kv_app.h:644-647).
- Pull responses are stashed per timestamp; the last response reassembles
  per-server chunks sorted by first key into the caller's buffer
  (kv_app.h:686-792) — skipped entirely in zero-copy mode where the
  transport already delivered in place.
- ``KVServer`` converts messages to ``KVMeta``+``KVPairs`` for the user
  handler, which must call ``response`` (kv_app.h:499-564);
  ``register_recv_buffer`` pre-pins per-(worker, key) receive buffers
  (kv_app.h:396-403).
- ``KVServerDefaultHandle``: push => ``store[key] += val``, pull => return
  ``store[key]`` (kv_app.h:430-452).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import zlib
from dataclasses import dataclass, field
from functools import partial
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import ps as ps_mod
from .. import tenants as tenants_mod
from ..base import SERVER_GROUP, is_server_id, server_rank_to_id
from ..customer import Customer
from ..message import (
    CodecInfo,
    Message,
    OPT_APPLY_ERROR,
    OPT_OVERLOAD,
    OPT_REPLICA,
    OPT_SEND_FAILED,
    OPT_WRONG_OWNER,
    OPT_XFER_PART,
    Role,
)
from ..ops import codecs as codecs_mod
from ..parallel.sparse import PulledGroup
from ..range import Range, find_range
from ..sarray import SArray
from ..utils import logging as log
from ..utils.bounded import BoundedKeySet
from ..utils.profiling import (
    COMPLETE_SPANS, COMPLETED, KV_OP, OP_SPAN, TraceAnnotation, stage_clock,
    stamp, tracing,
)
from ..vans import native
from . import snapshot as snapshot_mod
from .apply_shards import ApplyShardPool
from .hot_cache import HotKeyCache
from .snapshot import SNAPSHOT_LOCAL_CMD

# meta.head marker of the hot-key introspection pull (docs/qos.md): the
# server answers with its ``kv.hot_keys`` top-k — keys + counts — which
# the worker uses to seed its hot-key pull cache.  Distinct from the
# replication plane's REPLICA_FETCH_CMD (0x5EED).
HOT_KEYS_CMD = 0x407C

# meta.head of an elastic range-migration transfer (docs/elasticity.md):
# the OLD owner pushes a range's snapshotted state to the NEW owner
# named by the routing table; meta.key is the range's begin, meta.addr
# the routing epoch.  Server-to-server only — never sliced by workers.
MIGRATE_CMD = 0x314D

# meta.head of the LOCAL routing-cutover marker a server's routing hook
# posts into its own customer queue: processing it on the request
# thread serializes the ownership flip against every earlier queued
# request (they apply under the old epoch; later ones park or bounce).
# Never on the wire.
ROUTING_LOCAL_CMD = 0x52E9

# Small-op aggregation plane (kv/batching.py, docs/batching.md) —
# hoisted once so the per-frame/per-response hot paths don't pay a
# sys.modules lookup per call (batching.py imports nothing from this
# module, so there is no cycle).
from ..message import BatchInfo as _BatchInfo  # noqa: E402
from ..message import BatchOp as _BatchOp  # noqa: E402
from .batching import BATCH_PROBE_CMD as _BATCH_PROBE_CMD  # noqa: E402
from .batching import BATCH_WIRE_VERSION as _BATCH_WIRE_VERSION  # noqa: E402,E501
from .batching import split_batch_message as _split_batch_message  # noqa: E402,E501


class OverloadError(RuntimeError):
    """The server SHED this request under per-tenant admission control
    (``OPT_OVERLOAD`` — docs/qos.md).  Nothing was applied; this is a
    RETRYABLE backoff signal, not a failure: back off (the attribute
    below is a reasonable floor) and re-issue the request."""

    retry_after_s = 0.005


class ElasticZeroCopyError(RuntimeError):
    """Zero-copy registered pull buffers (``ZPush``/``ZPull`` into an
    ``alloc_pull_buffer`` destination) are incompatible with elastic
    membership (``PS_ELASTIC=1`` — docs/elasticity.md): the buffer's
    per-server byte offsets are frozen at registration, and the first
    live range migration would silently deliver slices at stale
    offsets.  Raised LOUDLY at registration (PR 9 declined silently —
    callers that ignored the warning pulled into ordinary arrays
    without knowing why).  Workarounds: pull into ordinary arrays
    (plain ``pull`` — correct under elastic routing, the transport
    still reassembles per slice), or run the cluster without
    ``PS_ELASTIC`` when registered-buffer delivery is required."""


@dataclass
class KVPairs:
    """Sorted unique keys + values (+ optional per-key value lengths)."""

    keys: np.ndarray = field(default_factory=lambda: np.empty(0, np.uint64))
    vals: np.ndarray = field(default_factory=lambda: np.empty(0, np.float32))
    lens: Optional[np.ndarray] = None
    priority: int = 0
    # Lazily-decoded codec payload (docs/compression.md): when set,
    # ``vals`` is empty and ``enc = (codes, scales, CodecInfo)`` — the
    # apply pool's shard threads decode exactly their own keys'
    # segments in parallel (codecs.decode_key_ranges) instead of one
    # whole-payload decode serializing the server's receive pump.
    enc: Optional[tuple] = None

    def empty(self) -> bool:
        return len(self.keys) == 0

    def materialize(self) -> None:
        """Eagerly decode a lazy codec payload into ``vals`` (callers
        that need the whole flat payload: global ops, handlers without
        ``apply_shard``, registered-buffer placement)."""
        if self.enc is None:
            return
        codes, scales, info = self.enc
        codec = codecs_mod.by_wire_id(info.codec)
        self.vals = codec.decode(codes, scales, info.raw_len // 4,
                                 flags=info.flags)
        self.enc = None


@dataclass
class KVMeta:
    """Request metadata handed to the server handler (kv_app.h:72-96)."""

    cmd: int = 0
    push: bool = False
    pull: bool = False
    sender: int = 0
    timestamp: int = 0
    customer_id: int = 0
    key: int = 0
    addr: int = 0
    val_len: int = 0
    option: int = 0
    priority: int = 0
    # Distributed tracing id (telemetry/tracing.py): nonzero when the
    # originating worker sampled this request; carried so server-side
    # apply/respond spans join the same trace.
    trace: int = 0
    # Wire-codec marker (docs/compression.md): the request's CodecInfo.
    # On a pull request (raw_len == 0) it names the codec the worker
    # wants the response encoded with; on a decoded push it records
    # what the payload traveled as (replication forwards re-send it).
    codec: object = None
    # Multi-tenant QoS (docs/qos.md): the request's tenant id — echoed
    # on the response, scheduled by weight in every contended queue,
    # and bounded by per-tenant admission control.
    tenant: int = 0
    # Hot-cache version stamp (kv/hot_cache.py): on a pull, the server
    # push-version captured at request intake (what the response
    # piggybacks); on a push, set by the server's one-shot version bump
    # as the response leaves.
    stamp: int = 0


# Legacy re-export (the one-off int8 option marker): wire compression
# now rides the codec registry + EXT_CODEC extension instead
# (ops/codecs.py — docs/compression.md); kept for existing importers.
from ..message import OPT_COMPRESS_INT8  # noqa: E402,F401
# Zero-copy pull (is_worker_zpull_, kv_app.h:727-792): the transport
# delivers each server's pull-response slice directly into the worker's
# pre-registered buffer; meta.addr carries (buf_id << 40) | byte_offset.
# (Defined in message.py so transports can consume them without importing
# the app layer.)
from ..message import OPT_ZPULL, ZPULL_OFF_BITS as _ZPULL_OFF_BITS  # noqa: E402,E501

# buf_ids are process-global so two KVWorker apps sharing one node (same
# postoffice/van) can never derive the same shm segment name.
_ZPULL_SEQ = itertools.count(1)


def default_slicer(
    kvs: KVPairs, ranges: List[Range]
) -> List[Optional[KVPairs]]:
    """Partition sorted keys over server key ranges (kv_app.h:566-621)."""
    n = len(ranges)
    out: List[Optional[KVPairs]] = [None] * n
    if kvs.empty():
        return out
    keys = kvs.keys
    if kvs.lens is not None:
        log.check_eq(len(kvs.lens), len(keys), "lens/keys size mismatch")
        val_offsets = np.concatenate(
            ([0], np.cumsum(np.asarray(kvs.lens, dtype=np.int64)))
        )
        k = None
    else:
        log.check(
            len(keys) == 0 or len(kvs.vals) % len(keys) == 0,
            "vals not divisible by keys",
        )
        k = len(kvs.vals) // max(len(keys), 1)
        val_offsets = None
    for i, rng in enumerate(ranges):
        pos = find_range(keys, rng.begin, rng.end)
        if pos.size() == 0:
            continue
        if k is not None:
            vb, ve = pos.begin * k, pos.end * k
            lens = None
        else:
            vb, ve = int(val_offsets[pos.begin]), int(val_offsets[pos.end])
            lens = kvs.lens[pos.begin : pos.end]
        out[i] = KVPairs(
            keys=keys[pos.begin : pos.end],
            vals=kvs.vals[vb:ve],
            lens=lens,
            priority=kvs.priority,
        )
    return out


@dataclass
class _EncodedSlice:
    """One slice's codec-encoded payload (docs/compression.md).  Built
    ONCE at send time so deadline-sweeper retries and replica failovers
    re-send byte-identical compressed data — re-encoding on retry would
    double-fold the error-feedback residual."""

    codes: np.ndarray        # uint8 wire payload
    scales: np.ndarray       # float32 scale table (empty for bf16)
    lens: Optional[np.ndarray]
    info: CodecInfo


@dataclass
class _PendingSlice:
    """One per-server slice of an in-flight bounded request."""

    group_rank: int
    part: KVPairs
    dest: int
    sent_msg: Optional[Message] = None  # for resender forget on re-route
    responded: bool = False
    enc: Optional[_EncodedSlice] = None  # codec payload (encode-once)
    # Set when THIS slice's delivery is known failed (send raised, or
    # the van synthesized OPT_SEND_FAILED): the sweeper retries it
    # immediately — and ONLY it, so one bad destination cannot trigger
    # duplicate sends of the request's healthy slices.
    retry_now: bool = False
    # The destination answered OPT_WRONG_OWNER (docs/elasticity.md):
    # the sweeper re-SLICES this part under the current routing table
    # before re-routing — a range split mid-flight can divide one
    # slice across two new owners.
    wrong_owner: bool = False
    # Spread pull (docs/serving_reads.md): the destination may be a
    # replica, so the response's applied stamp is validated against
    # the worker's newest-seen push stamp before acceptance.
    replica_read: bool = False


@dataclass
class _PendingReq:
    """Deadline bookkeeping for one timestamp (PS_REQUEST_TIMEOUT —
    docs/fault_tolerance.md): the sweeper retries unresponded slices
    with exponential backoff against the failed-over destination, and
    after PS_REQUEST_RETRIES fails the request so wait(ts) raises
    TimeoutError instead of hanging."""

    ts: int
    push: bool
    pull: bool
    cmd: int
    deadline: float
    trace: int = 0
    attempt: int = 0
    # Wrong-owner re-routes (docs/elasticity.md) are counted apart from
    # ``attempt``: a bounce answers immediately, so a routing-table lag
    # of a few ms could otherwise burn the whole retry budget without a
    # single real failure.  Bounces are bounded separately (generous —
    # each one is a LIVE server actively answering).
    bounces: int = 0
    slices: List[_PendingSlice] = field(default_factory=list)
    val_dtype: object = None
    val_nbytes: int = 0
    codec: Optional[str] = None
    zpull: Optional[dict] = None
    tenant: int = 0


class MultiGetHandle:
    """Completion handle of one :meth:`KVWorker.multi_get` fan-out.

    One handle covers the whole serving request: ``wait()`` joins every
    sub-get (cache-served ones are already complete), collects per-sub
    failures into ``errors`` (index -> exception), and re-raises the
    FIRST failure only after every sibling finished — a shed or
    timed-out sub-get never strands or aborts the others (the per-sub
    fail-only-the-affected-keys contract, docs/batching.md)."""

    __slots__ = ("_worker", "timestamps", "outs", "errors", "cached")

    def __init__(self, worker: "KVWorker", n: int):
        self._worker = worker
        # Per-sub-get request timestamp; None = answered entirely from
        # the hot-key cache (no message left the worker).
        self.timestamps: List[Optional[int]] = [None] * n
        self.outs: List[Optional[np.ndarray]] = [None] * n
        self.errors: Dict[int, Exception] = {}
        self.cached = 0  # sub-gets served fully from the hot cache

    def __len__(self) -> int:
        return len(self.timestamps)

    def wait(self) -> List[Optional[np.ndarray]]:
        """Join every in-flight sub-get; returns the destination
        buffers.  Raises the first recorded per-sub error (Overload /
        Timeout / server-side apply error) AFTER all siblings
        completed; ``errors`` holds every failure by sub-get index."""
        first: Optional[Exception] = None
        for i, ts in enumerate(self.timestamps):
            if ts is None:
                continue
            try:
                self._worker.wait(ts)
            except Exception as exc:  # noqa: BLE001 - re-raised below
                self.errors[i] = exc
                if first is None:
                    first = exc
        if first is not None:
            raise first
        return self.outs


class KVWorker:
    """Client of the KV store (kv_app.h:134-300)."""

    def __init__(self, app_id: int, customer_id: int = 0, postoffice=None):
        self.po = postoffice or ps_mod.postoffice(Role.WORKER)
        # Executor clamped to <= 1 (like KVServer): _process's
        # last-response detection (num_response(ts)+1 >= expected) and
        # _finish's reassembly assume responses are handled one at a
        # time — two executor threads racing it would drop pull data.
        self._customer = Customer(
            app_id, customer_id, self._process, self.po,
            executor_workers=min(
                1, self.po.env.find_int("PS_CUSTOMER_EXECUTOR", 0)
            ),
        )
        self._mu = threading.Lock()
        self._callbacks: Dict[int, Callable[[], None]] = {}
        self._recv_kvs: Dict[int, List[KVPairs]] = {}
        self._pull_dst: Dict[int, Tuple[np.ndarray, np.ndarray, Optional[np.ndarray]]] = {}
        self._slicer = default_slicer
        # Zero-copy pull (is_worker_zpull_, kv_app.h:727-792): buffers
        # allocated via alloc_pull_buffer are transport-backed (shm van);
        # servers write their response slices directly into them and
        # _finish skips reassembly.  Ordinary caller buffers reassemble as
        # usual; the ICI engine path never reaches _finish at all.
        self._zpull_bufs: Dict[Tuple[int, int, int], dict] = {}
        self._zpull_ts: set = set()
        self.zpull_hits = 0  # pulls completed without reassembly
        # Timestamps whose response carried OPT_APPLY_ERROR (the server
        # handler raised): wait(ts) raises instead of hanging/returning
        # unapplied data, and completion callbacks are suppressed.  An
        # bounded FIFO so eviction drops the OLDEST entry (set.pop
        # would evict arbitrarily — possibly the very ts a caller is
        # about to wait on).
        self._error_ts = BoundedKeySet(4096)
        # Timestamps whose response carried OPT_OVERLOAD (the server
        # shed the request under per-tenant admission control —
        # docs/qos.md): wait(ts) raises the RETRYABLE OverloadError.
        self._overload_ts = BoundedKeySet(4096)
        # Multi-tenant QoS (docs/qos.md): this worker's default tenant
        # (PS_TENANT names it; per-op tenant= overrides) and the shared
        # tenant table.
        self.tenants = tenants_mod.table_for(self.po.env)
        self._tenant = self.tenants.resolve(
            self.po.env.find("PS_TENANT") or None
        )
        # Hot-key pull cache (kv/hot_cache.py, PS_HOT_CACHE=1): repeat
        # pulls of hot keys answer locally, invalidated by the push-
        # version stamp piggybacked on responses.
        self._hot_cache: Optional[HotKeyCache] = None
        if self.po.env.find_int("PS_HOT_CACHE", 0):
            self._hot_cache = HotKeyCache(
                max_bytes=int(self.po.env.find_float(
                    "PS_HOT_CACHE_MB", 64.0) * (1 << 20)),
                ttl_s=self.po.env.find_float("PS_HOT_CACHE_TTL_S", 1.0),
                metrics=self.po.metrics,
            )
        # Raw-response timestamps (fetch_hot_keys): _finish stashes the
        # per-server response KVPairs instead of scattering them into a
        # destination buffer.
        self._raw_ts: set = set()
        self._raw_results: Dict[int, List[KVPairs]] = {}
        self._c_overloads = self.po.metrics.counter("kv.overloads")
        # Small-op aggregation (kv/batching.py, docs/batching.md):
        # PS_BATCH_BYTES > 0 turns on the per-(destination, tenant,
        # priority, codec) combiner — concurrently-issued small ops to
        # one destination coalesce into EXT_BATCH frames under the byte
        # cap, closing at the next dispatcher pickup
        # (PS_BATCH_WINDOW_US=0, the default) so an idle worker adds no
        # timer latency.  0 (the conservative default) bypasses the
        # plane entirely: every frame is byte-identical to a pre-batch
        # build.  64 KiB is the recommended serving-storm setting
        # (docs/batching.md).
        self._batch_bytes = max(0, self.po.env.find_int("PS_BATCH_BYTES",
                                                        0))
        self._combiner = None
        # Per-destination capability (docs/batching.md): None = probe
        # in flight (ops pass through unbatched meanwhile), True/False
        # = answered.  PS_BATCH_NEGOTIATE=0 asserts a homogeneous
        # cluster and skips the probe round trip.
        self._batch_caps: Dict[int, bool] = {}
        self._batch_probe_ts: Dict[int, int] = {}
        self._batch_probing: set = set()
        self._batch_negotiate = bool(
            self.po.env.find_int("PS_BATCH_NEGOTIATE", 1))
        if self._batch_bytes > 0:
            from .batching import OpCombiner

            if getattr(self.po, "elastic", False):
                # Declined under elastic membership (docs/batching.md):
                # wrong-owner re-slicing is per sub-op machinery the
                # batched request path does not carry.
                log.warning("PS_BATCH_BYTES set but PS_ELASTIC is "
                            "active; small-op batching disabled")
                self._batch_bytes = 0
            else:
                self._combiner = OpCombiner(
                    lambda m: self.po.van.send(m),
                    self._batch_send_failed,
                    max_bytes=self._batch_bytes,
                    window_us=self.po.env.find_float(
                        "PS_BATCH_WINDOW_US", 0.0),
                    min_ops=self.po.env.find_int("PS_BATCH_MIN_OPS", 32),
                    hold_max_us=self.po.env.find_float(
                        "PS_BATCH_HOLD_US", 2000.0),
                    on_sent=self._batch_sent,
                    tracer=self.po.tracer,
                )
        # Dense buckets / sparse tables routed through the collective engine
        # (ICI van): (nkeys, first, last) -> bucket name (full key arrays
        # compared on lookup).
        self._dense_routes: Dict[Tuple[int, int, int], str] = {}
        # Quantized transport tier (docs/compression.md): per-bucket
        # default codec ((nkeys, first, last) -> (keys, codec name),
        # registered via register_bucket) and the worker-side error-
        # feedback bank — push quantization error folds into the NEXT
        # push of the same slice before encoding (PS_CODEC_EF=0 off).
        self._bucket_codecs: Dict[Tuple[int, int, int],
                                  Tuple[np.ndarray, Optional[str]]] = {}
        self._codec_ef = (
            codecs_mod.ErrorFeedback(codecs_mod.ef_slots(self.po.env),
                                     metrics=self.po.metrics)
            if codecs_mod.ef_enabled(self.po.env) else None
        )
        self._c_codec_raw = self.po.metrics.counter("codec.raw_bytes")
        self._c_codec_wire = self.po.metrics.counter("codec.wire_bytes")
        self._device_results: Dict[int, object] = {}
        self._engine_pool = None  # lazy completion executor (engine path)
        self._stage_clock = stage_clock()
        self._note = self._stage_clock.note  # one C call an op
        # Last completion per pinned bucket: the next pinned pull joins it
        # before donating the previous result (one-outstanding contract).
        self._pinned_pull_futs: Dict[str, Callable] = {}
        # Bounded requests + failover (docs/fault_tolerance.md):
        # PS_REQUEST_TIMEOUT (seconds, 0 = off) deadlines every message-
        # path request; a sweeper thread retries expired slices with
        # exponential backoff, re-routing a dead rank's slice to its
        # first live replica when PS_KV_REPLICATION is on; after
        # PS_REQUEST_RETRIES the request fails and wait(ts) raises
        # TimeoutError.  _down_servers mirrors the failure detector's
        # NODE_FAILURE broadcasts via the postoffice hook registry.
        # Elastic membership (docs/elasticity.md) re-routes stale-epoch
        # slices through the sweeper, so deadlines default ON when the
        # cluster is elastic (an explicit PS_REQUEST_TIMEOUT still
        # wins, including an explicit 0).
        replica_reads = bool(self.po.env.find_int("PS_REPLICA_READS", 0))
        self._req_timeout = self.po.env.find_float(
            "PS_REQUEST_TIMEOUT",
            10.0 if (replica_reads or getattr(self.po, "elastic", False))
            else 0.0,
        )
        self._req_retries = self.po.env.find_int("PS_REQUEST_RETRIES", 3)
        self._replication = self.po.env.find_int("PS_KV_REPLICATION", 1)
        # Replica read fan-out (docs/serving_reads.md): spread pure
        # pulls across each range's whole replica chain, validated
        # against the newest push stamp this worker has seen per
        # primary.  Needs the deadline/sweeper machinery — the stale-
        # replica fallback is a sweeper re-route — hence the timeout
        # default above.
        self._replica_reads = (
            replica_reads and self._replication >= 2
            and self.po.num_servers >= 2 and self._req_timeout > 0
        )
        self._read_policy = (self.po.env.find("PS_REPLICA_READ_POLICY")
                             or "sticky").strip().lower()
        self._rr_counter = itertools.count()
        # Cluster-truth source for the `load` policy: a ClusterHistory
        # whose windowed per-server pull rates rank the spread set
        # (attach_history; the scheduler's history when co-located).
        # None → this worker's local send counts, as before.
        self._cluster_history = None
        # Newest push stamp ACKNOWLEDGED to this worker, per node id —
        # the worker half of read-your-writes: a replica answer whose
        # applied stamp trails this floor is stale for THIS worker.
        self._seen_stamps: Dict[int, int] = {}
        self._read_share: Dict[int, int] = {}  # dest -> spread pulls
        self._c_replica_reads = self.po.metrics.counter(
            "replica_read.spread")
        self._c_replica_fallbacks = self.po.metrics.counter(
            "replica_read.fallbacks")
        self._fallback_logged = 0.0
        self._down_servers: set = set()
        # Dead ranks whose first failover re-route was already flight-
        # recorded (one event per outage TRANSITION — _route runs per
        # slice, and per-message recording would wrap the bounded ring
        # with identical spam, evicting the context a postmortem needs).
        self._failover_logged: set = set()
        self._pending: Dict[int, _PendingReq] = {}
        self._static_entries = None  # _route_entries cache (non-elastic)
        self._timeout_ts = BoundedKeySet(4096)
        self._sweep_thread: Optional[threading.Thread] = None
        self._sweep_cv = threading.Condition()
        self._sweep_stop = False
        # Telemetry (docs/observability.md): request-latency histograms
        # (message path, send → last response), failure-path counters,
        # and per-ts trace bookkeeping for the distributed spans.
        self._c_pushes = self.po.metrics.counter("kv.pushes")
        self._c_pulls = self.po.metrics.counter("kv.pulls")
        # Engine-path ops completed on the kv-engine-complete thread (they
        # carried ``out`` or ``callback``); the others complete in wait().
        self._c_threaded = self.po.metrics.counter("kv.complete.threaded")
        self._h_push_lat = self.po.metrics.histogram("kv.push_latency_s")
        self._h_pull_lat = self.po.metrics.histogram("kv.pull_latency_s")
        self._c_timeouts = self.po.metrics.counter("kv.timeouts")
        self._c_failovers = self.po.metrics.counter("kv.failovers")
        self._c_retries = self.po.metrics.counter("kv.retries")
        # ts -> (monotonic start, pull?, trace id, wall-aligned start
        # us, parent trace id — multi_get fan-outs link their sub-gets)
        self._req_track: Dict[int, Tuple[float, bool, int, float,
                                         int]] = {}
        # ts -> failure-class outcome ("error"/"shed"/"timeout"/
        # "retry"/"wrong_owner"/"send_failed"), set on the failure
        # paths and consumed by the tail-keep decision at completion
        # (docs/observability.md) — an errored request's trace is
        # always interesting.
        self._req_outcome: Dict[int, str] = {}
        # Tail-based tracing: the rolling slow threshold falls back to
        # these local histograms when no TRACE_PULL hint is fresh.
        if getattr(self.po.tracer, "tail", None) is not None:
            self.po.tracer.set_tail_source("push", self._h_push_lat)
            self.po.tracer.set_tail_source("pull", self._h_pull_lat)
        self.po.register_node_failure_hook(self._on_node_event)
        # Elastic routing (docs/elasticity.md): wrong-owner bounce
        # accounting, throttled stale-table pulls, and the routing hook
        # that invalidates migrated hot-cache entries.
        self._c_wrong_owner = self.po.metrics.counter(
            "kv.wrong_owner_bounces")
        self._last_routing_pull = 0.0
        self._routing_hook = self._on_routing
        self.po.register_routing_hook(self._routing_hook)

    @property
    def engine(self):
        """Collective engine when running over the ICI van, else None."""
        return getattr(self.po.van, "engine", None)

    def set_slicer(self, slicer) -> None:
        """Custom slicer hook (kv_app.h:256-265)."""
        self._slicer = slicer

    # -- quantized transport tier (docs/compression.md) ----------------------

    def register_bucket(self, keys, codec: Optional[str] = None) -> None:
        """Register a default wire codec for exactly these keys: every
        ``push``/``pull`` of this key set then travels codec-encoded
        (``'int8'``, ``'fp8_e4m3'``, ``'bf16'``) unless the call
        overrides with ``codec=`` (``codec='raw'`` forces uncompressed).
        ``codec=None`` unregisters.  Message-path only — the collective
        (ICI) plane needs no wire compression and ignores it.  What
        ``codec='bf16'`` is to this path, a dense bucket's ``job_dtype``
        (:meth:`register_dense`) is to the engine's: the same contract by
        two routes, values rounded to 16 bits on the way and an f32 store
        that no rounding reaches."""
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        log.check(len(keys) > 0, "register_bucket: empty key set")
        if codec is not None:
            codecs_mod.get_codec(codec)  # fail loudly on unknown names
        sig = (len(keys), int(keys[0]), int(keys[-1]))
        with self._mu:
            if codec is None:
                self._bucket_codecs.pop(sig, None)
            else:
                self._bucket_codecs[sig] = (keys, codec)

    def _resolve_tenant(self, tenant) -> int:
        """Effective tenant id of one op: the explicit ``tenant=``
        (name or id) when given, else this worker's PS_TENANT default."""
        if tenant is None:
            return self._tenant
        return self.tenants.resolve(tenant)

    def _resolve_codec(self, keys: np.ndarray,
                       codec: Optional[str],
                       compress: Optional[str]) -> Optional[str]:
        """Effective codec of one op: explicit ``codec=`` (or the
        legacy ``compress=`` alias) wins, then the registered bucket
        default; ``'raw'`` forces uncompressed."""
        if codec is None:
            codec = compress  # legacy alias (kept for callers/docs)
        if codec == "raw":
            return None
        if codec is not None:
            codecs_mod.get_codec(codec)
            return codec
        if not self._bucket_codecs:
            return None  # no registered buckets: skip the sig lookup
        if len(keys) == 0:
            return None
        sig = (len(keys), int(keys[0]), int(keys[-1]))
        with self._mu:
            ent = self._bucket_codecs.get(sig)
        if ent is not None and np.array_equal(ent[0], keys):
            return ent[1]
        return None

    def _encode_part(self, codec_name: str, group_rank: int,
                     part: KVPairs) -> _EncodedSlice:
        """Encode one slice's payload (once — retries re-send these
        exact bytes), folding in the worker-side EF residual for this
        (destination, slice)."""
        codec = codecs_mod.get_codec(codec_name)
        lens = (None if part.lens is None
                else np.asarray(part.lens, dtype=np.int64))
        if self._codec_ef is not None:
            # Slot identity must pin the EXACT key set: two buckets
            # sharing (rank, first key, size) would otherwise cross-
            # fold each other's residuals — crc32 over the key bytes
            # is ~C-speed and collision-safe in practice.
            key = (group_rank, int(part.keys[0]),
                   zlib.crc32(part.keys), int(part.vals.size))
            resid, lock = self._codec_ef.slot(key, int(part.vals.size))
            with lock:
                codes, scales, flags = codec.encode(
                    part.vals, lens=lens, resid=resid
                )
        else:
            codes, scales, flags = codec.encode(part.vals, lens=lens)
        self._c_codec_raw.inc(part.vals.nbytes)
        self._c_codec_wire.inc(codes.nbytes + scales.nbytes)
        info = CodecInfo(codec=codec.wire_id, raw_len=part.vals.nbytes,
                         block=codec.block, flags=flags)
        return _EncodedSlice(codes=codes, scales=scales, lens=part.lens,
                             info=info)

    # -- zero-copy pull (is_worker_zpull_) -----------------------------------

    def alloc_pull_buffer(self, keys, val_len: int, dtype=np.float32):
        """Allocate a transport-backed pull destination for exactly these
        keys (fixed ``val_len`` values per key).

        Pulls of these keys into the returned array are delivered in
        place: each server writes its response slice directly into the
        buffer at the slice's offset and ``_finish`` skips reassembly —
        the ``is_worker_zpull_`` contract (kv_app.h:727-792).  Requires a
        transport with an ``alloc_pull_segment`` hook (shm van, same
        host); returns None when the transport can't back it (callers
        then pull into ordinary arrays).  Contract: at most one
        outstanding pull per buffer (kv_app.h:210-217).
        """
        if getattr(self.po, "elastic", False):
            # Elastic membership migrates ranges live; the per-server
            # byte offsets registered below would silently go stale on
            # the first epoch change.  Fail LOUDLY (the PR 9 silent
            # decline left callers pulling into ordinary arrays without
            # knowing why) — docs/elasticity.md documents the
            # workarounds the error names.
            raise ElasticZeroCopyError(
                "alloc_pull_buffer (zero-copy ZPull buffers) is "
                "incompatible with elastic membership: PS_ELASTIC=1 "
                "migrates key ranges live, which would silently "
                "invalidate the buffer's frozen per-server offsets. "
                "Pull into ordinary arrays instead, or disable "
                "PS_ELASTIC for this cluster."
            )
        alloc = getattr(self.po.van, "alloc_pull_segment", None)
        if alloc is None:
            return None
        if self._slicer is not default_slicer:
            # The per-server offsets below assume the default key-range
            # partition; a custom slicer would misplace slices silently.
            log.warning("alloc_pull_buffer: custom slicer set; zero-copy "
                        "pull disabled for this worker")
            return None
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        log.check(len(keys) > 0, "empty key set")
        sig = (len(keys), int(keys[0]), int(keys[-1]))
        with self._mu:
            old = self._zpull_bufs.get(sig)
        # Same (len, first, last) but DIFFERENT keys would silently free a
        # live buffer the caller still uses — refuse BEFORE allocating the
        # new segment; same keys is a legitimate reallocation.
        log.check(
            old is None or np.array_equal(old["keys"], keys),
            "alloc_pull_buffer: a different key set with the same "
            "signature is already registered; free_pull_buffer it first",
        )
        itemsize = np.dtype(dtype).itemsize
        total = len(keys) * val_len * itemsize
        buf_id = next(_ZPULL_SEQ)
        raw = alloc(buf_id, total)
        if raw is None:
            return None
        vals = raw[:total].view(np.dtype(dtype))
        # Per-server byte offsets of this buffer's slices (fixed-k layout,
        # mirroring DefaultSlicer's key-range partition).
        ranges = self.po.get_server_key_ranges()
        offsets = {}
        off = 0
        for rank, rng in enumerate(ranges):
            n = int(
                np.searchsorted(keys, rng.end)
                - np.searchsorted(keys, rng.begin)
            )
            offsets[rank] = off
            off += n * val_len * itemsize
        with self._mu:
            old = self._zpull_bufs.get(sig)
            self._zpull_bufs[sig] = {
                "buf_id": buf_id,
                "keys": keys,
                "vals": vals,
                "offsets": offsets,
            }
        if old is not None:
            # Re-registration: release the displaced segment instead of
            # leaking it until van shutdown.
            free = getattr(self.po.van, "free_pull_segment", None)
            if free is not None:
                free(old["buf_id"])
        return vals

    def free_pull_buffer(self, keys) -> None:
        """Release a registered pull buffer (and its transport segment)."""
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        sig = (len(keys), int(keys[0]), int(keys[-1]))
        with self._mu:
            reg = self._zpull_bufs.pop(sig, None)
        if reg is not None:
            free = getattr(self.po.van, "free_pull_segment", None)
            if free is not None:
                free(reg["buf_id"])

    def _zpull_lookup(self, keys: np.ndarray, vals) -> Optional[dict]:
        if self._slicer is not default_slicer:
            return None
        sig = (len(keys), int(keys[0]), int(keys[-1])) if len(keys) else None
        with self._mu:
            reg = self._zpull_bufs.get(sig)
        if reg is None or not isinstance(vals, np.ndarray):
            return None
        if vals is not reg["vals"] and not (
            vals.base is not None and np.shares_memory(vals, reg["vals"])
        ):
            return None
        if not np.array_equal(reg["keys"], keys):
            return None
        return reg

    # -- hot-key cache (kv/hot_cache.py) -------------------------------------

    @property
    def hot_cache(self) -> Optional[HotKeyCache]:
        """The worker's hot-key pull cache (None unless PS_HOT_CACHE=1)."""
        return self._hot_cache

    def fetch_hot_keys(self, k: int = 16,
                       timeout: Optional[float] = None) -> np.ndarray:
        """Ask every server for its ``kv.hot_keys`` top-k (the
        telemetry tracker's Space-Saving estimate) and seed the hot
        cache's admission set with the union.  Returns the keys.  The
        message-path analog of reading psmon's "hot keys" column —
        one tiny pull per server, cmd=HOT_KEYS_CMD."""
        entries = self._route_entries()
        ts = self._customer.new_request(SERVER_GROUP,
                                        num_responses=len(entries))
        with self._mu:
            self._raw_ts.add(ts)
        try:
            for rng, owner in entries:
                msg = Message()
                m = msg.meta
                m.app_id = self._customer.app_id
                m.customer_id = self._customer.customer_id
                m.request = True
                m.pull = True
                m.head = HOT_KEYS_CMD
                m.timestamp = ts
                m.recver = self._route(owner)
                m.val_len = int(k)  # how many hot keys we want back
                m.key = int(rng.begin)
                msg.add_data(SArray(np.array([rng.begin],
                                             dtype=np.uint64)))
                msg.add_data(SArray(np.empty(0, np.float32)))
                self.po.van.send(msg)
            self._customer.wait_request(ts, timeout)
        finally:
            with self._mu:
                chunks = self._raw_results.pop(ts, [])
                self._raw_ts.discard(ts)
        keys = (np.concatenate([c.keys for c in chunks])
                if chunks else np.empty(0, np.uint64))
        if self._hot_cache is not None and len(keys):
            self._hot_cache.seed(keys)
        return keys

    def seed_hot_cache(self, k: int = 16) -> np.ndarray:
        """Fetch the servers' hot keys AND warm the cache: one pull of
        nothing (the fetch) plus the first real pulls of those keys by
        the caller fill it.  Returns the seeded keys."""
        return self.fetch_hot_keys(k=k)

    # -- ICI collective fast path -------------------------------------------

    def register_dense(self, name: str, keys, val_len: Optional[int] = None,
                       dtype=None, init=None, lens=None, flags=None,
                       job_dtype=None, shapes=None):
        """Register a dense bucket on the collective engine; subsequent
        push/pull on exactly these keys ride jitted ICI collectives.  The
        analog of the reference's first-touch rendezvous + registration
        (rdma_van.h:520-548).

        ``val_len`` values a key, or ``lens``: each key's own length, as
        ``KVPairs.lens`` gives it on the message path.  A call on these
        keys that carries no ``lens``, or the registered ones, is then the
        engine's; ``flags`` (a word a key, ``parallel.engine.KEY_NO_DECAY``
        / ``KEY_NO_ADAPT`` / ``KEY_ELEMENTWISE``) is read by a server handle
        that treats keys apart (``lamb:...``, ``muon:...``); ``shapes``
        (``(rows, cols)`` a key, ``rows * cols`` the key's length) by one
        that works on whole matrices (``muon:...``: a key not flagged
        ``KEY_ELEMENTWISE`` is a matrix to it, and it refuses by name where
        a matrix would lie across chips).

        ``job_dtype`` (default ``dtype``): what this job pushes and what
        ``push_pull`` / ``pull`` hand back, where it is narrower than the
        store: ``dtype=float32, job_dtype=bfloat16`` keeps f32 master
        parameters and moments on the server under a bf16 model.  A
        gradient is widened exactly and summed over W in f32; a pulled
        value is the stored one rounded to nearest-even.  The gradient is
        rows ``[W, total]`` as for any bucket, of ``job_dtype`` or
        refused (a device array of another shape too: nothing is laid out
        anew on the way); so is a call that would leave the engine for the
        message path (a custom ``cmd``).  An ``out`` of another dtype is
        given the values converted, as for any bucket.  See
        ``CollectiveEngine.register_dense``."""
        log.check(self.engine is not None,
                  "register_dense requires the ici van")
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        engine = self.engine
        old = engine._buckets.get(name)
        if old is not None:
            # A name registered again under other keys: its old signature
            # must not route to it (one- and two-key sets are not
            # compared, see _engine_route).
            self._dense_routes.pop(
                (len(old.keys), old.keys.item(0), old.keys.item(-1)), None)
        bucket = engine.register_dense(name, keys, val_len, dtype=dtype,
                                       init=init, lens=lens, flags=flags,
                                       job_dtype=job_dtype, shapes=shapes)
        self._dense_routes[(len(keys), keys.item(0), keys.item(-1))] = name
        self._routes_mixed = any(
            engine.bucket(n).mixed for n in self._dense_routes.values())
        # From the buckets registered now: registering a small bucket in a
        # large one's place lifts it again.
        self._results_heavy = any(
            engine.bucket(n).nbytes * self._MAX_DEVICE_RESULTS
            > self._DEVICE_RESULTS_BYTES
            for n in self._dense_routes.values())
        return bucket

    def reshard(self, mesh) -> None:
        """Coordinated elastic recut of the collective data plane onto a
        new mesh (every worker of the cluster must call this with the
        same mesh — see ``_IciDataPlane.reshard_engines``).  Registered
        bucket/table names stay valid; key ranges are recut and
        programs rebuild lazily on the next op."""
        hook = getattr(self.po.van, "reshard_engines", None)
        log.check(hook is not None,
                  "reshard requires an ICI van (collective data plane)")
        hook(mesh, customer_id=self._customer.customer_id)

    def register_pull_buffer(self, name: str):
        """Pin a persistent device pull buffer for a registered dense
        bucket (the UCX PinMemory / w_pool_ contract at the app level):
        every engine ``pull`` for ``name`` then lands in the same HBM
        buffer (``push_pull`` keeps its own fresh outputs and is NOT
        pinned).  Back-to-back pinned pulls serialize on the previous
        completion — the registered-buffer one-outstanding contract.
        Returns the initial buffer; see
        ``CollectiveEngine.register_pull_buffer``."""
        log.check(self.engine is not None,
                  "register_pull_buffer requires the ici van")
        return self.engine.register_pull_buffer(name)

    def _engine_route(self, keys: np.ndarray, cmd: int = 0,
                      lens=None) -> Optional[str]:
        """Bucket name iff these exact keys are registered and the request
        carries nothing the collective path cannot express (a custom cmd,
        or ``lens`` on a bucket registered with one ``val_len``, fall back
        to the message path).  ``lens`` on a bucket registered with its
        keys' own lengths must be those lengths."""
        engine = self.engine
        n = len(keys)
        if engine is None or n == 0:
            return None
        if cmd != 0:
            if self._routes_mixed:
                self._refuse_mixed_off_engine(keys, f"a custom cmd ({cmd})")
            return None
        name = self._dense_routes.get((n, keys.item(0), keys.item(-1)))
        if name is None:
            return None
        # Of one or two keys the signature (len, first, last) is the key
        # set; a longer set can share it and differ in between.
        if n > 2 and not np.array_equal(engine.bucket(name).keys, keys):
            return None
        if lens is not None:
            have = engine.bucket(name).lens
            if have is None:
                return None
            log.check(np.array_equal(have, np.asarray(lens).reshape(-1)),
                      f"bucket {name!r} was registered with other lens than "
                      f"this call carries: a registered key keeps its "
                      f"length (register the bucket again to change it)")
        return name

    _routes_mixed = False  # a registered dense bucket has a job dtype

    def _refuse_mixed_off_engine(self, keys: np.ndarray, why: str) -> None:
        """A call on the keys of a mixed bucket that the engine path cannot
        take would go to the message path, whose servers know nothing of
        the bucket's f32 store: refused by name."""
        name = self._dense_routes.get(
            (len(keys), keys.item(0), keys.item(-1)))
        if name is None:
            return
        bucket = self.engine.bucket(name)
        if bucket.mixed and np.array_equal(bucket.keys, keys):
            self.engine._refuse_mixed(
                bucket, f"the message path (this call carries {why})",
                "call push_pull, push or pull with cmd 0")

    # Device results kept for get_pulled(): the last 8.  While a dense
    # bucket is registered of which 8 pulled copies would pass
    # _DEVICE_RESULTS_BYTES (a whole tree in one bucket), of those 8 the
    # newest that together stay within it (_trim_results): small results
    # keep their window beside a tree, which keeps its last one.
    _MAX_DEVICE_RESULTS = 8
    _DEVICE_RESULTS_BYTES = 2 << 30
    _results_heavy = False

    def _engine_op(self, op, args, keys=None, cmd: int = 0, lens=None,
                   out=None, callback=None, keep_result: bool = False,
                   pull: bool = False,
                   handle: Optional[str] = None,
                   tables: int = 0,
                   pool: Optional[str] = None) -> Optional[int]:
        """One op of the collective path: route, the engine's op, then
        timestamp + async completion.  None where ``keys`` are no
        registered bucket: the op is the message path's.

        The stages are stamped for the ``StageClock``
        (``utils.profiling.STAGES``): ``route`` for a dense call (``keys``
        given; a sparse call routes nothing, its table's name is the
        first of ``args``), the engine's ``op(name, *args)``, which notes
        ``select``, ``prep`` and ``launch`` itself, then ``dispatch``,
        the rest of this method.  While a profiler session runs the op
        lies in a ``ps.kv.op`` span, whose metadata names the op's kind
        (``op``, one of ``profiling.LAUNCH_OPS``) and the kind
        of the server handle: the one a call brought (``push_sparse``; the
        engine is given it among ``args``), for a dense op the engine's
        own (``adam``, ``lamb``, ``muon``).  Callers pass everything by
        position, and the dispatch is not a method of its own: on the
        chip's host a Python call costs this path 2-3 us, a keyword call
        half a microsecond more (PERF.md, PR 24).

        Completion, by what the call itself carries.  With ``out`` or
        ``callback`` (and for a pinned pull, whose completion the next
        pull joins): device done -> host copy -> callback run on the
        dedicated ``kv-engine-complete`` thread, so callbacks fire
        without wait() and the D2H copy stays off the issuing thread,
        matching the message path; wait(ts) joins that future, and the op
        counts in ``kv.complete.threaded``.  With neither there is
        nothing for a thread to do but block, so none is woken: the wait
        hook (``_engine_ready``) blocks on the result itself, on the
        waiting thread, and lets go of the array after its first run.

        The op's result must be a NON-donated array: pushes hand back a
        tiny completion token (the store itself is donated by the next
        push of the same bucket, so blocking on it would crash
        back-to-back pushes); pulls hand back the gathered output.

        ``pull`` marks a dense pull: its result is retained for
        get_pulled() unless the bucket's pull buffer is pinned — a pinned
        result is donated by the NEXT pull, so retaining it would hand
        out deleted arrays; its completion is what that next pull joins.

        ``tables`` marks a GROUPED sparse op (``pull_sparse_group`` /
        ``push_sparse_group``) and says how many tables it carries: the
        first of ``args`` is then their names, the op goes by the first of
        them, and its span also carries ``tables``.  A grouped pull's
        result is a ``PulledGroup``: nothing here, in ``wait`` or in the
        completion touches an entry of it (a cut is a launch), only its
        class arrays; its ``out`` is a list, a host buffer a table.

        ``pool`` marks a sparse op whose lookups are BAGS (``pool="sum"``;
        the engine is given it last among ``args``, and nothing where the
        call gave none: the op without it is the call it has always been):
        its span carries ``pool``.
        Nothing else here knows a bag: a pooled pull's result is ``[W, B,
        d]``, a row a bag, a grouped one's entries lie side by side as rows
        do.
        """
        span = TraceAnnotation(OP_SPAN) if tracing() else None
        if span is not None:
            span.__enter__()
        t0 = stamp()
        route_ns = -1
        if keys is not None:
            name = self._engine_route(
                np.asarray(keys, dtype=np.uint64), cmd, lens)
            if name is None:
                if span is not None:
                    span.__exit__(None, None, None)
                return None
            args = (name, *args)
            route_ns = stamp() - t0
        name = args[0]
        result = op(*args)
        t2 = stamp()  # launch | dispatch, but for the way back up
        if tables:
            name = name[0]
        pinned = pull and self.engine.pinned_pull_buffer(name) is not None
        if pull:
            keep_result = not pinned
        ts = self._customer.new_request(SERVER_GROUP, 0)
        if keep_result:
            with self._mu:
                self._device_results[ts] = result
                while len(self._device_results) > self._MAX_DEVICE_RESULTS:
                    self._device_results.pop(next(iter(self._device_results)))
                if self._results_heavy:
                    self._trim_results()
        if out is None and callback is None and not pinned:
            hook = partial(self._engine_ready, ts, name, [result],
                           threading.Lock())
        else:
            with self._mu:
                if self._engine_pool is None:
                    import concurrent.futures

                    self._engine_pool = concurrent.futures.ThreadPoolExecutor(
                        1, "kv-engine-complete")
            hook = self._engine_pool.submit(
                self._engine_complete, ts, name, result, out, callback
            ).result
            self._c_threaded.inc()
            if pinned:
                self._pinned_pull_futs[name] = hook
        self._customer.add_wait_hook(ts, hook)
        t3 = stamp()
        self._note((KV_OP, t3, route_ns, t3 - t2, -1))
        if span is not None:
            if handle is None and keys is not None:
                # A dense op runs under the engine's own handle.
                handle = self.engine._server_handle
            # Which of ``LAUNCH_OPS`` it is, by the method it was handed
            # (a grouped op is its kind; ``_engine_pull`` is the dense pull).
            kind = op.__name__.rpartition("engine_")[2].removesuffix("_group")
            meta = {"ts": ts, "name": name,
                    "op": ("sparse." if keys is None else "dense.") + kind}
            if isinstance(handle, str):
                meta["handle"] = handle.partition(":")[0]
            if tables:
                meta["tables"] = tables
            if pool is not None:
                meta["pool"] = pool
            bucket = (self.engine._buckets.get(name)
                      if keys is not None else None)
            if bucket is not None and bucket.mixed:
                # Pushed and pulled in another dtype than it is kept.
                meta["job"] = str(bucket.job_dtype)
            if bucket is not None and bucket.owned is not None:
                # Sharded on its keys' borders: over how many owners.
                meta["owners"] = bucket.owned.shards
            span.set_metadata(**meta)
            span.__exit__(None, None, None)
        return ts

    def _trim_results(self) -> None:
        """Let go of the oldest kept device results until the rest hold
        at most ``_DEVICE_RESULTS_BYTES``; the newest stays whatever its
        size.  Call with ``_mu`` held."""
        kept = self._device_results
        room = self._DEVICE_RESULTS_BYTES
        stamps = list(kept)
        for i in range(len(stamps) - 1, -1, -1):
            result = kept[stamps[i]]
            room -= (sum(r.nbytes for r in result.arrays)  # a grouped pull's
                     if type(result) is PulledGroup
                     else getattr(result, "nbytes", 0))
            if room < 0 and i < len(stamps) - 1:
                for old in stamps[:i + 1]:
                    del kept[old]
                return

    def _engine_ready(self, ts: int, name: str, box: list, lock) -> None:
        """Wait hook of an op with nothing to copy and no callback: the
        first wait blocks on the result where it stands and lets go of
        the array (the customer keeps its last 256 hooks, which must not
        pin 256 pulled buffers); a wait meanwhile returns when the first
        has, a later one at once."""
        with lock:
            if box:
                self._engine_complete(ts, name, box[0], None, None)
                box.clear()

    def _engine_pull(self, name: str):
        """``engine.pull`` under the registered-buffer contract
        (kv_app.h:210-217 for the reference's pinned buffers): at most
        one outstanding pull per pinned bucket — the next pull donates
        the previous result's buffer, so dispatching it while the
        completion thread still copies would use-after-donate."""
        if self.engine.pinned_pull_buffer(name) is not None:
            prev = self._pinned_pull_futs.get(name)
            if prev is not None:
                prev()
        return self.engine.pull(name)

    def _engine_complete(self, ts: int, name: str, result, out, callback):
        """On the ``kv-engine-complete`` thread, or on the waiting thread
        for an op that has neither ``out`` nor ``callback``
        (``_engine_ready``): stage ``complete.wait`` (blocked on the
        device), then ``complete.copy`` (host work), each in a span that
        carries the op's ``ts`` while a profiler session runs."""
        traced = tracing()
        t0 = stamp()
        span = (TraceAnnotation(COMPLETE_SPANS[0], ts=ts, name=name)
                if traced else None)
        if span is not None:
            span.__enter__()
        grouped = type(result) is PulledGroup  # a grouped sparse pull's
        if grouped:
            for r in result.arrays:  # a class of its entries each
                r.block_until_ready()
        else:
            result.block_until_ready()
        if span is not None:
            span.__exit__(None, None, None)
        t1 = stamp()
        span = (TraceAnnotation(COMPLETE_SPANS[1], ts=ts, name=name)
                if traced else None)
        if span is not None:
            span.__enter__()
        if out is not None:
            if grouped:
                # One copy to the host a class; a table's buffer is filled
                # from its rows of that copy.
                hosts = [self._host_rows(r) for r in result.arrays]
                for (c, off, n), o in zip(result.entries, out):
                    self._fill(o, hosts[c][:, off:off + n])
            else:
                self._fill(out, self._host_rows(result))
        if callback is not None:
            callback()
        if span is not None:
            span.__exit__(None, None, None)
        t2 = stamp()
        self._note((COMPLETED, t2, t1 - t0, t2 - t1, -1))
        if not ts & 1023:  # now and then, and not while an op is issued
            self._stage_clock.fold()

    @staticmethod
    def _host_rows(result) -> np.ndarray:
        """One device result on the host."""
        if getattr(result, "is_fully_addressable", True) or getattr(
            result, "is_fully_replicated", False
        ):
            return np.asarray(result)
        # Multi-process mesh, worker-sharded result (sparse pull): this
        # process's rows are its addressable shards, in global row order
        # (along axis 0, the workers': a grouped pull's classes lie along
        # the lookup axis, which this does not touch).
        shards = sorted(
            result.addressable_shards,
            key=lambda s: tuple(sl.start or 0 for sl in s.index),
        )
        return np.concatenate([np.asarray(s.data) for s in shards], axis=0)

    @staticmethod
    def _fill(out, host) -> None:
        """A host array's values into the caller's host buffer, flat."""
        np.copyto(
            out.reshape(-1),
            host.reshape(-1)[: out.size].astype(out.dtype, copy=False),
        )

    def get_pulled(self, ts: int):
        """Device-resident pull result for a recent engine-path timestamp
        (bounded window of the last few results).  Of a grouped sparse pull
        a ``parallel.sparse.PulledGroup``: a read-only sequence of the
        entries' ``[W, n_i, d_i]`` rows in the call's order, over ONE device
        array a class of ``(d, dtype)`` (``.arrays``, with ``.entries`` the
        ``(class, offset, n)`` of each).  ``seq[i]`` and iteration CUT an
        entry from its class's array when asked: an eager slice, a launch
        and a buffer each.  A jitted forward pass takes the sequence itself
        (a pytree of ``.arrays``) and cuts inside, where a slice is free."""
        with self._mu:
            return self._device_results.get(ts)

    def coalescer(self, handle=None, **kw):
        """Coalescing async dispatcher over the worker's collective
        engine: per-op ``push_pull(name, grads)`` tickets micro-batch
        into ONE grouped program per window (the dispatch-amortized form
        of N concurrent ZPushes; see parallel/coalesce.py)."""
        log.check(self.engine is not None,
                  "coalescer requires the collective engine (ICI van)")
        return self.engine.coalescer(handle=handle, **kw)

    def replay(self, name: str, grads_seq, keep: str = "all"):
        """Fused multi-step push_pull on a registered dense bucket: T
        steps compiled into ONE program (engine.replay — lax.scan over
        the donated store).  Returns the pulled results device-resident
        (``[T, total]`` for keep="all", ``[total]`` for keep="last");
        np.asarray materializes."""
        log.check(self.engine is not None,
                  "replay requires the collective engine (ICI van)")
        return self.engine.replay(name, grads_seq, keep=keep)

    def push_pull_stream(self, name: str, grads_iter, depth: int = 2):
        """Host-origin streaming push_pull on a registered dense bucket:
        host->HBM staging pipelined against the collectives
        (engine.push_pull_stream).  Yields device-resident results."""
        log.check(self.engine is not None,
                  "push_pull_stream requires the collective engine "
                  "(ICI van)")
        return self.engine.push_pull_stream(name, grads_iter, depth=depth)

    def push_sparse(self, name: str, indices, grads,
                    handle: Optional[str] = None, callback=None,
                    pool: Optional[str] = None) -> int:
        """Sparse push: [W, n] rows + [W, n, d] grads into the sharded
        table.  With no ``handle`` they are scatter-added (the
        aggregation server handle); ``handle="row_adagrad:lr,eps"`` has
        the server apply row-wise Adagrad to the rows the push touches,
        its accumulator kept beside the table (``SparseEngine.push``).
        The handle is the call's, as ``CollectiveEngine.push_pull(name,
        grads, handle)`` has it: an unknown one fails at the first push,
        by name.

        ``pool="sum"``: a lookup is a BAG of ids whose rows the job takes
        summed (an ``EmbeddingBag``).  indices ``[W, B, h]``, a worker's
        ``B`` bags of ``h`` ids; grads ``[W, B, d]``, ONE gradient a bag,
        which every slot of the bag brings to its row, under the handle or
        without: what a push of ``[W, B * h]`` ids with each gradient
        repeated ``h`` times does, without that array.  A row that lies
        twice in a bag receives it twice; bags of one id are rows."""
        eng = getattr(self.po.van, "sparse_engine", None)
        log.check(eng is not None, "push_sparse requires the ici van")
        args = (name, indices, grads, handle)
        return self._engine_op(eng.push,
                               args if pool is None else (*args, pool),
                               None, 0, None, None, callback, False, False,
                               handle, 0, pool)

    def pull_sparse(self, name: str, indices, out=None,
                    callback=None, pool: Optional[str] = None) -> int:
        """Sparse pull: [W, n] rows -> ``get_pulled(ts)`` ``[W, n, d]``
        (``out``: a host buffer of as many values).  ``pool="sum"``:
        indices ``[W, B, h]``, bags of ``h`` ids -> ``[W, B, d]``, each
        bag's rows summed in f32 where the table lives; a row that lies
        twice in a bag is added twice."""
        eng = getattr(self.po.van, "sparse_engine", None)
        log.check(eng is not None, "pull_sparse requires the ici van")
        return self._engine_op(eng.pull,
                               (name, indices) if pool is None
                               else (name, indices, pool), None, 0,
                               None, out, callback, True, False, None, 0,
                               pool)

    def push_sparse_group(self, names, indices_list, grads_list,
                          handle: Optional[str] = None, callback=None,
                          pool: Optional[str] = None) -> int:
        """A step's rows of SEVERAL tables in one op: one timestamp, one
        program, one launch (``SparseEngine.push_group``).  A table's
        semantics are :meth:`push_sparse`'s own, ``handle`` applies to every
        table of the group, and a table may not appear twice (its store is
        donated to the program once).  ``pool="sum"`` applies to every table
        too, each with a bag size of its own: table ``t``'s indices ``[W, B,
        h_t]``, its gradients ``[W, B, d]``."""
        eng = getattr(self.po.van, "sparse_engine", None)
        log.check(eng is not None, "push_sparse_group requires the ici van")
        args = (names, indices_list, grads_list, handle)
        return self._engine_op(eng.push_group,
                               args if pool is None else (*args, pool),
                               None, 0, None, None, callback, False, False,
                               handle, len(names), pool)

    def pull_sparse_group(self, names, indices_list, outs=None,
                          callback=None, pool: Optional[str] = None) -> int:
        """The rows of SEVERAL tables in one op (``SparseEngine.pull_group``):
        the program gives one array ``[W, sum n_i, d]`` a class of ``(d,
        dtype)`` among the entries, ``get_pulled(ts)`` is the ``PulledGroup``
        over them (the entries' ``[W, n_i, d_i]`` rows in ``names`` order,
        each cut when asked for: see :meth:`get_pulled`), and ``wait(ts)``
        returns when every row is there.  With ``outs`` (a host buffer a
        table) each CLASS is copied to the host once, on the completion
        thread, and each table's buffer filled from its rows of that copy,
        as ``pull_sparse(..., out=)`` copies one.  ``pool="sum"``: table
        ``t``'s indices are bags ``[W, B, h_t]`` and its entry the pooled
        rows ``[W, B, d]`` (:meth:`pull_sparse`), a class's side by side
        ``[W, sum B, d]``."""
        eng = getattr(self.po.van, "sparse_engine", None)
        log.check(eng is not None, "pull_sparse_group requires the ici van")
        log.check(outs is None or len(outs) == len(names),
                  "pull_sparse_group: one host buffer a table")
        return self._engine_op(eng.pull_group,
                               (names, indices_list) if pool is None
                               else (names, indices_list, pool),
                               None, 0, None, outs, callback, True, False,
                               None, len(names), pool)

    # -- telemetry -----------------------------------------------------------

    def _track_request(self, ts: int, pull: bool, parent: int = 0) -> int:
        """Start request-latency tracking for a message-path timestamp
        and mint a trace id — EVERY request under tail capture
        (PS_TRACE_TAIL; the keep decision moves to completion), else
        head-sampled (PS_TRACE_SAMPLE).  Returns the trace id (0 =
        untraced); ``parent`` links a multi_get sub-get to its
        fan-out's parent id."""
        (self._c_pulls if pull else self._c_pushes).inc()
        trace = self.po.tracer.begin_request()
        t0_us = self.po.tracer.now_us() if trace else 0.0
        with self._mu:
            self._req_track[ts] = (time.monotonic(), pull, trace, t0_us,
                                   parent)
        return trace

    def _finish_trace(self, ts: int, trace: int, pull: bool, dur: float,
                      t0_us: float, parent: int,
                      outcome: Optional[str],
                      observed: bool = True) -> None:
        """The tail-keep decision point (docs/observability.md): at
        completion the worker keeps this request's trace only if it is
        interesting — a failure outcome, slower than the rolling
        per-path quantile, or the uniform floor.  Kept traces get
        their ``request`` root span (what makes them assemble at the
        collector) and attach as an exemplar to the latency histogram
        bucket they landed in."""
        tracer = self.po.tracer
        path = "pull" if pull else "push"
        reason = tracer.tail_keep(dur, path, outcome)
        if reason is None:
            return
        args = {"ts": ts, "pull": pull, "keep": reason}
        if outcome:
            args["outcome"] = outcome
        if parent:
            args["parent"] = f"{parent:x}"
        tracer.span(trace, "request", t0_us, dur * 1e6, args=args)
        tracer.instant(trace, "complete", args={"ts": ts})
        if observed:
            # Exemplars link HISTOGRAM buckets to traces, so only a
            # duration the histogram actually observed may attach —
            # a timed-out request (observed=False: _finish never runs,
            # its latency never lands in the histogram) would park an
            # exemplar on a zero-count bucket that never renders,
            # evicting the live slow-trace links a timeout storm
            # needs most.  The timeout's trace itself is still kept.
            (self._h_pull_lat if pull else self._h_push_lat
             ).attach_exemplar(dur, trace)

    # -- small-op aggregation (kv/batching.py, docs/batching.md) -------------

    @property
    def combiner(self):
        """The worker's op combiner (None unless PS_BATCH_BYTES > 0)."""
        return self._combiner

    def _batch_capable(self, dest: int) -> bool:
        """Per-destination capability gate: old decoders must never
        see an EXT_BATCH frame (docs/batching.md).  Until the probe
        answers, ops pass through inline — never queued."""
        if not self._batch_negotiate:
            return True
        # Unlocked fast path: caps only ever transition None -> bool,
        # and dict reads are atomic under the GIL.
        cap = self._batch_caps.get(dest)
        if cap is not None:
            return cap
        self._probe_batch_cap(dest)
        return False

    def _probe_batch_cap(self, dest: int) -> None:
        """One-shot capability probe: a tiny BATCH_PROBE_CMD pull the
        server answers before its handler.  A peer that errors (an
        older build routing the unknown cmd into its handler) is
        recorded incapable; no answer leaves the destination unbatched
        without ever blocking an op.  The probing reservation is taken
        BEFORE the request is allocated, so a racing second caller
        neither double-probes nor leaks a tracker entry."""
        with self._mu:
            if dest in self._batch_caps or dest in self._batch_probing:
                return
            self._batch_probing.add(dest)
        ts = self._customer.new_request(dest)  # direct id: expect 1
        with self._mu:
            self._batch_probe_ts[ts] = dest
        msg = Message()
        m = msg.meta
        m.app_id = self._customer.app_id
        m.customer_id = self._customer.customer_id
        m.request = True
        m.pull = True
        m.head = _BATCH_PROBE_CMD
        m.timestamp = ts
        m.recver = dest
        # The probe declares THIS sender's batch wire version too
        # (val_len — older servers ignore it): the server must never
        # send a v2 per-op table (traced responses) to a v1 decoder.
        m.val_len = _BATCH_WIRE_VERSION
        msg.add_data(SArray(np.zeros(1, np.uint64)))
        msg.add_data(SArray(np.empty(0, np.float32)))
        try:
            self.po.van.send(msg)
        except Exception as exc:  # noqa: BLE001 - re-probed later
            log.warning(f"batch capability probe to {dest} failed: "
                        f"{exc!r}")
            with self._mu:
                self._batch_probe_ts.pop(ts, None)
                self._batch_probing.discard(dest)
            # Square the ledger so the dead probe entry reads complete
            # (prunable) instead of in-flight forever.
            self._customer.add_response(ts, 1)

    def _batch_sent(self, msgs, wire_msg: Message) -> None:
        """Combiner sent hook: record the frame that actually left on
        each member's pending slice — for a merged frame that is the
        ENVELOPE message, whose resender signature is what a failover
        must ``forget()`` (a None sent_msg would leave the resender
        retransmitting toward the abandoned destination and eventually
        failing a request that succeeded at its replica)."""
        for m in msgs:
            sl = getattr(m, "_batch_sl", None)
            if sl is not None:
                sl.sent_msg = wire_msg

    def _batch_send_failed(self, msgs, exc: Exception) -> None:
        """Combiner error hook: a flush's transport send raised off the
        caller thread — fail each member op exactly as an inline send
        failure would have (sweeper retry with deadlines on, fast
        TimeoutError without)."""
        for m in msgs:
            self._slice_send_failed(
                getattr(m, "_batch_ts", m.meta.timestamp),
                getattr(m, "_batch_sl", None), exc,
            )

    def _slice_send_failed(self, ts: int, sl, exc: Exception) -> None:
        """Shared failure path of one slice's send (inline sends and
        combiner flushes)."""
        if sl is not None:
            # Deadlines on: mark THIS slice failed — the sweeper
            # re-routes it (to a replica if the rank is down) right
            # away, without touching healthy siblings.
            log.warning(
                f"send ts={ts} failed ({exc!r}); handing to the "
                f"deadline sweeper"
            )
            with self._mu:
                sl.retry_now = True
            self._wake_sweeper()
        else:
            # No deadline machinery: fail the slice fast so wait(ts)
            # raises TimeoutError instead of hanging — and release the
            # doomed request's pull state (no response will ever
            # arrive to trigger _finish).
            log.warning(
                f"send ts={ts} failed ({exc!r}); failing the request "
                f"(PS_REQUEST_TIMEOUT off)"
            )
            with self._mu:
                self._mark_timed_out(ts)
                self._recv_kvs.pop(ts, None)
                self._pull_dst.pop(ts, None)
                self._callbacks.pop(ts, None)
                self._zpull_ts.discard(ts)
            self._customer.add_response(ts, 1)

    # -- public ops ----------------------------------------------------------

    def push(
        self,
        keys,
        vals,
        lens=None,
        cmd: int = 0,
        callback: Optional[Callable[[], None]] = None,
        priority: int = 0,
        compress: Optional[str] = None,
        codec: Optional[str] = None,
        tenant=None,
    ) -> int:
        """Zero-copy push; caller must not mutate buffers until wait(ts)
        (kv_app.h:210-231).

        ``tenant=`` (a ``PS_TENANTS`` name or id — docs/qos.md) labels
        the request for weighted-fair scheduling and per-tenant
        admission; defaults to this worker's ``PS_TENANT``.

        ``codec=`` selects a wire codec from the registry
        (``ops/codecs.py`` — ``'int8'``, ``'fp8_e4m3'``, ``'bf16'``;
        docs/compression.md): the payload travels compressed and is
        decoded server-side before the handler, with worker-side error
        feedback folding each push's quantization error into the next
        (``PS_CODEC_EF=0`` disables).  Defaults to the bucket codec
        registered via :meth:`register_bucket` for these exact keys;
        ``codec='raw'`` forces uncompressed.  ``compress=`` is the
        legacy alias of ``codec=``.  Ragged ``lens`` payloads are
        supported via per-key blockwise scaling.  Ignored on the
        collective (ICI) path, which needs no wire compression.
        """
        engine = self.engine
        if engine is not None:
            ts = self._engine_op(engine.push, (vals,), keys, cmd, lens,
                                 None, callback)
            if ts is not None:
                return ts
        kvs = _as_kvs(keys, vals, lens, priority)
        codec = self._resolve_codec(kvs.keys, codec, compress)
        if codec is not None:
            log.check(
                kvs.vals.dtype == np.float32,
                f"codec {codec!r} requires float32 values, got "
                f"{kvs.vals.dtype}",
            )
        ts = self._customer.new_request(SERVER_GROUP)
        trace = self._track_request(ts, pull=False)
        if callback is not None:
            with self._mu:
                self._callbacks[ts] = callback
        self._send(ts, push=True, pull=False, cmd=cmd, kvs=kvs,
                   codec=codec, trace=trace,
                   tenant=self._resolve_tenant(tenant))
        return ts

    def pull(
        self,
        keys,
        vals: np.ndarray,
        lens: Optional[np.ndarray] = None,
        cmd: int = 0,
        callback: Optional[Callable[[], None]] = None,
        priority: int = 0,
        compress: Optional[str] = None,
        codec: Optional[str] = None,
        tenant=None,
        _batch_sink: Optional[List[Message]] = None,
        _trace_parent: int = 0,
    ) -> int:
        """Zero-copy pull into ``vals`` (kv_app.h:241-247, 727-792).

        With the hot-key cache on (``PS_HOT_CACHE=1`` —
        kv/hot_cache.py), a plain fixed-k pull whose every key has a
        live cached value is answered LOCALLY: no message leaves the
        worker and the returned timestamp is already complete.
        ``tenant=`` labels the request for QoS (docs/qos.md).

        ``codec=`` asks each server to encode its response slice with a
        registry codec (``ops/codecs.py``; docs/compression.md) — the
        server folds its per-(key, worker) error-feedback residual in
        before encoding, and the response is decoded here.  Defaults to
        the bucket codec registered via :meth:`register_bucket`;
        ``codec='raw'`` forces uncompressed; ``compress=`` is the
        legacy alias.  float32 values only; ignored on the collective
        path and mutually exclusive with registered zero-copy pull
        buffers.
        """
        keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
        codec = self._resolve_codec(keys, codec, compress)
        if codec is not None:
            log.check(vals.dtype == np.float32,
                      f"codec {codec!r} requires float32 values")
        if self.engine is not None:
            ts = self._engine_op(self._engine_pull, (), keys, cmd, lens,
                                 vals, callback, False, True)
            if ts is not None:
                return ts
        if (self._hot_cache is not None and lens is None
                and codec is None and cmd == 0
                and isinstance(vals, np.ndarray)
                and self._hot_cache.serve(keys, vals)):
            # Local hit: every key was cached fresh (stamp + TTL) and
            # the values are already in the caller's buffer.  Hand back
            # a zero-expected timestamp so wait(ts) completes
            # immediately — the round trip is the saved cost.
            ts = self._customer.new_request(SERVER_GROUP,
                                            num_responses=0)
            self._c_pulls.inc()
            self._h_pull_lat.observe(0.0)
            if callback is not None:
                callback()
            return ts
        ts = self._customer.new_request(SERVER_GROUP)
        trace = self._track_request(ts, pull=True, parent=_trace_parent)
        zpull = (
            self._zpull_lookup(keys, vals)
            if lens is None and codec is None else None
        )
        with self._mu:
            if callback is not None:
                self._callbacks[ts] = callback
            self._pull_dst[ts] = (keys, vals, lens)
            if zpull is not None:
                self._zpull_ts.add(ts)
        kvs = KVPairs(keys=keys, vals=np.empty(0, vals.dtype), priority=priority)
        self._send(ts, push=False, pull=True, cmd=cmd, kvs=kvs,
                   val_dtype=vals.dtype, val_nbytes=vals.nbytes,
                   zpull=zpull, codec=codec, trace=trace,
                   tenant=self._resolve_tenant(tenant),
                   batch_sink=_batch_sink)
        return ts

    def push_pull(
        self,
        keys,
        vals,
        outs: np.ndarray,
        lens=None,
        cmd: int = 0,
        callback: Optional[Callable[[], None]] = None,
        priority: int = 0,
        compress: Optional[str] = None,
        codec: Optional[str] = None,
        tenant=None,
    ) -> int:
        """Fused push+pull round trip (the benchmark hot path).

        The PUSH leg honors the bucket/explicit codec
        (docs/compression.md) like :meth:`push`; the fused RESPONSE
        always travels raw — it must be eligible for in-place
        registered-buffer delivery, and the request's EXT_CODEC marker
        already describes the pushed payload, not a response wish.
        """
        engine = self.engine
        if engine is not None:
            ts = self._engine_op(engine.push_pull, (vals,), keys, cmd, lens,
                                 outs, callback, True)
            if ts is not None:
                return ts
        kvs = _as_kvs(keys, vals, lens, priority)
        codec = self._resolve_codec(kvs.keys, codec, compress)
        if codec is not None:
            log.check(
                kvs.vals.dtype == np.float32,
                f"codec {codec!r} requires float32 values, got "
                f"{kvs.vals.dtype}",
            )
        ts = self._customer.new_request(SERVER_GROUP)
        trace = self._track_request(ts, pull=True)
        # Registered pull buffers apply to the fused round trip too: the
        # response is transport-delivered into ``outs`` in place
        # (is_worker_zpull_ covers Pull_ from PushPull as well,
        # kv_app.h:727-792).
        zpull = self._zpull_lookup(kvs.keys, outs) if lens is None else None
        with self._mu:
            if callback is not None:
                self._callbacks[ts] = callback
            self._pull_dst[ts] = (kvs.keys, outs, lens)
            if zpull is not None:
                self._zpull_ts.add(ts)
        self._send(ts, push=True, pull=True, cmd=cmd, kvs=kvs, zpull=zpull,
                   codec=codec, trace=trace,
                   tenant=self._resolve_tenant(tenant))
        return ts

    def multi_get(
        self,
        key_lists,
        outs: Optional[List[np.ndarray]] = None,
        val_len: Optional[int] = None,
        dtype=np.float32,
        cmd: int = 0,
        priority: int = 0,
        compress: Optional[str] = None,
        codec: Optional[str] = None,
        tenant=None,
        callbacks: Optional[List[Callable[[], None]]] = None,
        callback: Optional[Callable[[], None]] = None,
    ) -> MultiGetHandle:
        """Serving fan-in (docs/batching.md): pull N independent key
        sets — a DLRM-style request's whole embedding fan-out — as ONE
        logical operation that completes in ~1 round trip per
        contacted server.

        Each ``key_lists[i]`` is a sorted unique key array (typically
        a single embedding row); its values land in ``outs[i]`` (or a
        freshly allocated ``len(keys) * val_len`` array of ``dtype``).
        Every sub-get is sliced across servers like :meth:`pull`, and
        with the op combiner on (``PS_BATCH_BYTES``) the WHOLE
        fan-out's per-server slices are handed to the combiner
        atomically (``submit_many``), so each contacted server
        receives ONE ``EXT_BATCH`` frame and — through the server's
        batched group apply — answers with ONE ``response_batch``
        frame: N lookups cost one frame build, one lane handoff, and
        one syscall each way instead of N.

        Hot-key cache (``PS_HOT_CACHE=1``): sub-gets whose every key
        is live-cached are answered locally (no message at all);
        PARTIAL hits serve the cached rows in place and fetch only the
        misses, with the same stamp/TTL validity as :meth:`pull` —
        read-your-writes survives, and fill-race fills born invalid
        are still skipped (kv/hot_cache.py).

        Completion: returns ONE :class:`MultiGetHandle`; per-sub-get
        ``callbacks[i]`` fire as each sub-get completes (suppressed on
        that sub-get's failure, like :meth:`pull`'s), and the
        aggregate ``callback`` fires once after the LAST sub-get
        completed successfully.  A per-sub failure (``OPT_OVERLOAD``
        shed, timeout, apply error) fails only that sub-get:
        ``handle.wait()`` finishes the siblings first, then re-raises.

        ``codec=`` applies to every list; ``codec=None`` resolves each
        list's own registered bucket codec (:meth:`register_bucket`).
        """
        n = len(key_lists)
        log.check(outs is not None or val_len is not None,
                  "multi_get needs outs= or val_len=")
        if outs is not None:
            log.check(len(outs) == n, "multi_get: len(outs) != len(key_lists)")
        if callbacks is not None:
            log.check(len(callbacks) == n,
                      "multi_get: len(callbacks) != len(key_lists)")
        handle = MultiGetHandle(self, n)
        sink: Optional[List[Message]] = (
            [] if self._combiner is not None else None
        )
        agg_mu = threading.Lock()
        agg_left = [n]

        def _complete(i: int) -> None:
            if callbacks is not None and callbacks[i] is not None:
                callbacks[i]()
            if callback is not None:
                with agg_mu:
                    agg_left[0] -= 1
                    fire = agg_left[0] == 0
                if fire:
                    callback()

        # Skip per-sub completion closures entirely when the caller
        # registered none — the storm path then pays no callback-dict
        # traffic per sub-op.
        want_cb = callbacks is not None or callback is not None
        hc = self._hot_cache
        # Fan-in trace linkage (docs/observability.md): one PARENT id
        # spans the whole multi_get; every sub-get mints its own trace
        # as usual and records the parent on its root span, so an
        # assembled serving request reads as one tree across servers.
        tracer = self.po.tracer
        parent = tracer.begin_request() if tracer.active else 0
        if parent:
            tracer.instant(parent, "multi_get", args={"subs": n})
        try:
            self._multi_get_issue(key_lists, outs, val_len, dtype, cmd,
                                  priority, compress, codec, tenant,
                                  handle, sink, want_cb, hc, _complete,
                                  parent)
        finally:
            if sink:
                # The whole fan-out enters the combiner in one atomic
                # batch: one EXT_BATCH frame per contacted destination
                # at the very next dispatcher pickup — no adaptive-
                # hold latency, no partial frames.  In a finally so an
                # exception partway through the issue loop can never
                # strand already-queued sub-gets' slices locally
                # (their waits would hang with deadlines off).
                self._combiner.submit_many(sink)
        return handle

    def _multi_get_issue(self, key_lists, outs, val_len, dtype, cmd,
                         priority, compress, codec, tenant, handle,
                         sink, want_cb, hc, _complete,
                         parent: int = 0) -> None:
        for i, keys in enumerate(key_lists):
            keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
            out = (outs[i] if outs is not None
                   else np.empty(len(keys) * val_len, dtype))
            handle.outs[i] = out
            codec_i = self._resolve_codec(keys, codec, compress)
            mask = None
            if (hc is not None and cmd == 0 and codec_i is None
                    and len(keys) and isinstance(out, np.ndarray)):
                mask = hc.serve_mask(keys, out)
            if mask is not None and mask.all():
                # Every key live-cached: no message leaves the worker.
                handle.cached += 1
                self._c_pulls.inc()
                self._h_pull_lat.observe(0.0)
                _complete(i)
                continue
            if mask is not None and mask.any():
                # Partial hit: fetch ONLY the misses into a staging
                # buffer and scatter them into the served rows'
                # siblings on completion (fixed-k row layout —
                # serve_mask proved divisibility).
                miss = np.flatnonzero(~mask)
                k = out.reshape(-1).size // len(keys)
                tmp = np.empty(len(miss) * k, out.dtype)

                def _scatter(i=i, out=out, tmp=tmp, miss=miss, k=k):
                    flat = out.reshape(-1)
                    for j, pos in enumerate(miss):
                        flat[pos * k:(pos + 1) * k] = tmp[j * k:(j + 1) * k]
                    _complete(i)

                handle.timestamps[i] = self.pull(
                    keys[miss], tmp, cmd=cmd, priority=priority,
                    tenant=tenant, callback=_scatter,
                    _batch_sink=sink, _trace_parent=parent,
                )
                continue
            handle.timestamps[i] = self.pull(
                keys, out, cmd=cmd, priority=priority, codec=codec_i,
                tenant=tenant,
                callback=(lambda i=i: _complete(i)) if want_cb else None,
                _batch_sink=sink, _trace_parent=parent,
            )

    def pull_multi(
        self,
        key_lists,
        outs: Optional[List[np.ndarray]] = None,
        **kw,
    ) -> MultiGetHandle:
        """Vectorized pull over registered buckets: each key list
        resolves its own bucket default codec (:meth:`register_bucket`)
        and the whole fan-out rides :meth:`multi_get`'s one-frame-per-
        server path.  The reference-style spelling for callers that
        think in buckets rather than serving requests."""
        return self.multi_get(key_lists, outs=outs, **kw)

    def wait(self, timestamp: int) -> None:
        self._customer.wait_request(timestamp)
        if not (self._timeout_ts or self._error_ts or self._overload_ts):
            # Unlocked emptiness probe (the overwhelmingly common
            # healthy path): no failure mark exists anywhere, so none
            # can name this timestamp.  Marks are only ever ADDED for
            # in-flight requests — ours completed above — so a miss
            # here cannot be a mark racing in later.
            return
        with self._mu:
            timed_out = timestamp in self._timeout_ts
            self._timeout_ts.discard(timestamp)
            failed = timestamp in self._error_ts
            self._error_ts.discard(timestamp)
            shed = timestamp in self._overload_ts
            self._overload_ts.discard(timestamp)
        if shed:
            raise OverloadError(
                f"request {timestamp} was shed by the server under "
                f"per-tenant admission control (OPT_OVERLOAD); back "
                f"off and retry"
            )
        if timed_out:
            raise TimeoutError(
                f"request {timestamp} was abandoned: no response within "
                f"PS_REQUEST_TIMEOUT across {self._req_retries} retries, "
                f"or its destination is dead with no live replica"
            )
        if failed:
            raise RuntimeError(
                f"request {timestamp} failed server-side (handler raised "
                f"while applying; see the server's log for the traceback)"
            )

    # aliases matching the reference spelling
    ZPush = push
    ZPull = pull
    ZPushPull = push_pull
    Wait = wait

    def stop(self) -> None:
        self.po.unregister_node_failure_hook(self._on_node_event)
        self.po.unregister_routing_hook(self._routing_hook)
        if self._combiner is not None:
            # Flush queued ops before the customer retires: a queued
            # sub-op's wait() still expects its response.
            self._combiner.stop()
        with self._sweep_cv:
            self._sweep_stop = True
            self._sweep_cv.notify_all()
        if self._sweep_thread is not None:
            self._sweep_thread.join(timeout=5)
            self._sweep_thread = None
        self._customer.stop()

    # -- failure handling / bounded requests ---------------------------------

    def _on_node_event(self, node_id: int, down: bool) -> None:
        """Postoffice node-failure hook: track dead servers for
        failover routing; a failure wakes the sweeper so in-flight
        requests against the dead rank retry immediately instead of
        waiting out their deadlines."""
        if not is_server_id(node_id):
            return
        with self._mu:
            if down:
                self._down_servers.add(node_id)
            else:
                self._down_servers.discard(node_id)
                # Re-arm the one-shot failover flight event: a fresh
                # outage of the recovered rank is a NEW transition.
                self._failover_logged.discard(node_id)
                # A recovered server restarts its push-stamp counter:
                # the old floor would brand every replica answer stale
                # forever (docs/serving_reads.md).
                self._seen_stamps.pop(node_id, None)
                self._read_share.pop(node_id, None)
        if down:
            self._wake_sweeper()

    def _on_routing(self, table) -> None:
        """Postoffice routing hook (docs/elasticity.md): a new epoch
        landed.  Invalidate hot-cache entries of every MIGRATED range —
        their fill stamps were minted by the old owner, which the new
        owner's independent version counter can never supersede — and
        wake the sweeper so wrong-owner slices re-route immediately."""
        if self._hot_cache is not None:
            for e in table.entries:
                if e.prev not in (-1, e.owner):
                    self._hot_cache.invalidate_range(e.begin, e.end)
        self._wake_sweeper()

    def _route_entries(self) -> List[Tuple[Range, int]]:
        """The worker's current ``(key range, owner rank)`` slicing
        plan: the routing table's entries under elastic membership
        (owners are NOT the entry index once ranges migrate), else the
        static uniform split where entry i is owned by rank i — cached,
        since a non-elastic cluster's split never changes and this runs
        on every op's issue path."""
        if not getattr(self.po, "elastic", False):
            ents = self._static_entries
            if ents is None:
                ents = self._static_entries = [
                    (rng, i)
                    for i, rng in enumerate(self.po.get_server_key_ranges())
                ]
            return ents
        rt = self.po.current_routing()
        if rt is not None:
            return [(Range(e.begin, e.end), e.owner) for e in rt.entries]
        return [(rng, i)
                for i, rng in enumerate(self.po.get_server_key_ranges())]

    def _maybe_pull_routing(self, seen_epoch: int) -> None:
        """A server bounced us with a routing epoch ahead of ours: pull
        the current table from the scheduler (throttled — one pull in
        flight per window, not one per bounced slice)."""
        rt = self.po.current_routing()
        if seen_epoch <= (rt.epoch if rt is not None else -1):
            return
        now = time.monotonic()
        with self._mu:
            if now - self._last_routing_pull < 0.2:
                return
            self._last_routing_pull = now
        from ..base import SCHEDULER_ID
        from ..message import Command, Control

        msg = Message()
        msg.meta.recver = SCHEDULER_ID
        msg.meta.request = True
        msg.meta.control = Control(cmd=Command.ROUTING)
        msg.meta.timestamp = self.po.van.next_timestamp()
        try:
            self.po.van.send(msg)
        except Exception as exc:  # noqa: BLE001 - next bounce retries
            log.warning(f"routing pull failed: {exc!r}")

    def _route(self, group_rank: int, trace: int = 0) -> int:
        """Destination id for a key-range slice: the owning rank, or —
        when it is down and replication is on — the first live member
        of its replica chain (the topology lives in ONE place:
        replication.chain_ranks, shared with the server's forwarder)."""
        gs = self.po.group_size
        base = server_rank_to_id(group_rank * gs + self.po.instance_idx)
        if base not in self._down_servers:
            return base
        from .replication import chain_ranks

        for rank in chain_ranks(group_rank, self._replication,
                                self.po.num_servers,
                                active=self.po.active_server_ranks):
            cand = server_rank_to_id(rank * gs + self.po.instance_idx)
            if cand not in self._down_servers:
                self._c_failovers.inc()
                if base not in self._failover_logged:
                    # Flight recorder (docs/observability.md): ONE
                    # event per outage transition naming the dead
                    # primary and the replica absorbing its range
                    # (re-armed when the rank recovers); the active
                    # trace id, when one is in scope, lets pstrace
                    # print the event inline with the trace.
                    self._failover_logged.add(base)
                    detail = {"trace": f"{trace:x}"} if trace else {}
                    self.po.flight.record("failover", severity="warn",
                                          dead=base, replica=cand,
                                          **detail)
                return cand
        return base

    def _route_read(self, group_rank: int,
                    trace: int = 0) -> Tuple[int, bool]:
        """Spread destination for a PURE pull slice
        (docs/serving_reads.md): any live member of the range's
        replica chain.  ``PS_REPLICA_READ_POLICY`` picks how —
        ``sticky`` (default) pins this worker's reads for the range
        to ONE member by worker-rank rotation, so the cluster-wide
        read load spreads across the chain while each worker keeps a
        single hot connection and its request aggregation intact;
        ``rr`` rotates per pull; ``load`` picks the member this
        worker has sent the fewest reads.  Returns ``(dest,
        is_replica)``; collapses to plain primary routing — keeping
        the failover flight event — when the chain has one live
        member or the primary itself is down."""
        gs = self.po.group_size
        base = server_rank_to_id(group_rank * gs + self.po.instance_idx)
        from .replication import chain_ranks

        # chain_ranks lists the REPLICAS (owner excluded) — the spread
        # set is the primary plus every live chain member.
        members = [] if base in self._down_servers else [base]
        for rank in chain_ranks(group_rank, self._replication,
                                self.po.num_servers,
                                active=self.po.active_server_ranks):
            cand = server_rank_to_id(rank * gs + self.po.instance_idx)
            if cand not in self._down_servers:
                members.append(cand)
        if len(members) <= 1 or base in self._down_servers:
            return self._route(group_rank, trace), False
        if self._read_policy == "load":
            dest = self._least_loaded_member(members)
        elif self._read_policy == "rr":
            dest = members[next(self._rr_counter) % len(members)]
        else:
            # sticky: worker-rank rotation over the chain, offset by
            # the range's rank so one worker's reads of DIFFERENT
            # ranges also land on different members.  Deterministic —
            # no per-pull state, re-evaluated when membership shifts.
            dest = members[(self.po.my_rank() + group_rank)
                           % len(members)]
        self._read_share[dest] = self._read_share.get(dest, 0) + 1
        if dest != base:
            self._c_replica_reads.inc()
        return dest, dest != base

    def attach_history(self, history) -> None:
        """Give the ``load`` read policy cluster truth: rank the
        spread set by ``history``'s windowed per-server pull rates
        (every worker's traffic, not just this one's).  Pass the
        scheduler's ClusterHistory when co-located with it, or any
        replica fed by the same METRICS_PULL snapshots; ``None``
        reverts to local send counts."""
        self._cluster_history = history

    def _least_loaded_member(self, members) -> int:
        """``load`` policy pick: the member with the lowest windowed
        ``kv.server_pull_requests`` rate in the attached ClusterHistory
        (local send counts break ties and cover members the history
        has not ranked yet); purely local counts when no history is
        attached — a worker without cluster truth balances what it can
        see, exactly the pre-history behavior."""
        hist = self._cluster_history
        if hist is not None:
            rated = {}
            for d in members:
                r = hist.rate(d, "kv.server_pull_requests")
                if r is not None:
                    rated[d] = r
            if rated:
                return min(members, key=lambda d: (
                    rated.get(d, 0.0), self._read_share.get(d, 0)))
        return min(members, key=lambda d: self._read_share.get(d, 0))

    # Wrong-owner re-routes allowed per request before it is abandoned
    # (each bounce is a live server answering; the worker's table pull
    # converges in a broadcast round trip — 50 is a deep safety net).
    _MAX_WRONG_OWNER_BOUNCES = 50

    def _mark_timed_out(self, ts: int) -> None:
        """Record a timed-out/abandoned request (caller holds _mu):
        wait(ts) raises TimeoutError; completion callbacks suppress.
        No _finish will ever run, so the tail-keep decision happens
        HERE — a timeout is exactly the kind of trace the tail plane
        exists to keep."""
        self._timeout_ts.add(ts)
        self._c_timeouts.inc()
        track = self._req_track.pop(ts, None)
        outcome = self._req_outcome.pop(ts, None) or "timeout"
        if track is not None:
            t0, was_pull, trace, t0_us, parent = track
            if trace:
                self._finish_trace(ts, trace, was_pull,
                                   time.monotonic() - t0, t0_us, parent,
                                   outcome if outcome != "retry"
                                   else "timeout", observed=False)

    def _ensure_sweeper(self) -> None:
        if self._sweep_thread is not None and self._sweep_thread.is_alive():
            return
        self._sweep_thread = threading.Thread(
            target=self._sweep_loop, name="kv-deadline-sweeper", daemon=True
        )
        self._sweep_thread.start()

    def _wake_sweeper(self) -> None:
        with self._sweep_cv:
            self._sweep_cv.notify_all()

    def _sweep_loop(self) -> None:
        period = max(0.02, min(self._req_timeout / 4.0, 0.5))
        while True:
            with self._sweep_cv:
                if self._sweep_stop:
                    return
                self._sweep_cv.wait(period)
                if self._sweep_stop:
                    return
            try:
                self._sweep_once()
            except Exception as exc:  # noqa: BLE001 - sweeper must survive
                log.warning(f"deadline sweeper error: {exc!r}")

    def _sweep_once(self) -> None:
        now = time.monotonic()
        retries: List[Tuple[_PendingReq, List[_PendingSlice]]] = []
        failures: List[Tuple[int, int]] = []
        with self._mu:
            for ts, req in list(self._pending.items()):
                unresp = [s for s in req.slices if not s.responded]
                if not unresp:
                    self._pending.pop(ts)
                    continue
                # A slice is retried when the request's deadline passed,
                # or ITS delivery is known failed (destination declared
                # dead / send raised / OPT_SEND_FAILED) — never its
                # healthy siblings, which would duplicate their sends.
                expired = now >= req.deadline
                troubled = [
                    s for s in unresp
                    if expired or s.retry_now
                    or s.dest in self._down_servers
                ]
                if not troubled:
                    continue
                # A pass whose every troubled slice is a wrong-owner
                # bounce charges the (generous) bounce budget, not the
                # retry budget: bounces answer immediately, so a few ms
                # of routing-table lag would otherwise exhaust
                # PS_REQUEST_RETRIES without one real failure.
                bounce_only = not expired and all(
                    s.wrong_owner for s in troubled
                )
                exhausted = (
                    req.bounces >= self._MAX_WRONG_OWNER_BOUNCES
                    if bounce_only else req.attempt >= self._req_retries
                )
                if exhausted:
                    self._pending.pop(ts)
                    self._mark_timed_out(ts)
                    # Release the abandoned request's pull state NOW:
                    # no further response may ever arrive to trigger
                    # _finish, and these entries hold real payload
                    # arrays (partial chunks, destination buffers).
                    self._recv_kvs.pop(ts, None)
                    self._pull_dst.pop(ts, None)
                    self._callbacks.pop(ts, None)
                    self._zpull_ts.discard(ts)
                    failures.append((ts, len(unresp)))
                    continue
                if bounce_only:
                    req.bounces += 1
                    req.deadline = max(req.deadline,
                                       now + self._req_timeout)
                else:
                    req.attempt += 1
                    # Exponential backoff: each attempt doubles the
                    # window.
                    req.deadline = now + self._req_timeout * (
                        2 ** req.attempt)
                    # Retried requests are tail-keep material even
                    # when the retry eventually succeeds — the saved
                    # trace shows WHY the first attempt was lost.
                    self._req_outcome.setdefault(ts, "retry")
                for s in troubled:
                    s.retry_now = False
                self._c_retries.inc(len(troubled))
                retries.append((req, troubled))
        for req, slices in retries:
            for sl in slices:
                subs = [sl]
                if sl.wrong_owner:
                    # Stale-epoch bounce (docs/elasticity.md): re-slice
                    # under the CURRENT routing table — a split that
                    # landed mid-range divides this slice across two
                    # new owners.
                    sl.wrong_owner = False
                    subs = self._resplit_slice(req, sl)
                for sub in subs:
                    # Retries always fall back to primary routing: a
                    # spread pull that timed out (or answered stale)
                    # does not get a second replica gamble.
                    sub.replica_read = False
                    dest = self._route(sub.group_rank, req.trace)
                    old = sub.sent_msg
                    if (old is not None and dest != sub.dest
                            and self.po.van.resender is not None):
                        # Stop retransmitting the original: its
                        # destination is being abandoned, and a give-up
                        # there would spuriously fail the now-failed-
                        # over request.
                        self.po.van.resender.forget(
                            old.meta.control.msg_sig)
                    log.vlog(1, f"retry ts={req.ts} slice rank="
                                f"{sub.group_rank} -> node {dest} "
                                f"(attempt {req.attempt})")
                    sub.dest = dest
                    msg = self._slice_msg(
                        req.ts, req.push, req.pull, req.cmd, sub.part,
                        sub.group_rank, dest, req.val_dtype,
                        req.val_nbytes, req.codec, req.zpull, req.trace,
                        enc=sub.enc, tenant=req.tenant,
                    )
                    try:
                        self.po.van.send(msg)
                        sub.sent_msg = msg
                    except Exception as exc:  # noqa: BLE001 - next sweep
                        log.warning(
                            f"retry send ts={req.ts} to {dest} failed: "
                            f"{exc!r}"
                        )
        for ts, deficit in failures:
            log.warning(
                f"request ts={ts} abandoned after {self._req_retries} "
                f"retries; failing wait()"
            )
            # Square the response ledger so wait(ts) unblocks (and then
            # raises TimeoutError via _timeout_ts).
            self._customer.add_response(ts, deficit)

    def _resplit_slice(self, req: _PendingReq,
                       sl: _PendingSlice) -> List[_PendingSlice]:
        """Re-slice a wrong-owner slice's keys under the current
        routing table (docs/elasticity.md).  Single-owner results
        reuse the slice (retargeted); multi-owner splits REPLACE it in
        the request's slice list and raise the expected-response bar by
        the extra sub-slices.  Codec payloads re-encode per sub-slice
        (fresh EF slots — the original fold stays with the abandoned
        destination's slot; a migration-window fold is one step of
        residual, not a correctness loss)."""
        entries = self._route_entries()
        ranges = [rng for rng, _owner in entries]
        parts = self._slicer(sl.part, ranges)
        live = [
            (entries[i][1], p) for i, p in enumerate(parts)
            if p is not None and not p.empty()
        ]
        if len(live) <= 1:
            if live:
                sl.group_rank = live[0][0]
            return [sl]
        subs = [
            _PendingSlice(group_rank=owner, part=p, dest=-1)
            for owner, p in live
        ]
        if req.codec is not None and req.push:
            for sub in subs:
                sub.enc = self._encode_part(req.codec, sub.group_rank,
                                            sub.part)
        with self._mu:
            try:
                idx = req.slices.index(sl)
            except ValueError:
                return [sl]  # already replaced/retired elsewhere
            req.slices[idx:idx + 1] = subs
        # Each sub-slice draws its own response; pre-charge the ledger
        # so completion still needs every one of them.
        self._customer.add_response(req.ts, -(len(subs) - 1))
        log.vlog(1, f"re-sliced ts={req.ts} across "
                    f"{[s.group_rank for s in subs]} (routing change)")
        return subs

    # -- internals -----------------------------------------------------------

    def _slice_msg(
        self,
        ts: int,
        push: bool,
        pull: bool,
        cmd: int,
        part: KVPairs,
        group_rank: int,
        dest: int,
        val_dtype=None,
        val_nbytes: int = 0,
        codec: Optional[str] = None,
        zpull: Optional[dict] = None,
        trace: int = 0,
        enc: Optional[_EncodedSlice] = None,
        tenant: int = 0,
    ) -> Message:
        """Build one per-server slice message (shared by the initial
        send and the deadline sweeper's failover retries).  ``enc`` is
        the slice's encode-once codec payload — a retry re-sends the
        exact original bytes."""
        msg = Message()
        m = msg.meta
        m.trace = trace
        m.priority = part.priority
        m.tenant = tenant
        m.app_id = self._customer.app_id
        m.customer_id = self._customer.customer_id
        m.request = True
        m.push = push
        m.pull = pull
        m.head = cmd
        m.timestamp = ts
        m.recver = dest
        m.key = int(part.keys[0]) if len(part.keys) else 0
        if pull and not push:
            m.val_len = val_nbytes
        else:
            m.val_len = part.vals.nbytes
        if zpull is not None:
            # Registered-buffer routing: the transport writes this
            # slice's response at (buf_id, offset) in the worker's
            # buffer (the rdma_van pull_addr_ / ucx w_pool_ analog).
            m.option = OPT_ZPULL
            m.addr = (
                (zpull["buf_id"] << _ZPULL_OFF_BITS)
                | zpull["offsets"][group_rank]
            )
        else:
            if codec is not None and pull and not push:
                # Ask the server to encode its response slice with this
                # codec (raw_len=0 marks the request direction).
                c = codecs_mod.get_codec(codec)
                m.codec = CodecInfo(codec=c.wire_id, raw_len=0,
                                    block=c.block)
            m.addr = id(part.vals)  # same-process fast-path token
        msg.add_data(SArray(part.keys))
        if enc is not None and push:
            # Codec payload (docs/compression.md): codes + scale table
            # (+ per-key lens); the codec identity rides the EXT_CODEC
            # meta extension so it survives re-chunking and replication
            # forwards.  m.val_len already holds the raw byte count.
            m.codec = enc.info
            msg.add_data(SArray(enc.codes))
            msg.add_data(SArray(enc.scales))
            if enc.lens is not None:
                msg.add_data(
                    SArray(np.asarray(enc.lens, dtype=np.int32))
                )
        else:
            msg.add_data(SArray(part.vals))
            if part.lens is not None:
                msg.add_data(
                    SArray(np.asarray(part.lens, dtype=np.int32))
                )
        return msg

    def _send(
        self,
        ts: int,
        push: bool,
        pull: bool,
        cmd: int,
        kvs: KVPairs,
        val_dtype=None,
        val_nbytes: int = 0,
        codec: Optional[str] = None,
        zpull: Optional[dict] = None,
        trace: int = 0,
        tenant: int = 0,
        batch_sink: Optional[List[Message]] = None,
    ) -> None:
        entries = self._route_entries()
        ranges = [rng for rng, _owner in entries]
        if len(ranges) == 1 and self._slicer is default_slicer:
            # Single-destination fast path (the 1-server serving shape,
            # and the hot path of the small-op storm): the lone range
            # spans the whole key space, so slicing is the identity —
            # skip the searchsorted partition work per op.
            sliced = [kvs]
        else:
            sliced = self._slicer(kvs, ranges)
        live = [
            (entries[i][1], part)
            for i, part in enumerate(sliced)
            if part is not None and not part.empty()
        ]
        # Square the response ledger against what is actually sent:
        # empty slices are pre-credited as before, and under elastic
        # routing the entry count may DIFFER from the active server
        # count the tracker recorded (a merged range's owner holds two
        # entries — the negative credit raises the expected bar).
        credit = self._customer.num_expected(ts) - len(live)
        if credit:
            self._customer.add_response(ts, credit)
        if not live:
            self._finish(ts)  # also releases any _pull_dst entry
            return
        if (self._replica_reads and pull and not push and cmd == 0
                and zpull is None and codec is None):
            # Replica read fan-out (docs/serving_reads.md): pure pulls
            # spread across each range's live chain members; _process
            # validates the response's applied stamp before accepting.
            # Zpull and codec responses stay primary-only (decline
            # matrix): their payloads are server-state-dependent in
            # ways a stamp cannot vouch for.
            routed = [self._route_read(owner, trace)
                      for owner, _part in live]
        else:
            routed = [(self._route(owner, trace), False)
                      for owner, _part in live]
        parts = [
            (owner, part, dest)
            for (owner, part), (dest, _r) in zip(live, routed)
        ]
        # Encode ONCE, before any send can fail: a sweeper retry (or
        # replica failover) re-sends the identical compressed bytes —
        # re-encoding would double-fold the error-feedback residual
        # and break the matrix bit-exactness contract.
        encs: List[Optional[_EncodedSlice]] = [
            self._encode_part(codec, gr, part)
            if codec is not None and push else None
            for gr, part, _dest in parts
        ]
        req: Optional[_PendingReq] = None
        if self._req_timeout > 0:
            # Built COMPLETE before publication: a sweeper tick racing
            # this send must never observe a half-populated slice list
            # (it retires requests whose every slice has responded).
            req = _PendingReq(
                ts=ts, push=push, pull=pull, cmd=cmd,
                deadline=time.monotonic() + self._req_timeout,
                trace=trace,
                slices=[
                    _PendingSlice(group_rank=gr, part=part, dest=dest,
                                  enc=enc, replica_read=rr)
                    for (gr, part, dest), enc, (_d, rr)
                    in zip(parts, encs, routed)
                ],
                val_dtype=val_dtype, val_nbytes=val_nbytes,
                codec=codec, zpull=zpull, tenant=tenant,
            )
            with self._mu:
                self._pending[ts] = req
            self._ensure_sweeper()
        for idx, (group_rank, part, dest) in enumerate(parts):
            sl = req.slices[idx] if req is not None else None
            msg = self._slice_msg(ts, push, pull, cmd, part, group_rank,
                                  dest, val_dtype, val_nbytes, codec,
                                  zpull, trace, enc=encs[idx],
                                  tenant=tenant)
            if (self._combiner is not None
                    and self._batch_capable(msg.meta.recver)):
                # Small-op aggregation (docs/batching.md): EVERY slice
                # toward a batch-capable destination rides the
                # combiner's per-(dest, tenant, priority) FIFO — small
                # compatible ops merge into EXT_BATCH frames, while
                # unmergeable ops (zpull, lens, traced, oversized,
                # codec-mismatched) flow through the same stream as
                # single frames IN POSITION, so batching can never
                # reorder a lane's ops.  Transport failures come back
                # via _batch_send_failed; sweeper retries/failovers
                # re-send per sub-op directly.
                msg._batch_ts = ts
                msg._batch_sl = sl
                if batch_sink is not None:
                    # multi_get fan-out (docs/batching.md): the caller
                    # collects every slice of the whole fan-out and
                    # hands them to the combiner ATOMICALLY
                    # (submit_many), so each contacted destination gets
                    # ONE EXT_BATCH frame instead of a trickle.
                    batch_sink.append(msg)
                else:
                    self._combiner.submit(msg)
                continue
            try:
                self.po.van.send(msg)
                if sl is not None:
                    sl.sent_msg = msg
            except Exception as exc:  # noqa: BLE001 - PeerDeadError & co
                self._slice_send_failed(ts, sl, exc)

    def _process(self, msg: Message) -> None:
        if msg.meta.request:
            return  # workers only receive responses
        if msg.meta.batch is not None:
            # Batched response envelope (docs/batching.md): one frame,
            # N sub-op results — account each sub-op, then count its
            # response (the Customer skips its per-envelope count for
            # batch frames).
            info = msg.meta.batch
            if not msg.data and all(
                    op.option == 0 and not op.pull for op in info.ops):
                # Fast path: an all-ack push-response frame (the
                # storm's dominant return traffic) — per-op accounting
                # without constructing per-op Message objects.
                sender = msg.meta.sender
                hc = self._hot_cache
                tracer = self.po.tracer
                tr_active = tracer.active
                for op in info.ops:
                    ts = op.timestamp
                    discount = False
                    if tr_active and op.trace:
                        # The batch ENVELOPE carries no trace id; the
                        # per-op response-arrival instant is what
                        # bounds the response_wire stage for merged
                        # traffic (telemetry/critical_path.py).
                        tracer.instant(op.trace, "recv",
                                       args={"from": sender,
                                             "request": False})
                    try:
                        with self._mu:
                            req = self._pending.get(ts)
                            if req is not None:
                                sl = next(
                                    (s for s in req.slices
                                     if len(s.part.keys)
                                     and int(s.part.keys[0]) == op.key),
                                    None)
                                if sl is not None:
                                    if sl.responded:
                                        discount = True  # dup: 1st wins
                                    else:
                                        sl.responded = True
                            if (self._replica_reads and op.stamp
                                    and op.stamp
                                    > self._seen_stamps.get(sender, 0)):
                                # Batched push acks raise the read-
                                # your-writes floor too (every op in
                                # this frame is a push — the fast
                                # path's precondition).
                                self._seen_stamps[sender] = op.stamp
                        if hc is not None and op.stamp:
                            hc.observe(sender, op.stamp)
                        if discount:
                            continue
                        if (self._customer.num_response(ts) + 1
                                >= self._customer.num_expected(ts)):
                            self._finish(ts)
                    except Exception as exc:  # noqa: BLE001
                        log.warning(f"batched sub-op ts={ts} response "
                                    f"handling failed: {exc!r}")
                    finally:
                        # One sub-op's failure must not strand its
                        # siblings' (or its own) wait() — the count is
                        # unconditional, exactly like the Customer's
                        # per-message finally on the unbatched path.
                        if not discount:
                            self._customer.add_response(ts)
                return
            tracer = self.po.tracer
            for sub in _split_batch_message(msg):
                if tracer.active and sub.meta.trace:
                    tracer.instant(sub.meta.trace, "recv",
                                   args={"from": msg.meta.sender,
                                         "request": False})
                try:
                    self._process(sub)
                except Exception as exc:  # noqa: BLE001
                    log.warning(
                        f"batched sub-op ts={sub.meta.timestamp} "
                        f"response handling failed: {exc!r}"
                    )
                finally:
                    self._customer.add_response(sub.meta.timestamp)
            return
        ts = msg.meta.timestamp
        probe_dest = None
        if self._batch_probe_ts:  # unlocked probe: empty ~always
            with self._mu:
                probe_dest = self._batch_probe_ts.pop(ts, None)
        if probe_dest is not None:
            # Capability probe answer (docs/batching.md): a clean
            # response carrying at least BATCH_WIRE_VERSION marks the
            # destination batch-capable; an error-marked one (an older
            # build's handler rejecting the unknown cmd) marks it
            # incapable — it only ever gets plain frames.
            ok = False
            if msg.meta.option == 0 and len(msg.data) >= 2:
                vals = msg.data[1].numpy().reshape(-1)
                ok = vals.size >= 1 and int(vals[0]) >= _BATCH_WIRE_VERSION
            with self._mu:
                self._batch_caps[probe_dest] = ok
                self._batch_probing.discard(probe_dest)
            return
        discount = False
        retry_now = False
        wrong_owner_epoch = None
        with self._mu:
            req = self._pending.get(ts)
            sl = None
            if req is not None:
                key = msg.meta.key  # responses echo the slice's first key
                sl = next(
                    (s for s in req.slices
                     if len(s.part.keys) and int(s.part.keys[0]) == key),
                    None,
                )
            if msg.meta.option == OPT_WRONG_OWNER:
                # The destination no longer owns the slice's key range
                # (docs/elasticity.md): nothing was applied there.  With
                # retry budget left, hand the slice to the sweeper —
                # which re-SLICES it under the current routing table —
                # and discount the bounce so the re-routed slices'
                # real responses complete the count.
                self._c_wrong_owner.inc()
                wrong_owner_epoch = msg.meta.val_len
                if ts in self._req_track:
                    self._req_outcome[ts] = "wrong_owner"
                if (req is not None
                        and req.bounces < self._MAX_WRONG_OWNER_BOUNCES):
                    discount = retry_now = True
                    if sl is not None:
                        sl.retry_now = True
                        sl.wrong_owner = True
                    else:
                        req.deadline = 0.0  # unmatched: expire them all
                elif req is None and self._req_timeout > 0:
                    # Stale bounce after the slice already completed
                    # elsewhere (or was abandoned): never fail a
                    # finished wait().
                    pass
                else:
                    self._mark_timed_out(ts)
                    if sl is not None:
                        sl.responded = True
            elif msg.meta.option == OPT_SEND_FAILED:
                # The van abandoned the slice's delivery.  With retry
                # budget left, hand it to the sweeper (and discount the
                # synthesized response so the retry's real response
                # completes the count); otherwise the request fails.
                if ts in self._req_track:
                    self._req_outcome[ts] = "send_failed"
                if req is not None and req.attempt < self._req_retries:
                    discount = retry_now = True
                    if sl is not None:
                        sl.retry_now = True
                    else:
                        req.deadline = 0.0  # unmatched: expire them all
                elif req is None and self._req_timeout > 0:
                    # Stale give-up: with deadlines on, a missing
                    # pending entry means the request already completed
                    # (failover) or was already abandoned — marking it
                    # now would make a SUCCESSFUL wait() raise.
                    pass
                else:
                    self._mark_timed_out(ts)
                    if sl is not None:
                        sl.responded = True
            elif sl is not None:
                if sl.responded:
                    # Duplicate (a slow original answered after its
                    # retry already did): the first response per slice
                    # is the one that counts.
                    discount = True
                else:
                    stale = False
                    if sl.replica_read:
                        pid = server_rank_to_id(
                            sl.group_rank * self.po.group_size
                            + self.po.instance_idx)
                        stale = (
                            msg.meta.sender != pid
                            and msg.meta.stamp
                            < self._seen_stamps.get(pid, 0)
                        )
                    if stale:
                        # Stale replica answer (docs/serving_reads.md):
                        # its applied stamp trails a push THIS worker
                        # already saw acknowledged.  Discard it and
                        # re-pull from the primary — read-your-writes
                        # beats the saved hop.
                        discount = retry_now = True
                        sl.retry_now = True
                        sl.replica_read = False  # sweeper -> primary
                        self._c_replica_fallbacks.inc()
                        if ts in self._req_track:
                            self._req_outcome[ts] = "replica_stale"
                        now = time.monotonic()
                        if now - self._fallback_logged > 1.0:
                            # Throttled: a lagging replica under a read
                            # storm would otherwise wrap the flight ring.
                            self._fallback_logged = now
                            self.po.flight.record(
                                "replica_stale_fallback",
                                severity="warn",
                                replica=msg.meta.sender, primary=pid,
                                stamp=msg.meta.stamp,
                                seen=self._seen_stamps.get(pid, 0),
                            )
                    else:
                        sl.responded = True
            if (self._replica_reads and msg.meta.push
                    and msg.meta.stamp):
                # An acknowledged push raises this worker's read-your-
                # writes floor for the acking server.
                if msg.meta.stamp > self._seen_stamps.get(
                        msg.meta.sender, 0):
                    self._seen_stamps[msg.meta.sender] = msg.meta.stamp
        if wrong_owner_epoch is not None:
            # The bouncing server runs a newer routing epoch than ours:
            # pull the current table from the scheduler (throttled) so
            # the re-route targets the right owner, not the same wall.
            self._maybe_pull_routing(wrong_owner_epoch)
        if discount:
            # Pre-compensate the +1 the Customer adds after this handle.
            self._customer.add_response(ts, -1)
            if retry_now:
                self._wake_sweeper()
            return
        if msg.meta.option == OPT_APPLY_ERROR:
            with self._mu:
                self._error_ts.add(ts)
                if ts in self._req_track:
                    self._req_outcome[ts] = "error"
        elif msg.meta.option == OPT_OVERLOAD:
            # The server shed this slice under admission control
            # (docs/qos.md): the request completes FAST — wait(ts)
            # raises the retryable OverloadError, never hangs.
            self._c_overloads.inc()
            with self._mu:
                self._overload_ts.add(ts)
                if ts in self._req_track:
                    self._req_outcome[ts] = "shed"
        cache_ident = msg.meta.sender
        if sl is not None and sl.replica_read:
            # Replica-served pull (docs/serving_reads.md): its stamp
            # lives in the PRIMARY's counter domain (the replica's
            # applied stamp of the primary's push stream), so cache
            # bookkeeping files it under the primary's identity — the
            # fill carries the replica's applied stamp, never the
            # primary's current counter.
            cache_ident = server_rank_to_id(
                sl.group_rank * self.po.group_size
                + self.po.instance_idx)
        if self._hot_cache is not None and msg.meta.stamp:
            # Push-driven invalidation (kv/hot_cache.py): every stamped
            # response advances the newest-known version of its server,
            # invalidating older cached fills.
            self._hot_cache.observe(cache_ident, msg.meta.stamp)
        if msg.meta.pull and len(msg.data) >= 2:
            ci = msg.meta.codec
            if ci is not None and ci.raw_len > 0 and len(msg.data) >= 3:
                # The server encoded its response slice (EXT_CODEC);
                # raw_len sizes the decode, data[3] carries per-key
                # lens for ragged payloads.
                codec = codecs_mod.by_wire_id(ci.codec)
                codecs_mod.check_block(ci)
                lens = (msg.data[3].astype_view(np.int32).numpy()
                        if len(msg.data) > 3 else None)
                kvs = KVPairs(
                    keys=msg.data[0].astype_view(np.uint64).numpy(),
                    vals=codec.decode(
                        msg.data[1].astype_view(np.uint8).numpy(),
                        msg.data[2].astype_view(np.float32).numpy(),
                        ci.raw_len // 4, lens=lens, flags=ci.flags,
                    ),
                    lens=lens,
                )
            else:
                kvs = KVPairs(
                    keys=msg.data[0].astype_view(np.uint64).numpy(),
                    vals=msg.data[1].numpy(),
                    lens=(msg.data[2].astype_view(np.int32).numpy()
                          if len(msg.data) > 2 else None),
                )
            with self._mu:
                self._recv_kvs.setdefault(ts, []).append(kvs)
                zp = ts in self._zpull_ts
            if (not zp and self._hot_cache is not None and msg.meta.stamp
                    and msg.meta.option == 0 and msg.meta.head == 0
                    and kvs.lens is None
                    and len(kvs.keys)
                    and len(kvs.vals) % len(kvs.keys) == 0):
                # Fill the hot cache from this server slice (copies —
                # response buffers recycle).  The fill stamp was read
                # at the server's request intake, so it never claims
                # freshness past what the snapshot actually observed;
                # fills older than a known push park invalid.
                self._hot_cache.fill(cache_ident, msg.meta.stamp,
                                     kvs.keys, kvs.vals)
        # The Customer increments the response count *after* this handle, so
        # "last response" is expected-1 (reference: kv_app.h:686-710).
        # Expected is the PER-REQUEST count the tracker recorded at
        # issue time: under elastic routing the fan-out varies with the
        # table (and with sweeper re-slices), so a global server count
        # would mis-detect completion.
        expected = self._customer.num_expected(ts)
        if self._customer.num_response(ts) + 1 >= expected:
            self._finish(ts)

    def _finish(self, ts: int) -> None:
        with self._mu:
            chunks = self._recv_kvs.pop(ts, [])
            dst = self._pull_dst.pop(ts, None)
            zpull = ts in self._zpull_ts
            self._zpull_ts.discard(ts)
            self._pending.pop(ts, None)  # retire deadline tracking
            track = self._req_track.pop(ts, None)
            if ts in self._raw_ts:
                # Raw-response request (fetch_hot_keys): the caller
                # wants the per-server KVPairs as-is, not a scatter
                # into a destination buffer.
                self._raw_ts.discard(ts)
                self._raw_results[ts] = chunks
                chunks = []
        if track is not None:
            t0, was_pull, trace, t0_us, parent = track
            dur = time.monotonic() - t0
            (self._h_pull_lat if was_pull else self._h_push_lat).observe(dur)
            with self._mu:
                outcome = self._req_outcome.pop(ts, None)
            if trace:
                self._finish_trace(ts, trace, was_pull, dur, t0_us,
                                   parent, outcome)
        if zpull and chunks and dst is not None and all(
            np.shares_memory(c.vals, dst[1]) for c in chunks
        ):
            # Delivered in place: every chunk aliases the registered
            # buffer, so reassembly would be a self-copy — skip it
            # (is_worker_zpull_; falls through to the copy below if any
            # transport hop didn't honor the registration).
            self.zpull_hits += 1
            self._run_callback(ts)
            return
        if dst is not None and chunks:
            keys, vals_out, lens_out = dst
            chunks.sort(key=lambda kv: int(kv.keys[0]) if len(kv.keys) else 0)
            total = sum(c.vals.nbytes for c in chunks)
            log.check(
                total <= vals_out.nbytes,
                f"pull response too large: {total} > {vals_out.nbytes}",
            )
            flat = vals_out.reshape(-1).view(np.uint8)
            off = 0
            for c in chunks:
                raw = c.vals.reshape(-1).view(np.uint8)
                flat[off : off + raw.nbytes] = raw
                off += raw.nbytes
            if lens_out is not None:
                loff = 0
                for c in chunks:
                    if c.lens is not None:
                        lens_out[loff : loff + len(c.lens)] = c.lens
                        loff += len(c.lens)
        self._run_callback(ts)

    def _run_callback(self, ts: int) -> None:
        with self._mu:
            cb = self._callbacks.pop(ts, None)
            # An error-, timeout-, or overload-marked response means
            # this request's data never (fully) landed: running the
            # completion callback would hand the caller a partially-
            # written buffer as if it were good.  The marks stay
            # recorded for wait(ts) to raise.
            errored = (ts in self._error_ts or ts in self._timeout_ts
                       or ts in self._overload_ts)
        if cb is not None and not errored:
            cb()


class _StagingStore:
    """Plain-dict shim handle ``snapshot.restore_into`` fills while the
    live store keeps serving (model-namespace publish)."""

    def __init__(self):
        self.store: dict = {}


class KVServer:
    """Holder of a key-range shard of the store (kv_app.h:304-420).

    Apply concurrency (``docs/apply_shards.md``): when the handler
    implements the shard-safe ``apply_shard`` protocol (the default and
    optimizer handles do), incoming requests are hash-split across
    ``PS_APPLY_SHARDS`` shard threads (default ``min(8, cpus)``) so N
    workers' pushes apply concurrently instead of serializing on the
    Customer's receive thread.  ``PS_APPLY_SHARDS=0`` restores the
    serial inline path; handlers without ``apply_shard`` always run
    serially.
    """

    def __init__(self, app_id: int, postoffice=None):
        self.po = postoffice or ps_mod.postoffice(Role.SERVER)
        self._handle: Optional[Callable[[KVMeta, KVPairs, "KVServer"], None]] = None
        self._apply_pool: Optional[ApplyShardPool] = None
        # Elastic membership (docs/elasticity.md): ownership + parking
        # state.  _owned is None until a routing table lands (static
        # behavior — every request is ours); after that, requests whose
        # keys fall outside it bounce with OPT_WRONG_OWNER, and
        # requests for a PENDING range (gained, migration data not yet
        # arrived) park until the handoff lands.  Initialized from the
        # node's CURRENT table BEFORE the customer starts draining
        # parked requests: a joiner that applied early-routed requests
        # tableless would have them silently overwritten by the
        # migration import.
        self._elastic_mu = threading.Lock()
        self._owned: Optional[List[Range]] = None
        self._table = None  # the applied RoutingTable (gate reads it)
        self._routing_epoch = -1
        # (owner rank, begin, end) triples this server replicated under
        # the PREVIOUS routing epoch: the diff against the new table's
        # chains names the ranges a chain recomputation newly assigned
        # here, which must BACKFILL existing state instead of holding
        # only post-change forwards (docs/serving_reads.md).  None until
        # the first table lands (the boot baseline never backfills —
        # except an elastic joiner, whose first table IS a chain
        # change against a populated cluster).
        self._replicated_prev: Optional[set] = None
        # range begin -> {"range", "frm", "epoch", "parked", "timer"}
        self._pending_ranges: Dict[int, dict] = {}
        # Migrations that arrived BEFORE their routing table (begin ->
        # epoch): the table application skips parking those ranges.
        self._arrived_migrations: Dict[int, int] = {}
        self._migrate_timeout = self.po.env.find_float(
            "PS_MIGRATE_TIMEOUT", 30.0)
        self._c_wrong_owner = self.po.metrics.counter("kv.wrong_owner")
        self._c_migrated_out = self.po.metrics.counter(
            "kv.migrated_keys_out")
        self._c_migrated_in = self.po.metrics.counter(
            "kv.migrated_keys_in")
        self._c_parked = self.po.metrics.counter("kv.parked_requests")
        # Migration acks that came back ERROR-marked (the new owner's
        # import raised): the old owner must NOT drop its copy.
        self._migrate_nacks = BoundedKeySet(256)
        # Outbound migrations are SERIALIZED through one worker thread
        # (queue + in-flight flag): a second epoch landing mid-handoff
        # must neither spawn a concurrent exporter nor let a leaver
        # report REMOVE_DONE while an earlier epoch's ranges are still
        # streaming out.
        self._migrate_q: List[tuple] = []
        self._migrating = False
        self._routing_hook = None
        if getattr(self.po, "elastic", False):
            table = self.po.current_routing()
            if table is not None:
                self._apply_routing_update(table)
            elif getattr(self.po, "elastic_join", False):
                # Live joiner whose first ROUTING broadcast is still in
                # flight: it owns NOTHING yet.  Bounce (never apply)
                # early-routed requests — applying them tableless would
                # let the migration import silently overwrite them.
                self._owned = []
        # Executor mode is clamped to <= 1 here: the apply pool's
        # invariants (arrival-order shard affinity, per-sender response
        # order, serial/sharded bit-exactness) all assume ONE thread
        # submits requests in arrival order — PS_CUSTOMER_EXECUTOR>1 on
        # a server would silently break them.
        self._customer = Customer(
            app_id, app_id, self._process, self.po,
            on_request_error=self._request_error,
            executor_workers=min(
                1, self.po.env.find_int("PS_CUSTOMER_EXECUTOR", 0)
            ),
        )
        self._handle: Optional[Callable[[KVMeta, KVPairs, "KVServer"], None]] = None
        self._recv_buffers: Dict[Tuple[int, int], np.ndarray] = {}
        # Count of pushes the TRANSPORT placed directly into a registered
        # buffer (vs the kv_app copy fallback) — observability for the
        # zero-copy delivery contract.
        self.delivered_in_place = 0
        self._apply_pool: Optional[ApplyShardPool] = None
        self._apply_shards = self._resolve_apply_shards()
        # Chain replication (PS_KV_REPLICATION=k, docs/fault_tolerance.md):
        # accepted pushes forward to the next k-1 servers in rank order;
        # a recovered server restores its range from its first replica
        # before serving.
        self._replicator = None
        self._restored = False
        # While a recovered server restores its range from the replica,
        # incoming requests PARK here (list) and replay in arrival
        # order afterwards — applying them to the still-empty store and
        # then overwriting with the restore snapshot would silently
        # lose them.  None = not restoring (steady-state fast path).
        self._restore_mu = threading.Lock()
        self._restore_buffer: Optional[List[Message]] = None
        # Streamed chunked pushes (docs/chunking.md): (sender, xfer) ->
        # open _StreamHandle — partial deliveries feed the apply pool
        # while the rest of the transfer is still on the wire; the
        # final reassembled message closes the handle (response emitted
        # when the last fed slice's shard work completes).  Bounded +
        # reclaimed on sender death, so killed-peer partial transfers
        # cannot grow the table.
        self._streams_mu = threading.Lock()
        self._streams: Dict[Tuple[int, int], object] = {}
        # TTL (matches the assembler's PS_XFER_TIMEOUT): a stream whose
        # transfer died at the assembler never gets its close — reclaim
        # it opportunistically instead of waiting for sender death.
        self._stream_ttl = self.po.env.find_float("PS_XFER_TIMEOUT", 120.0)
        self._stream_ticks = 0
        self.po.register_node_failure_hook(self._on_stream_peer_event)
        # Telemetry (docs/observability.md): request counters and the
        # bounded hot-key tracker psmon's "top keys" column renders.
        self._c_push_reqs = self.po.metrics.counter("kv.server_push_requests")
        self._c_pull_reqs = self.po.metrics.counter("kv.server_pull_requests")
        self._hot_keys = self.po.metrics.topk("kv.hot_keys")
        self._h_serial_apply = self.po.metrics.histogram("apply.latency_s")
        # Multi-tenant QoS (docs/qos.md): the tenant table, per-tenant
        # request/shed counters (psmon's tenant rollup rows), and the
        # admission bound — a tenant whose apply backlog exceeds
        # PS_TENANT_QUEUE_LIMIT gets an OPT_OVERLOAD fast-fail instead
        # of unbounded queueing.  Default: 1024 in-flight requests per
        # tenant when PS_TENANTS is configured, off otherwise.
        self.tenants = tenants_mod.table_for(self.po.env)
        self._admit_limit = self.po.env.find_int(
            "PS_TENANT_QUEUE_LIMIT",
            1024 if self.tenants.enabled else 0,
        )
        self._c_shed = self.po.metrics.counter("qos.shed_requests")
        self._tenant_counters: Dict[int, tuple] = {}
        # Per-tenant [last flight record monotonic, suppressed count]
        # for coalesced overload_shed events (see _intake_admission).
        self._shed_flight: Dict[int, list] = {}
        # Hot-key cache support (kv/hot_cache.py): the push-version
        # stamp.  Bumped AFTER a push fully applies (as its response
        # leaves); read at pull intake, so a pull response's stamp
        # never claims a version its snapshot might not have observed.
        # Starts at 1: stamp 0 means "unstamped" on the wire, and a
        # push-free serving store must still hand out cacheable pulls.
        # GATED: stamping engages only when some QoS feature is
        # configured (PS_TENANTS / PS_HOT_CACHE / explicit
        # PS_QOS_STAMPS=1 / replica reads, which use the stamp as their
        # consistency currency — docs/serving_reads.md) — default
        # deployments keep every frame byte-identical to pre-tenant
        # builds (no EXT_QOS tail).
        self._qos_mu = threading.Lock()
        self._push_version = 1
        self._replica_reads = bool(
            self.po.env.find_int("PS_REPLICA_READS", 0))
        self._qos_stamps = bool(
            self.tenants.enabled
            or self.po.env.find_int("PS_HOT_CACHE", 0)
            or self.po.env.find_int("PS_QOS_STAMPS", 0)
            or self._replica_reads
        )
        # Serving fan-in: the response-direction aggregation plane
        # (docs/batching.md, "Response aggregation").  Independent
        # small pull results / push acks headed back to one (sender,
        # tenant, priority) lane — whether their requests arrived
        # batched or as separate frames within the aggregation window
        # — coalesce into ONE EXT_BATCH response frame.  Only senders
        # that PROVED batch awareness (a capability probe or an
        # EXT_BATCH frame received from them) are ever aggregated
        # toward: un-upgraded workers keep seeing plain frames.
        # PS_RESP_BATCH_BYTES caps a response frame's payload and
        # defaults to PS_BATCH_BYTES, so one knob turns on both
        # directions; 0 disables the plane (every response frame is
        # byte-identical to a pre-fan-in build).
        self._batch_senders: set = set()
        # Senders PROVEN to decode the v2 per-op table (trace ids):
        # their probe declared version >= 2, or an EXT_BATCH frame
        # they sent carried a per-op trace.  Traced responses only
        # ever MERGE toward these — a v1 decoder mid-rolling-upgrade
        # would misparse the trace flag and walk the table at wrong
        # offsets (traced responses to everyone else go as singles).
        self._batch_senders_v2: set = set()
        self._resp_combiner = None
        resp_bytes = max(0, self.po.env.find_int(
            "PS_RESP_BATCH_BYTES",
            max(0, self.po.env.find_int("PS_BATCH_BYTES", 0)),
        ))
        if resp_bytes > 0:
            from .batching import OpCombiner

            self._resp_combiner = OpCombiner(
                lambda m: self.po.van.send(m),
                self._resp_send_failed,
                max_bytes=resp_bytes,
                window_us=self.po.env.find_float(
                    "PS_RESP_BATCH_WINDOW_US", 0.0),
                min_ops=self.po.env.find_int("PS_RESP_BATCH_MIN_OPS",
                                             32),
                hold_max_us=self.po.env.find_float(
                    "PS_RESP_BATCH_HOLD_US", 2000.0),
                response=True,
                tracer=self.po.tracer,
            )
        # Quantized transport tier (docs/compression.md): the server is
        # the ENCODER of codec pull responses — its per-(key, worker)
        # error-feedback residuals live on the handle (ef_bank, created
        # lazily in _encode_response) so they share the store's
        # lifetime; PS_CODEC_EF=0 disables.
        self._codec_ef_enabled = codecs_mod.ef_enabled(self.po.env)
        self._c_codec_raw = self.po.metrics.counter("codec.raw_bytes")
        self._c_codec_wire = self.po.metrics.counter("codec.wire_bytes")
        # Elastic routing updates flow through the customer queue (the
        # cutover must serialize against earlier queued requests), so
        # the hook registers only now that the customer exists; the
        # registration replays the current table, which the epoch guard
        # in _apply_routing_update discards as already applied.
        if getattr(self.po, "elastic", False):
            self._routing_hook = self._on_routing
            self.po.register_routing_hook(self._routing_hook)
        # Durable state tier (docs/durability.md): the coordinated-
        # snapshot fence (Command.SNAPSHOT -> the request-thread cut in
        # _run_snapshot), restore-on-boot (PS_SNAPSHOT_RESTORE=1), and
        # the beyond-RAM tiered store (PS_STORE_RAM_MB — installed in
        # set_request_handle).
        self._snapshot_dir = getattr(self.po, "snapshot_dir", None)
        self._snapshot_quiesce_s = self.po.env.find_float(
            "PS_SNAPSHOT_QUIESCE_S", 30.0)
        self._h_snapshot = self.po.metrics.histogram("snapshot.duration_s")
        self._snapshotting = False
        self._snap_restored = False
        # Model namespaces (docs/serving_reads.md): a published snapshot
        # manifest staged as an immutable store, flipped in atomically
        # on the request thread (the customer queue IS the parking), the
        # displaced store retained for instant rollback.
        self._ns_staged: Optional[tuple] = None   # (name, version, store)
        self._ns_prev: Optional[tuple] = None     # (name, version, store)
        self._ns_current: Tuple[str, str] = ("live", "")
        self._ns_staging = False
        self._snapshot_hook = self._on_snapshot_request
        reg_snap = getattr(self.po, "register_snapshot_hook", None)
        if reg_snap is not None:  # stub postoffices lack the registry
            reg_snap(self._snapshot_hook)
        if self._snapshot_dir:
            # Sampled at METRICS_PULL time: the SLO watchdog's
            # snapshot_age rule and psmon's snapshot-age line read it.
            self.po.metrics.gauge(
                "snapshot.age_s",
                fn=lambda d=self._snapshot_dir:
                    snapshot_mod.manifest_age_s(d),
            )
        rep = self.po.env.find_int("PS_KV_REPLICATION", 1)
        if rep >= 2 and self.po.num_servers >= 2:
            from .replication import Replicator

            self._replicator = Replicator(self, rep)
            # Rehabilitation resync: if THIS server is falsely declared
            # dead and later forgiven, it missed every write that
            # failed over to its replica in the window — re-restore
            # from the replica before resuming as the range's truth.
            self.po.register_node_failure_hook(self._on_self_rehab)

    def _resolve_apply_shards(self) -> int:
        try:
            # Affinity-aware, like TcpVan's native auto-select: a pinned
            # container must not spawn 8 shard threads for 1 core.
            n_cores = len(os.sched_getaffinity(0))
        except (AttributeError, OSError):
            n_cores = os.cpu_count() or 1
        return self.po.env.find_int("PS_APPLY_SHARDS", min(8, n_cores))

    def set_request_handle(
        self, handle: Callable[[KVMeta, KVPairs, "KVServer"], None]
    ) -> None:
        if self._apply_pool is not None:
            self._abort_streams()  # handles reference the old pool
            self._apply_pool.stop()
            self._apply_pool = None
        if self._handle is not None and handle is not self._handle:
            # Handle replacement: release the displaced tiered store's
            # segment files instead of leaking them until process exit.
            old_store = getattr(self._handle, "store", None)
            if callable(getattr(old_store, "close", None)):
                old_store.close()
        self._handle = handle
        # Hand the handle this node's Environment so its apply path
        # (native.try_iadd) honors a per-node PS_NATIVE=0 override in
        # in-process clusters, like every other native.load() caller.
        if hasattr(handle, "apply_shard"):
            handle._env = self.po.env
        pool_eligible = self._apply_shards > 0 and callable(
            getattr(handle, "apply_shard", None)
        )
        # Beyond-RAM tiered store (docs/durability.md): PS_STORE_RAM_MB
        # swaps the handle's plain dict for a TieredStore — hot keys in
        # RAM, cold keys in mmap'd append-only segment files — BEFORE
        # the apply pool spins up, so every apply/restore/import flows
        # through the tier from the first request.  Eviction classes
        # mirror the pool's shard affinity (key % shards), which is
        # what keeps eviction serialized with each key's applies and
        # the tiered store bit-exact vs all-RAM.
        ram_mb = self.po.env.find_float("PS_STORE_RAM_MB", 0.0)
        if ram_mb > 0 and isinstance(getattr(handle, "store", None),
                                     dict):
            from .tiered import TieredStore

            handle.store = TieredStore(
                ram_bytes=int(ram_mb * (1 << 20)),
                directory=self.po.env.find("PS_STORE_DIR") or None,
                shards=self._apply_shards if pool_eligible else 1,
                hot_fn=lambda k=64: [kk for kk, _ in
                                     self._hot_keys.top(k)],
                metrics=self.po.metrics,
                flight=self.po.flight,
                segment_mb=self.po.env.find_float(
                    "PS_STORE_SEGMENT_MB", 64.0),
            )
        if pool_eligible:
            self._apply_pool = ApplyShardPool(
                handle, self._apply_shards, self
            )
        want_snap = (
            self.po.env.find_int("PS_SNAPSHOT_RESTORE", 0) != 0
            and self._snapshot_dir and not self._snap_restored
            # An elastic joiner receives its ranges via live migration
            # — importing the (stale) manifest here would resurrect
            # keys deleted/migrated since the snapshot (same guard as
            # the replica-restore path below).
            and not getattr(self.po, "elastic_join", False)
        )
        want_repl = (self._replicator is not None and self.po.is_recovery
                     and not getattr(self.po, "elastic_join", False)
                     and not self._restored)
        if want_snap or want_repl:
            # Restore BEFORE serving (docs/durability.md,
            # docs/fault_tolerance.md): the disk snapshot first (the
            # full-cluster-kill path — replacing the silent empty-store
            # cold start), then the replica fetch, which overwrites
            # snapshot-restored ranges with anything newer a surviving
            # replica holds (the "delta since the manifest" interop —
            # set-semantics import, so the overwrite is idempotent when
            # the replicas themselves just restored the same cut).
            # Requests arriving during EITHER restore park in
            # _restore_buffer (workers may route back the moment the
            # roster lands) and replay in arrival order after the last
            # import — applying them between the two restores would let
            # the replica fetch silently overwrite them.
            with self._restore_mu:
                if self._restore_buffer is None:
                    self._restore_buffer = []
            # A tiered store enforces its budget DURING the restore
            # imports (requests are parked and the pool idle, so the
            # never-evict-on-insert shard argument doesn't apply) —
            # otherwise a beyond-RAM restore materializes the whole
            # table in RAM before the first get() can demote anything.
            tier_mode = getattr(getattr(handle, "store", None),
                                "set_evict_on_insert", None)
            try:
                if callable(tier_mode):
                    tier_mode(True)
                if want_snap:
                    self._snap_restored = True
                    self._restore_from_snapshot(handle)
                if want_repl:
                    self._restored = True
                    self._replicator.restore(handle)
            finally:
                if callable(tier_mode):
                    tier_mode(False)
                self._drain_restore_buffer()
        # Replica-backfill kick (docs/serving_reads.md): an elastic
        # joiner's first routing table replays at hook registration —
        # before this handle existed — so _note_replicated_ranges
        # deferred.  Re-run it now that the store can accept imports.
        with self._elastic_mu:
            table = self._table
        if table is not None:
            self._note_replicated_ranges(table, self.po.my_group_rank())

    def _restore_from_snapshot(self, handle) -> None:
        """Boot-time restore from the committed snapshot manifest
        (``PS_SNAPSHOT_RESTORE=1``): digest-verified per-range import of
        every manifest range this server owns.  A digest mismatch or
        missing segment raises (loud failure); a missing manifest is a
        logged cold start."""
        t0 = time.monotonic()
        self.po.flight.record("restore_begin", severity="info",
                              dir=self._snapshot_dir)
        manifest = snapshot_mod.load_manifest(self._snapshot_dir)
        if manifest is None:
            log.warning(
                f"PS_SNAPSHOT_RESTORE=1 but no committed manifest under "
                f"{self._snapshot_dir!r}; starting with an empty store"
            )
            self.po.flight.record("restore_end", severity="warn",
                                  keys=0, reason="no manifest")
            return
        owned = self.po.server_key_ranges_of(self.po.my_group_rank())
        try:
            n_keys, n_bytes = snapshot_mod.restore_into(
                handle, self._snapshot_dir, owned, manifest=manifest
            )
        except Exception:
            self.po.flight.record("restore_end", severity="crit",
                                  keys=0, reason="restore failed")
            raise
        dur = time.monotonic() - t0
        self.po.metrics.histogram("snapshot.restore_s").observe(dur)
        self.po.flight.record(
            "restore_end", severity="info", keys=n_keys, bytes=n_bytes,
            epoch=manifest.get("epoch"), duration_s=round(dur, 3),
        )
        log.vlog(1, f"snapshot restore: {n_keys} keys "
                    f"({n_bytes >> 20} MiB) from epoch "
                    f"{manifest.get('epoch')} in {dur:.2f}s")

    def _on_self_rehab(self, node_id: int, down: bool) -> None:
        if down or node_id != self.po.van.my_node.id:
            return
        if self._handle is None or self._replicator is None:
            return
        # Off-thread: this hook runs on the van's receive pump, and the
        # resync must WAIT for fetch responses that arrive through that
        # very pump — blocking here would deadlock the node.
        threading.Thread(
            target=self._resync_from_replica,
            name="kv-rehab-resync", daemon=True,
        ).start()

    def _resync_from_replica(self) -> None:
        with self._restore_mu:
            if self._restore_buffer is not None:
                return  # a restore/resync is already in flight
            self._restore_buffer = []
        log.warning("rehabilitated after a false death declaration; "
                    "resyncing ranges from replicas")
        try:
            self._replicator.restore(self._handle)
        except Exception as exc:  # noqa: BLE001 - keep serving regardless
            log.warning(f"rehab resync failed: {exc!r}")
        finally:
            self._drain_restore_buffer()

    def _drain_restore_buffer(self) -> None:
        """Replay requests parked during a restore, in arrival order;
        concurrent arrivals keep parking until the buffer drains dry."""
        while True:
            with self._restore_mu:
                batch = self._restore_buffer
                if not batch:
                    self._restore_buffer = None
                    return
                self._restore_buffer = []
            for msg in batch:
                # _process_request directly (NOT _process — a replayed
                # message must not re-park on the still-active buffer),
                # with the normal fail-the-remote-waiter error handling.
                try:
                    self._process_request(msg)
                except Exception as exc:  # noqa: BLE001
                    log.warning(
                        f"replayed request failed: {exc!r}"
                    )
                    try:
                        self._request_error(msg, exc)
                    except Exception:  # noqa: BLE001
                        pass

    def register_recv_buffer(
        self, sender_id: int, key: int, buffer: np.ndarray
    ) -> None:
        """Pre-pin the receive buffer for (worker, key) — pushes for that key
        land in exactly this buffer (kv_app.h:396-403, 457-496)."""
        self._recv_buffers[(sender_id, key)] = buffer
        hook = getattr(self.po.van, "register_recv_buffer", None)
        if hook is not None:
            hook(sender_id, key, buffer)

    def _response_msg(self, req: KVMeta) -> Message:
        """Response skeleton echoing the request's routing fields so
        one-sided transports can deliver in place (kv_app.h:536-564) —
        shared by response() and response_error()."""
        msg = Message()
        m = msg.meta
        m.app_id = self._customer.app_id
        m.customer_id = req.customer_id
        m.request = False
        m.push = req.push
        m.pull = req.pull
        m.head = req.cmd
        m.timestamp = req.timestamp
        m.recver = req.sender
        m.key = req.key
        m.addr = req.addr
        m.val_len = req.val_len
        m.option = req.option
        # Echo the request's priority: the response carries the bulk
        # bytes on a pull, so scheduling must apply where they travel.
        m.priority = req.priority
        # Echo the tenant (docs/qos.md): a bulk tenant's pull response
        # carries the bulk bytes — weighted-fair shares must hold on
        # the return path too.
        m.tenant = getattr(req, "tenant", 0)
        # Hot-cache stamp (kv/hot_cache.py): a pull's intake-time
        # version, or the one-shot bump a completed push just earned.
        m.stamp = getattr(req, "stamp", 0)
        # Echo the trace id so the response's wire/recv spans (and the
        # worker's completion) join the request's trace.
        m.trace = req.trace
        if req.trace and self.po.tracer.active:
            self.po.tracer.instant(req.trace, "respond",
                                   args={"to": req.sender,
                                         "ts": req.timestamp})
        return msg

    def _resp_send_failed(self, msgs, exc: Exception) -> None:
        """Response-combiner error hook: a flush's transport send
        raised off-thread.  Nothing to repair server-side — the
        waiting workers' deadline sweepers / timeouts own retry — but
        it must be LOUD, not swallowed."""
        log.warning(
            f"response flush of {len(msgs)} frame(s) failed: {exc!r}"
        )

    def _send_response(self, msg: Message) -> None:
        """Emit one response frame, riding the response combiner's
        per-(sender, tenant, priority) lane when the plane is on and
        the sender negotiated batch capability (docs/batching.md) —
        mergeable small results coalesce into one EXT_BATCH frame,
        unmergeable ones travel as singles IN POSITION so per-lane
        response order never relaxes.  Everything else (un-upgraded
        senders, custom cmds, control-adjacent answers) sends
        directly, byte-identical to a pre-fan-in build."""
        m = msg.meta
        if (self._resp_combiner is not None
                and m.head == 0
                and m.control.empty()
                and not m.shm_data
                and m.recver in self._batch_senders
                and (m.trace == 0
                     or m.recver in self._batch_senders_v2)):
            self._resp_combiner.submit(msg)
            return
        self.po.van.send(msg)

    def _qos_push_done(self, req) -> None:
        """One-shot push-version bump (kv/hot_cache.py): called as an
        applied push's response leaves (and on aborted streams, which
        may have partially applied).  The bump lands on ``req.stamp``
        so the response piggybacks it — a worker that saw this push
        complete can never again serve a cache fill that predates it.
        No-op unless stamping is configured (see ``_qos_stamps``)."""
        if not self._qos_stamps:
            return
        if getattr(req, "push", False) and getattr(req, "stamp", 1) == 0:
            with self._qos_mu:
                self._push_version += 1
                req.stamp = self._push_version

    def response(self, req: KVMeta, res: Optional[KVPairs] = None) -> None:
        """Reply to a request (kv_app.h:536-564)."""
        self._qos_push_done(req)
        if req.option == OPT_REPLICA:
            # Replica-forwarded pushes are fire-and-forget at the app
            # level (van-level ACKs cover delivery under PS_RESEND): a
            # response would collide with the origin worker's timestamp
            # numbering at the primary.  The forward's stamp is marked
            # APPLIED here — the completion edge the
            # replication.applied_stamp_lag gauge measures.
            if self._replicator is not None and getattr(req, "stamp", 0):
                self._replicator.note_applied(req.sender, req.stamp)
            return
        msg = self._response_msg(req)
        m = msg.meta
        if res is not None and not res.empty():
            ci = getattr(req, "codec", None)
            if (
                req.pull
                and ci is not None
                and ci.raw_len == 0  # request marker, not a push echo
                and isinstance(res.vals, np.ndarray)
                and res.vals.dtype == np.float32
                and res.vals.size > 0
            ):
                # Pull-side wire compression (docs/compression.md): the
                # worker asked for this codec via the request's
                # EXT_CODEC marker.  The per-(key, worker) error-
                # feedback residual folds in before encoding; ragged
                # lens payloads scale per key.  Declines (non-float32 /
                # empty) fall through uncompressed with meta.codec
                # unset, which the worker decodes as plain.
                enc = self._encode_response(ci, req, res)
                if enc is not None:
                    codes, scales, info = enc
                    m.codec = info
                    m.val_len = res.vals.nbytes
                    msg.add_data(SArray(res.keys))
                    msg.add_data(SArray(codes))
                    msg.add_data(SArray(scales))
                    if res.lens is not None:
                        msg.add_data(
                            SArray(np.asarray(res.lens, dtype=np.int32))
                        )
                    self._send_response(msg)
                    return
            msg.add_data(SArray(res.keys))
            msg.add_data(SArray(res.vals))
            if res.lens is not None:
                msg.add_data(SArray(np.asarray(res.lens, dtype=np.int32)))
        self._send_response(msg)

    def _encode_response(self, ci, req: KVMeta, res: KVPairs):
        """Encode a pull-response slice with the request's codec,
        folding in the handle's per-(worker, key-slice) EF residual
        (``KVServerDefaultHandle.ef_bank``, created lazily here so it
        shares the store's lifetime).  Returns (codes, scales,
        CodecInfo), or None to decline (unknown codec id — the
        response then travels uncompressed)."""
        try:
            codec = codecs_mod.by_wire_id(ci.codec)
        except Exception:  # noqa: BLE001 - unknown id: decline loudly
            log.warning(f"pull requested unknown codec id {ci.codec}; "
                        f"responding uncompressed")
            return None
        lens = (None if res.lens is None
                else np.asarray(res.lens, dtype=np.int64))
        resid = lock = None
        if self._codec_ef_enabled and self._handle is not None:
            bank = getattr(self._handle, "ef_bank", None)
            if bank is None:
                try:
                    bank = codecs_mod.ErrorFeedback(
                        codecs_mod.ef_slots(self.po.env),
                        metrics=self.po.metrics,
                    )
                    self._handle.ef_bank = bank
                except (AttributeError, TypeError):
                    bank = None  # handle refuses attributes: no EF
            if bank is not None:
                # Pin the exact key set (see KVWorker._encode_part):
                # (sender, first, crc(keys), size) — aliased slots
                # would cross-fold residuals between unrelated pulls.
                key = (req.sender,
                       int(res.keys[0]) if len(res.keys) else req.key,
                       zlib.crc32(np.ascontiguousarray(res.keys)),
                       int(res.vals.size))
                resid, lock = bank.slot(key, int(res.vals.size))
        if lock is not None:
            with lock:
                codes, scales, flags = codec.encode(res.vals, lens=lens,
                                                    resid=resid)
        else:
            codes, scales, flags = codec.encode(res.vals, lens=lens)
        self._c_codec_raw.inc(res.vals.nbytes)
        self._c_codec_wire.inc(codes.nbytes + scales.nbytes)
        return codes, scales, CodecInfo(
            codec=codec.wire_id, raw_len=res.vals.nbytes,
            block=codec.block, flags=flags,
        )

    def response_error(self, req: KVMeta) -> None:
        """Empty ``OPT_APPLY_ERROR``-marked response: the waiting worker
        still gets its response counted (so ``wait`` unblocks) and its
        ``wait`` raises instead of hanging until timeout."""
        # A failed push may have applied PARTIALLY (a shard raised
        # midway): bump the version anyway — conservative invalidation
        # is correct, a skipped one is not.
        self._qos_push_done(req)
        if req.option == OPT_REPLICA:
            # Even a FAILED forward apply advances the applied mark —
            # the lag gauge measures backlog, not success; the dedup
            # cache already recorded the origin either way.
            if self._replicator is not None and getattr(req, "stamp", 0):
                self._replicator.note_applied(req.sender, req.stamp)
            return  # no app-level responses on the replication plane
        msg = self._response_msg(req)
        # The error marker REPLACES any echoed option (OPT_ZPULL /
        # compression): an empty error response must not claim in-place
        # or quantized payload the transport would act on.
        msg.meta.option = OPT_APPLY_ERROR
        msg.meta.addr = 0
        msg.meta.val_len = 0
        # Error responses never MERGE (option != 0 declines) but still
        # ride the sender's response lane in position, so a failed
        # op's answer cannot overtake its siblings'.
        self._send_response(msg)

    def response_overload(self, req: KVMeta) -> None:
        """Empty ``OPT_OVERLOAD``-marked response (docs/qos.md): this
        request was SHED under per-tenant admission control — nothing
        was applied (so no version bump), and the worker's ``wait``
        raises the retryable ``OverloadError`` instead of hanging."""
        if req.option == OPT_REPLICA:
            return  # the replication plane must never shed (see intake)
        msg = self._response_msg(req)
        msg.meta.option = OPT_OVERLOAD
        msg.meta.addr = 0
        msg.meta.val_len = 0
        # Sheds are the control signal of an overloaded system: they
        # must not queue behind the very backlog they report — ride
        # the express band.
        msg.meta.priority = max(msg.meta.priority, 1)
        self.po.van.send(msg)

    # -- elastic membership (docs/elasticity.md) -----------------------------

    _MAX_PARKED = 4096  # per pending range; overflow sheds retryably

    def response_wrong_owner(self, req: KVMeta, epoch: int) -> None:
        """Empty ``OPT_WRONG_OWNER``-marked response: this server does
        not own the request's key range under its current routing
        epoch.  Nothing was applied; ``val_len`` carries the epoch so
        the stale worker can pull a fresher table, and its sweeper
        re-slices + re-routes — never a hang, never a silent apply at
        the wrong server."""
        if req.option == OPT_REPLICA:
            return
        msg = self._response_msg(req)
        msg.meta.option = OPT_WRONG_OWNER
        msg.meta.addr = 0
        msg.meta.val_len = max(int(epoch), 0)
        # Bounces are re-route control signals: express band, like sheds.
        msg.meta.priority = max(msg.meta.priority, 1)
        self.po.van.send(msg)

    def _on_routing(self, table) -> None:
        """Postoffice routing hook (van receive pump): post the new
        table through the request queue so the cutover runs on the
        request-processing thread — every request queued BEFORE it
        applies under the old epoch, everything after parks or
        bounces.  That ordering (plus the apply-pool quiesce token
        captured at cutover) is what makes the migration snapshot a
        consistent cut."""
        msg = Message()
        msg.meta.request = True
        msg.meta.app_id = self._customer.app_id
        msg.meta.customer_id = self._customer.customer_id
        msg.meta.head = ROUTING_LOCAL_CMD
        msg._routing_table = table
        self._customer.accept(msg)

    def _apply_routing_update(self, table) -> None:
        """Cutover to a new routing epoch (request thread only)."""
        if table is None:
            return
        my = self.po.my_group_rank()
        new_pending = []
        with self._elastic_mu:
            if table.epoch <= self._routing_epoch:
                return
            self._routing_epoch = table.epoch
            self._table = table
            self._owned = [Range(e.begin, e.end) for e in table.entries
                           if e.owner == my]
            losses = [e for e in table.entries
                      if e.prev == my and e.owner != my]
            for e in table.entries:
                if e.owner != my or e.prev in (-1, my):
                    continue
                if self._arrived_migrations.pop(e.begin, None) is not None:
                    continue  # the data beat the table here; already in
                if e.begin in self._pending_ranges:
                    continue
                ent = {"range": Range(e.begin, e.end), "frm": e.prev,
                       "epoch": table.epoch, "parked": [], "timer": None}
                self._pending_ranges[e.begin] = ent
                new_pending.append(ent)
        for ent in new_pending:
            t = threading.Timer(
                self._migrate_timeout, self._pending_timeout,
                args=(ent["range"].begin, ent["epoch"]),
            )
            t.daemon = True
            ent["timer"] = t
            t.start()
        self._note_replicated_ranges(table, my)
        if losses:
            if self._handle is None:
                log.warning("routing update assigns migrations but no "
                            "handle is set; ranges stay put")
                return
            # Quiesce token captured HERE (request thread): everything
            # submitted to the apply pool so far is what the snapshot
            # must wait for; requests after this point bounce at intake.
            token = (self._apply_pool.submit_token()
                     if self._apply_pool is not None else None)
            with self._elastic_mu:
                self._migrate_q.append((losses, table, token))
                spawn = not self._migrating
                if spawn:
                    self._migrating = True
            if spawn:
                threading.Thread(
                    target=self._migrate_out,
                    name="kv-migrate-out", daemon=True,
                ).start()
        else:
            with self._elastic_mu:
                migrating = self._migrating
            if my in table.leaving and not migrating:
                # Decommission with nothing (left) to move: report done
                # directly.  With a migration still in flight, the
                # worker thread reports when it drains — a leaver must
                # never be retired mid-handoff.
                self._send_remove_done()

    def _note_replicated_ranges(self, table, my: int) -> None:
        """Replica-backfill debt (docs/serving_reads.md): diff the set
        of ranges this rank REPLICATES (someone else owns, we sit in
        their chain) across routing epochs, and backfill the state of
        newly gained ones from their primaries.  Without this a chain
        recomputation (join/leave/recovery) leaves the new replica
        holding only post-change pushes — it would answer spread reads
        with a permanently stale store."""
        # getattr: the __init__-time cutover runs before the
        # replication engine is constructed.  Returning BEFORE the
        # prev-set update matters: an elastic joiner's first table
        # replays ahead of set_request_handle (handle still None), and
        # recording it here would swallow the backfill debt — the
        # set_request_handle kick re-runs this once both halves exist.
        replicator = getattr(self, "_replicator", None)
        if replicator is None or self._handle is None:
            return
        from .replication import chain_ranks
        active = list(getattr(table, "active", []))
        repl_now = set()
        for e in table.entries:
            if e.owner == my:
                continue
            chain = chain_ranks(e.owner, replicator.k,
                                self.po.num_servers, active=active)
            if my in chain:
                repl_now.add((e.owner, e.begin, e.end))
        prev = self._replicated_prev
        self._replicated_prev = repl_now
        if prev is None:
            # First table ever seen.  A boot-time baseline needs no
            # backfill (everyone starts empty together) — but a live
            # elastic JOINER enters chains that already hold state.
            if not getattr(self.po, "elastic_join", False):
                return
            gained = repl_now
        else:
            gained = repl_now - prev
        if not gained:
            return
        threading.Thread(
            target=self._backfill_replicas, args=(sorted(gained),),
            name="kv-replica-backfill", daemon=True,
        ).start()

    def _backfill_replicas(self, gained) -> None:
        """Background half of the replica backfill: park new arrivals
        (restore buffer), fetch each newly replicated range from its
        primary (quiesced cut; the response stamp floors forward
        re-applies), then replay everything parked."""
        with self._restore_mu:
            if self._restore_buffer is not None:
                return  # a restore/resync already covers this window
            self._restore_buffer = []
        total = 0
        try:
            for owner, begin, end in gained:
                oid = server_rank_to_id(owner * self.po.group_size
                                        + self.po.instance_idx)
                if self.po.van.is_peer_down(oid):
                    continue  # recovery restore covers dead primaries
                total += self._replicator.backfill_range(
                    self._handle, Range(begin, end), oid)
            self.po.flight.record(
                "replica_backfill", severity="info",
                ranges=len(gained), keys=total,
            )
        except Exception as exc:  # noqa: BLE001 - keep serving
            log.warning(f"replica backfill failed: {exc!r}")
        finally:
            self._drain_restore_buffer()

    def _elastic_gate(self, msg: Message) -> bool:
        """Ownership check at intake (request thread).  Returns True
        when the message was consumed: parked at a pending range
        (gained, migration data still in flight) or bounced with
        OPT_WRONG_OWNER.  Plain KV requests only — migration,
        replication, fetch, and introspection traffic passes."""
        m = msg.meta
        if (not m.request or m.simple_app or m.head != 0
                or m.option in (OPT_REPLICA, OPT_XFER_PART)):
            return False
        if not msg.data:
            return False
        keys = msg.data[0].astype_view(np.uint64).numpy()
        if len(keys) == 0:
            return False
        park_full = False
        with self._elastic_mu:
            epoch = self._routing_epoch
            for ent in self._pending_ranges.values():
                r = ent["range"]
                lo = int(np.searchsorted(keys, r.begin))
                hi = int(np.searchsorted(keys, r.end))
                if hi > lo:  # any key in the pending range: park whole
                    if len(ent["parked"]) >= self._MAX_PARKED:
                        park_full = True
                        break
                    ent["parked"].append(msg)
                    self._c_parked.inc()
                    return True
            if not park_full:
                # EVERY key must fall in an acceptable range — a very
                # stale worker's slice can span ranges that now
                # interleave with another owner's; first/last checks
                # would let the middle keys apply at the wrong server
                # silently.  Acceptable = owned by me, OR owned by a
                # DOWN rank whose replica chain includes me: the
                # failover machinery (docs/fault_tolerance.md)
                # deliberately re-routes a dead owner's slices here,
                # and the routing table knows nothing about crashes —
                # bouncing those would turn every failover into a
                # bounce loop.
                table = self._table
                my = self.po.my_group_rank()
                n_in = 0
                for e in (table.entries if table is not None else ()):
                    lo = int(np.searchsorted(keys, e.begin))
                    hi = int(np.searchsorted(keys, e.end))
                    if hi <= lo:
                        continue
                    if e.owner == my:
                        n_in += hi - lo
                    elif self._replicator is not None:
                        from .replication import chain_ranks

                        oid = server_rank_to_id(
                            e.owner * self.po.group_size
                            + self.po.instance_idx)
                        in_chain = my in chain_ranks(
                            e.owner, self._replicator.k,
                            self.po.num_servers,
                            active=self.po.active_server_ranks)
                        # A chain member admits the dead owner's ENTIRE
                        # traffic (failover), and — with replica reads
                        # on — PULLS for the live owner's ranges too
                        # (docs/serving_reads.md): the response is
                        # stamped in the primary's currency at intake,
                        # so the worker can judge its freshness.
                        if in_chain and (
                                self.po.van.is_peer_down(oid)
                                or (self._replica_reads
                                    and m.pull and not m.push)):
                            n_in += hi - lo
                if n_in == len(keys):
                    return False
        meta = KVMeta(
            cmd=m.head, push=m.push, pull=m.pull, sender=m.sender,
            timestamp=m.timestamp, customer_id=m.customer_id, key=m.key,
            option=m.option, priority=m.priority, trace=m.trace,
            tenant=m.tenant,
        )
        if park_full:
            # Park buffer overflow: shed retryably (OPT_OVERLOAD)
            # rather than queue unbounded memory behind a slow handoff.
            # Same coalescing as the admission path — a slow migration
            # rejects at request rate.
            self._c_shed.inc()
            self._record_shed_flight(m.tenant, m.sender, m.timestamp,
                                     trace=m.trace,
                                     why="migration park buffer full")
            self.response_overload(meta)
            return True
        self._c_wrong_owner.inc()
        self.response_wrong_owner(meta, epoch)
        return True

    def _import_migration(self, msg: Message) -> None:
        """A range handoff landed (MIGRATE_CMD from the old owner):
        import the snapshot, release the pending range, replay parked
        requests in arrival order (request thread — no new arrivals
        interleave), and ack the sender."""
        from .replication import import_range as _import_range

        m = msg.meta
        if self._handle is None:
            # Construction race: the app registered its customer but
            # has not installed the handle yet.  Requeue — an error-
            # marked response here would read as an ACK at the old
            # owner, which would then DROP the only copy.
            time.sleep(0.002)
            self._customer.accept(msg)
            return
        keys = (msg.data[0].astype_view(np.uint64).numpy()
                if len(msg.data) >= 1 else np.empty(0, np.uint64))
        vals = (msg.data[1].numpy() if len(msg.data) >= 2
                else np.empty(0, np.float32))
        lens = (msg.data[2].astype_view(np.int32).numpy()
                if len(msg.data) > 2 else None)
        if len(keys):
            _import_range(self._handle, keys, vals, lens)
            self._c_migrated_in.inc(len(keys))
        with self._elastic_mu:
            ent = self._pending_ranges.pop(m.key, None)
            if ent is None:
                # Data raced ahead of the routing broadcast: remember
                # the arrival so the table application skips parking.
                self._arrived_migrations[m.key] = int(m.addr)
                while len(self._arrived_migrations) > 64:
                    self._arrived_migrations.pop(
                        next(iter(self._arrived_migrations)))
        if ent is not None and ent.get("timer") is not None:
            ent["timer"].cancel()
        log.vlog(1, f"imported {len(keys)} migrated keys at "
                    f"{m.key} (epoch {m.addr})")
        meta = KVMeta(
            cmd=m.head, push=True, pull=False, sender=m.sender,
            timestamp=m.timestamp, customer_id=m.customer_id,
            key=m.key, addr=m.addr,
        )
        # NOT chain-forwarded: a migration import is SET semantics and
        # cannot safely ride the replicas' ordered += apply path.  The
        # old owner's chain still holds the range's pre-handoff state
        # (only the old PRIMARY drops its copy), and the new owner's
        # chain backfills through subsequent pushes — full backfill on
        # chain recomputation is a ROADMAP follow-up.
        self.response(meta)
        self._notify_migrate_done(int(m.addr), int(m.key))
        if ent is not None:
            for parked in ent["parked"]:
                try:
                    self._process_request(parked)
                except Exception as exc:  # noqa: BLE001
                    log.warning(f"parked request replay failed: {exc!r}")
                    try:
                        self._request_error(parked, exc)
                    except Exception:  # noqa: BLE001
                        pass

    def _migrate_out(self) -> None:
        """Migration worker thread: drain queued migration batches in
        epoch order — for each, wait for every apply submitted before
        its cutover to finish (quiesce token), then stream each lost
        range to its new owner.  A leaver reports REMOVE_DONE only
        when the queue is DRY (never mid-handoff), judged against the
        CURRENT table."""
        while True:
            with self._elastic_mu:
                if not self._migrate_q:
                    self._migrating = False
                    table = self._table
                    break
                losses, table, token = self._migrate_q.pop(0)
            if self._apply_pool is not None and token is not None:
                if not self._apply_pool.quiesce(
                        token, timeout_s=self._migrate_timeout):
                    log.warning("migrate: apply pool did not quiesce "
                                "in time; snapshotting anyway")
            for e in losses:
                try:
                    self._migrate_range(e, table)
                except Exception as exc:  # noqa: BLE001
                    log.warning(f"migration of [{e.begin}, {e.end}) -> "
                                f"rank {e.owner} failed: {exc!r}")
        if (table is not None
                and self.po.my_group_rank() in table.leaving):
            self._send_remove_done()

    def _migrate_range(self, e, table) -> None:
        """Snapshot one lost range and push it to the new owner
        (MIGRATE_CMD; large snapshots ride the chunked streaming
        plane automatically).  The local copy is dropped only after
        the new owner acks the import."""
        from .replication import export_range as _export_range

        keys, vals, lens = _export_range(self._handle, e.begin, e.end)
        dest = server_rank_to_id(
            e.owner * self.po.group_size + self.po.instance_idx)
        ts = self._customer.new_request(dest)
        msg = Message()
        m = msg.meta
        m.app_id = self._customer.app_id
        m.customer_id = self._customer.customer_id
        m.request = True
        m.push = True
        m.head = MIGRATE_CMD
        m.timestamp = ts
        m.recver = dest
        m.key = int(e.begin)
        m.addr = int(table.epoch)
        m.val_len = vals.nbytes
        msg.add_data(SArray(keys))
        msg.add_data(SArray(vals))
        msg.add_data(SArray(np.asarray(lens, dtype=np.int32)))
        self.po.van.send(msg)
        ok = self._customer.wait_request(
            ts, timeout=self._migrate_timeout)
        if not ok or ts in self._migrate_nacks:
            self._migrate_nacks.discard(ts)
            log.warning(f"migration of [{e.begin}, {e.end}) to rank "
                        f"{e.owner} "
                        f"{'failed at the importer' if ok else 'unacked'}"
                        f"; keeping the local copy")
            return
        self._drop_keys(keys)
        self._c_migrated_out.inc(len(keys))
        log.vlog(1, f"migrated {len(keys)} keys of [{e.begin}, {e.end}) "
                    f"-> rank {e.owner}")

    def _drop_keys(self, keys) -> None:
        handle = self._handle
        if callable(getattr(handle, "drop_keys", None)):
            handle.drop_keys(keys)
            return
        store = getattr(handle, "store", None)
        if store is None:
            return
        drop = _store_drop_fn(store)
        for k in keys.tolist():
            drop(int(k))

    def _pending_timeout(self, begin: int, epoch: int) -> None:
        """A gained range's migration data never arrived (source died
        mid-handoff?): try the old owner's replica chain, then unpark —
        parked waiters must complete or fail, never hang."""
        with self._elastic_mu:
            ent = self._pending_ranges.get(begin)
            if ent is None or ent["epoch"] != epoch:
                return
            rng, frm = ent["range"], ent["frm"]
        log.warning(f"migration of [{rng.begin}, {rng.end}) from rank "
                    f"{frm} overdue; trying replica fallback")
        if self._replicator is not None and self._handle is not None:
            from .replication import chain_ranks

            gs = self.po.group_size
            to_id = lambda r: server_rank_to_id(  # noqa: E731
                r * gs + self.po.instance_idx)
            cands = [to_id(frm)] + [
                to_id(r) for r in chain_ranks(
                    frm, self._replicator.k, self.po.num_servers,
                    active=self.po.active_server_ranks)
            ]
            try:
                self._replicator._fetch_range(self._handle, rng, cands,
                                              timeout_s=10.0)
            except Exception as exc:  # noqa: BLE001
                log.warning(f"replica fallback for [{rng.begin}, "
                            f"{rng.end}) failed: {exc!r}")
        with self._elastic_mu:
            ent = self._pending_ranges.pop(begin, None)
        if ent is None:
            return  # the real handoff landed while we were fetching
        # The range is live (degraded) from here on — release the
        # scheduler's migration ledger so snapshots stop deferring.
        self._notify_migrate_done(epoch, begin)
        for parked in ent["parked"]:
            # Re-inject through the intake queue: this is a timer
            # thread, and request processing is single-threaded.
            # Cross-timeout arrival order is best-effort — this is the
            # degraded path of a handoff whose source died.
            self._customer.accept(parked)

    def _notify_migrate_done(self, epoch: int, begin: int) -> None:
        """Tell the scheduler a range handoff landed here
        (MIGRATE_DONE_OPT on a ROUTING request): its migration ledger
        gates snapshot cuts, which must never slice a range
        mid-handoff."""
        import json as _json

        from ..base import SCHEDULER_ID
        from ..message import Command, Control

        msg = Message()
        msg.meta.recver = SCHEDULER_ID
        msg.meta.request = True
        msg.meta.option = self.po.van.MIGRATE_DONE_OPT
        msg.meta.body = _json.dumps({
            "epoch": int(epoch), "begin": int(begin),
            "rank": self.po.my_group_rank(),
        }).encode()
        msg.meta.control = Control(cmd=Command.ROUTING)
        msg.meta.timestamp = self.po.van.next_timestamp()
        try:
            self.po.van.send(msg)
        except Exception as exc:  # noqa: BLE001 - the ledger expires
            log.warning(f"MIGRATE_DONE note failed: {exc!r}")

    def _send_remove_done(self) -> None:
        """Tell the scheduler this leaver finished migrating
        (REMOVE_DONE_OPT on REMOVE_NODE): it may now retire the rank."""
        import json as _json

        from ..base import SCHEDULER_ID
        from ..message import Command, Control

        msg = Message()
        msg.meta.recver = SCHEDULER_ID
        msg.meta.request = True
        msg.meta.option = self.po.van.REMOVE_DONE_OPT
        msg.meta.body = _json.dumps(
            {"rank": self.po.my_group_rank()}).encode()
        msg.meta.control = Control(cmd=Command.REMOVE_NODE)
        msg.meta.timestamp = self.po.van.next_timestamp()
        try:
            self.po.van.send(msg)
        except Exception as exc:  # noqa: BLE001
            log.warning(f"REMOVE_DONE send failed: {exc!r}")

    def decommission(self, timeout_s: float = 60.0) -> None:
        """Gracefully leave the running cluster (docs/elasticity.md):
        the scheduler reassigns this server's ranges, this server
        migrates them live, and the rank is retired — no restart, no
        dropped requests.  Afterwards, ``stop()`` this server and
        ``finalize(do_barrier=False)`` its postoffice (a retired node
        is no longer counted in barriers)."""
        self.po.request_decommission(timeout_s)

    # -- coordinated snapshots (docs/durability.md) ---------------------------

    def _on_snapshot_request(self, msg: Message) -> bool:
        """Postoffice snapshot hook (van receive pump): post the
        scheduler's SNAPSHOT request through the request queue so the
        fence runs on the request-processing thread — every request
        queued BEFORE it lands in the cut, everything after applies
        only once the in-memory export completed.  The same ordering
        trick as the elastic routing cutover (ROUTING_LOCAL_CMD)."""
        marker = Message()
        marker.meta.request = True
        marker.meta.app_id = self._customer.app_id
        marker.meta.customer_id = self._customer.customer_id
        marker.meta.head = SNAPSHOT_LOCAL_CMD
        marker._snapshot_ctl = (msg.meta.sender, msg.meta.timestamp,
                                msg.meta.body)
        self._customer.accept(marker)
        return True

    def _run_snapshot(self, msg: Message) -> None:
        """The consistent cut (request thread): quiesce every apply
        submitted so far, export the owned ranges IN MEMORY (export
        copies — the park stays as short as the export), then hand the
        disk writes + reply to a background thread so serving resumes
        while segments stream out."""
        import json

        sender, token, body = msg._snapshot_ctl
        try:
            req = json.loads(body.decode()) if body else {}
        except Exception:  # noqa: BLE001 - a corrupt body vetoes below
            req = {}
        op = req.get("op")
        if op in ("publish", "flip", "rollback"):
            # Model-namespace control ops (docs/serving_reads.md) ride
            # the snapshot fence: same wire command, same request-
            # thread ordering guarantee.
            self._run_namespace(sender, token, op, req)
            return
        if op == "retune":
            self._run_retune(sender, token, req)
            return
        with self._elastic_mu:
            migrating = (bool(self._pending_ranges) or self._migrating
                         or bool(self._migrate_q))
        directory = req.get("dir") or self._snapshot_dir
        err = None
        if self._handle is None:
            err = "no request handle set"
        elif not directory:
            err = "no snapshot directory (PS_SNAPSHOT_DIR unset)"
        elif self._snapshotting:
            err = "a snapshot is already in progress"
        elif migrating:
            # Defense in depth behind the scheduler's own defer/veto
            # (Postoffice.snapshot): a cut taken mid-handoff would
            # commit a range whose state is split across the old and
            # new owner — refuse, the scheduler retries once settled.
            err = "range migration in flight — refusing a " \
                  "mid-handoff cut"
        elif self.po.group_size > 1:
            # Instance groups: every instance of a group rank owns the
            # same key range with its own per-instance store, so their
            # segment files would clobber each other.  Decline loudly
            # (docs/durability.md) — like elastic membership, the
            # durable tier is a DMLC_GROUP_SIZE=1 feature.
            err = "snapshots do not support instance groups " \
                  "(DMLC_GROUP_SIZE > 1)"
        if err is not None:
            self._snapshot_reply(sender, token, {"error": err})
            return
        self._snapshotting = True
        t0 = time.monotonic()
        self.po.flight.record("snapshot_begin", severity="info",
                              dir=directory)
        if self._apply_pool is not None:
            # The fence: everything already submitted must complete;
            # nothing new can be submitted while this thread waits
            # (later requests queue behind the marker).  A quiesce
            # TIMEOUT vetoes the cut — exporting while shard threads
            # still mutate arrays in place would commit torn values
            # under a digest that happily verifies them.
            tok = self._apply_pool.submit_token()
            if not self._apply_pool.quiesce(
                    tok, timeout_s=self._snapshot_quiesce_s):
                self._snapshotting = False
                err = (f"apply pool did not quiesce within "
                       f"{self._snapshot_quiesce_s}s — refusing a "
                       f"torn cut")
                log.warning(f"snapshot: {err}")
                self.po.flight.record("snapshot_end", severity="warn",
                                      ok=False, error=err)
                self._snapshot_reply(sender, token, {"error": err})
                return
        with self._streams_mu:
            open_streams = len(self._streams)
        if open_streams:
            # Decline-matrix edge (docs/durability.md): a chunked push
            # mid-STREAMING-apply straddles the fence — its fed prefix
            # is in the cut, its tail is not.  The op is still unacked
            # (its close has not been processed), so no acknowledged
            # write is ever torn; surface it for the postmortem trail.
            self.po.flight.record("snapshot_open_streams",
                                  severity="warn", streams=open_streams)
        from .replication import export_range as _export_range

        exported = []
        try:
            for rng in self.po.server_key_ranges_of(
                    self.po.my_group_rank()):
                keys, vals, lens = _export_range(self._handle, rng.begin,
                                                 rng.end)
                exported.append((rng, keys, vals,
                                 None if lens is None
                                 else np.asarray(lens)))
        except Exception as exc:  # noqa: BLE001 - veto the commit
            self._snapshotting = False
            self.po.flight.record("snapshot_end", severity="warn",
                                  ok=False, error=repr(exc)[:200])
            self._snapshot_reply(sender, token,
                                 {"error": f"export failed: {exc!r}"})
            return
        epoch = int(req.get("epoch", -1))
        uid = str(req.get("uid", ""))
        fmt = self.po.env.find("PS_SNAPSHOT_FORMAT") or "npz"
        threading.Thread(
            target=self._write_snapshot,
            args=(sender, token, directory, epoch, fmt, uid, exported,
                  t0),
            name="kv-snapshot-write", daemon=True,
        ).start()

    def _write_snapshot(self, sender: int, token: int, directory: str,
                        epoch: int, fmt: str, uid: str, exported: list,
                        t0: float) -> None:
        """Background half of the cut: stream the exported ranges into
        per-range segment files (names stamped with the scheduler's
        attempt uid — a vetoed attempt must never overwrite the
        committed snapshot's bytes) and reply with their digests (the
        scheduler commits by writing the manifest only after EVERY
        server answered clean)."""
        entries = []
        try:
            for rng, keys, vals, lens in exported:
                entries.append(snapshot_mod.write_range_segment(
                    directory, rng.begin, rng.end, keys, vals, lens,
                    fmt=fmt, uid=uid,
                ))
            dur = time.monotonic() - t0
            self._h_snapshot.observe(dur)
            self.po.flight.record(
                "snapshot_end", severity="info", ok=True,
                keys=sum(e["keys"] for e in entries),
                bytes=sum(e["nbytes"] for e in entries),
                duration_s=round(dur, 3),
            )
            self._snapshot_reply(sender, token, {
                "rank": self.po.my_group_rank(),
                "epoch": epoch,
                "ranges": entries,
                "duration_s": round(dur, 3),
            })
        except Exception as exc:  # noqa: BLE001 - veto the commit
            self.po.flight.record("snapshot_end", severity="warn",
                                  ok=False, error=repr(exc)[:200])
            self._snapshot_reply(
                sender, token,
                {"error": f"segment write failed: {exc!r}"},
            )
        finally:
            self._snapshotting = False

    def _run_retune(self, sender: int, token: int, req: dict) -> None:
        """Live knob retune (request thread, behind the snapshot
        fence so it serializes with every earlier queued request).
        Today's only knob: the apply task quantum — the autopilot's
        apply_wait actuator.  A server without an apply pool answers
        clean with nothing applied (the op is cluster-wide; partial
        coverage is expected, not an error)."""
        applied = {}
        tb = req.get("apply_task_bytes")
        if tb is not None and self._apply_pool is not None:
            applied["apply_task_bytes"] = \
                self._apply_pool.set_task_bytes(int(tb))
            self.po.flight.record("apply_retune", severity="info",
                                  task_bytes=applied["apply_task_bytes"])
        self._snapshot_reply(sender, token, {
            "rank": self.po.my_group_rank(), "applied": applied,
        })

    def _snapshot_reply(self, dest: int, token: int,
                        payload: dict) -> None:
        import json as _json

        from ..message import Command, Control

        msg = Message()
        msg.meta.recver = dest
        msg.meta.sender = self.po.van.my_node.id
        msg.meta.request = False
        msg.meta.timestamp = token  # the scheduler's gather token
        msg.meta.control = Control(cmd=Command.SNAPSHOT)
        msg.meta.body = _json.dumps(payload).encode()
        try:
            self.po.van.send(msg)
        except Exception as exc:  # noqa: BLE001 - scheduler times out
            log.warning(f"snapshot reply to {dest} failed: {exc!r}")

    # -- model namespaces (docs/serving_reads.md) -----------------------------

    def _run_namespace(self, sender: int, token: int, op: str,
                       req: dict) -> None:
        """Model-namespace control ops, on the request thread behind
        the snapshot fence so each op serializes against every earlier
        queued request (the routing-cutover ordering trick).
        ``publish`` stages a committed snapshot manifest into an
        OFF-LINE store on a background thread — serving never pauses;
        ``flip`` atomically swaps the staged store in (apply-pool
        quiesce, then one pointer assignment); ``rollback`` swaps the
        displaced store straight back."""
        handle = self._handle
        if handle is None:
            self._snapshot_reply(sender, token,
                                 {"error": "no request handle set"})
            return
        if not isinstance(getattr(handle, "store", None), dict):
            # Tiered / custom handles keep state outside a plain dict —
            # a store-pointer swap would strand it.  Decline loudly
            # (decline matrix, docs/serving_reads.md).
            self._snapshot_reply(sender, token, {
                "error": "model namespaces need a plain dict store "
                         "(tiered/custom handles decline)"})
            return
        if op == "publish":
            directory = req.get("dir") or self._snapshot_dir
            if not directory:
                self._snapshot_reply(sender, token, {
                    "error": "publish needs a snapshot directory"})
                return
            if self._ns_staging:
                self._snapshot_reply(sender, token, {
                    "error": "a namespace stage is already in progress"})
                return
            self._ns_staging = True
            threading.Thread(
                target=self._stage_namespace,
                args=(sender, token, directory,
                      str(req.get("namespace", "model")),
                      str(req.get("version", ""))),
                name="kv-ns-stage", daemon=True,
            ).start()
            return
        if op == "flip":
            staged = self._ns_staged
            if staged is None:
                self._snapshot_reply(sender, token, {
                    "error": "flip without a staged namespace "
                             "(publish first)"})
                return
            err = self._quiesce_applies("namespace flip")
            if err is not None:
                self._snapshot_reply(sender, token, {"error": err})
                return
            name, version, new_store = staged
            self._ns_prev = (*self._ns_current, handle.store)
            handle.store = new_store
            self._ns_current = (name, version)
            self._ns_staged = None
            self._after_namespace_swap("namespace_flip", name, version)
            self._snapshot_reply(sender, token, {
                "rank": self.po.my_group_rank(),
                "namespace": name, "version": version,
                "keys": len(new_store),
            })
            return
        prev = self._ns_prev  # rollback
        if prev is None:
            self._snapshot_reply(sender, token, {
                "error": "rollback without a previous namespace"})
            return
        err = self._quiesce_applies("namespace rollback")
        if err is not None:
            self._snapshot_reply(sender, token, {"error": err})
            return
        name, version, old_store = prev
        self._ns_prev = (*self._ns_current, handle.store)
        handle.store = old_store
        self._ns_current = (name, version)
        self._after_namespace_swap("namespace_rollback", name, version)
        self._snapshot_reply(sender, token, {
            "rank": self.po.my_group_rank(),
            "namespace": name, "version": version,
            "keys": len(old_store),
        })

    def _quiesce_applies(self, what: str) -> Optional[str]:
        """Drain every apply submitted so far (request thread only); a
        timeout vetoes the store swap exactly like it vetoes a
        snapshot cut — swapping under a shard thread mid-write would
        tear the displaced store."""
        if self._apply_pool is None:
            return None
        tok = self._apply_pool.submit_token()
        if not self._apply_pool.quiesce(
                tok, timeout_s=self._snapshot_quiesce_s):
            return (f"apply pool did not quiesce within "
                    f"{self._snapshot_quiesce_s}s — refusing {what}")
        return None

    def _after_namespace_swap(self, kind: str, name: str,
                              version: str) -> None:
        if self._qos_stamps:
            # Bump the push stamp so every hot-cache entry filled under
            # the displaced namespace fails validity on the worker's
            # next observe — lazy, but bounded by the cache TTL.
            with self._qos_mu:
                self._push_version += 1
        self.po.model_namespace = {"name": name, "version": version}
        self.po.flight.record(kind, severity="info",
                              namespace=name, version=version)

    def _serving_ranges(self) -> list:
        """Every range this server answers reads for: owned, plus —
        with replication — every range whose chain it sits in (a
        staged namespace must cover spread reads too)."""
        my = self.po.my_group_rank()
        with self._elastic_mu:
            owned = self._owned
            repl = list(self._replicated_prev or ())
        if owned is not None:
            ranges = list(owned)
            ranges.extend(Range(b, e) for _, b, e in repl)
            return ranges
        ranges = list(self.po.server_key_ranges_of(my))
        if self._replicator is not None and self._replicator.k > 1:
            from .replication import chain_ranks
            for o in range(self.po.num_servers):
                if o != my and my in chain_ranks(
                        o, self._replicator.k, self.po.num_servers):
                    ranges.extend(self.po.server_key_ranges_of(o))
        return ranges

    def _stage_namespace(self, sender: int, token: int, directory: str,
                         name: str, version: str) -> None:
        """Background half of publish: restore the manifest into an
        off-line store while the live one keeps serving; the later
        ``flip`` swaps it in on the request thread."""
        t0 = time.monotonic()
        try:
            manifest = snapshot_mod.load_manifest(directory)
            if manifest is None:
                raise RuntimeError(
                    f"no committed manifest in {directory!r}")
            shim = _StagingStore()
            keys, nbytes = snapshot_mod.restore_into(
                shim, directory, self._serving_ranges(), manifest)
            self._ns_staged = (name, version, shim.store)
            self.po.flight.record(
                "namespace_stage", severity="info", namespace=name,
                version=version, keys=keys,
                duration_s=round(time.monotonic() - t0, 3),
            )
            self._snapshot_reply(sender, token, {
                "rank": self.po.my_group_rank(), "staged": name,
                "version": version, "keys": keys, "bytes": nbytes,
            })
        except Exception as exc:  # noqa: BLE001 - veto the publish
            self._snapshot_reply(sender, token, {
                "error": f"namespace stage failed: {exc!r}"})
        finally:
            self._ns_staging = False

    def _tenant_counter(self, tid: int, kind: str):
        """Lazily created per-tenant counters (psmon's tenant rollup):
        ``tenant.<name>.requests`` / ``tenant.<name>.shed``."""
        ent = self._tenant_counters.get(tid)
        if ent is None:
            name = self.tenants.name(tid)
            ent = self._tenant_counters[tid] = (
                self.po.metrics.counter(f"tenant.{name}.requests"),
                self.po.metrics.counter(f"tenant.{name}.shed"),
            )
        return ent[0] if kind == "requests" else ent[1]

    def _request_error(self, msg: Message, exc: Exception) -> None:
        """Customer hook: the handler raised while processing ``msg`` on
        the serial path — fail the remote waiter fast."""
        if msg.meta.simple_app or not msg.meta.request:
            return
        if msg.meta.batch is not None:
            # A batched frame failed at intake: fail EVERY sub-op's
            # waiter (each holds its own timestamp), not just the
            # envelope's first.
            try:
                subs = _split_batch_message(msg)
                metas = [KVMeta(
                    cmd=s.meta.head, push=s.meta.push, pull=s.meta.pull,
                    sender=s.meta.sender, timestamp=s.meta.timestamp,
                    customer_id=s.meta.customer_id, key=s.meta.key,
                    trace=s.meta.trace,
                ) for s in subs]
                env = KVMeta(sender=msg.meta.sender,
                             customer_id=msg.meta.customer_id,
                             priority=msg.meta.priority,
                             tenant=msg.meta.tenant)
                self.response_batch(env, metas, [("error",)] * len(metas))
            except Exception as be:  # noqa: BLE001 - best effort
                log.warning(f"batched request-error response failed: "
                            f"{be!r}")
            return
        self.response_error(KVMeta(
            cmd=msg.meta.head,
            push=msg.meta.push,
            pull=msg.meta.pull,
            sender=msg.meta.sender,
            timestamp=msg.meta.timestamp,
            customer_id=msg.meta.customer_id,
            key=msg.meta.key,
            # Carry the option so replica-forwarded pushes stay
            # response-free even on the error path.
            option=msg.meta.option,
        ))

    def stop(self) -> None:
        self._customer.stop()
        self.po.unregister_node_failure_hook(self._on_stream_peer_event)
        unreg_snap = getattr(self.po, "unregister_snapshot_hook", None)
        if unreg_snap is not None:
            unreg_snap(self._snapshot_hook)
        if self._routing_hook is not None:
            self.po.unregister_routing_hook(self._routing_hook)
        with self._elastic_mu:
            pend = list(self._pending_ranges.values())
            self._pending_ranges.clear()
        for ent in pend:
            if ent.get("timer") is not None:
                ent["timer"].cancel()
        self._abort_streams()
        if self._apply_pool is not None:
            self._apply_pool.stop()
            self._apply_pool = None
        # AFTER the apply pool: in-flight shard tasks may still read/
        # evict through the tiered store until the pool drains (the
        # handle-replacement path in set_request_handle orders the
        # same way).
        store = getattr(self._handle, "store", None)
        if callable(getattr(store, "close", None)):
            store.close()  # release the tiered store's segment files
        if self._resp_combiner is not None:
            # After the pool: its stop-path emits stranded responses
            # through _send_response, which must still find the lane.
            self._resp_combiner.stop()
        if self._replicator is not None:
            self.po.unregister_node_failure_hook(self._on_self_rehab)
            self._replicator.close()

    # -- streamed chunked pushes (docs/chunking.md) --------------------------

    _MAX_STREAMS = 64

    def _abort_streams(self) -> None:
        with self._streams_mu:
            handles = list(self._streams.values())
            self._streams.clear()
        for h in handles:
            h.close(respond=False)

    def _sweep_stale_streams(self) -> None:
        """Reclaim streams idle past the TTL: their transfer died at
        the assembler (TTL sweep / table eviction), so no final message
        will ever close them."""
        now = time.monotonic()
        with self._streams_mu:
            stale = [k for k, h in self._streams.items()
                     if now - h.t_last > self._stream_ttl]
            handles = [self._streams.pop(k) for k in stale]
        for k, h in zip(stale, handles):
            log.warning(f"reclaiming stalled stream {k} (idle "
                        f"> {self._stream_ttl:.0f}s)")
            h.close(respond=False)

    def _on_stream_peer_event(self, node_id: int, down: bool) -> None:
        """Node-failure hook: a dead worker's open streams can never
        close (no further chunks) — reclaim them without responding."""
        if not down:
            return
        # A dead sender's batch capability dies with it: its id may be
        # reused by a recovered (possibly un-upgraded) process, which
        # must re-prove itself before seeing aggregated responses.
        self._batch_senders.discard(node_id)
        self._batch_senders_v2.discard(node_id)
        with self._streams_mu:
            stale = [k for k in self._streams if k[0] == node_id]
            handles = [self._streams.pop(k) for k in stale]
        for h in handles:
            log.warning(f"reclaiming open stream from dead node {node_id}")
            h.close(respond=False)

    def _stream_eligible(self, m) -> bool:
        """Streaming apply is the narrow fast path: apply pool present
        (shard-safe handler), no replication (forwards must observe the
        complete payload in arrival order), and no registered recv
        buffer for this (sender, key) (those apply synchronously from
        the pinned buffer).  Everything else waits for the final
        reassembled message — semantics identical to monolithic."""
        return (
            self._apply_pool is not None
            and self._replicator is None
            # Elastic routing live: a stream opened before a cutover
            # would have partially applied keys the final (bounced +
            # re-routed) message then re-applies at the new owner —
            # double-count.  Decline; the reassembled message takes the
            # normal (ownership-checked) path (docs/elasticity.md).
            and self._owned is None
            and (m.sender, m.key) not in self._recv_buffers
            # A partial straggling in after its sender was declared
            # dead must not re-open a stream the failure hook just
            # reclaimed (the van marks the peer down BEFORE the hooks
            # run, so this check closes the race).
            and not self.po.van.is_peer_down(m.sender)
        )

    def _admission_overloaded(self, tenant: int, extra: int = 0) -> bool:
        """Per-tenant admission probe (docs/qos.md): in-flight apply
        backlog plus this tenant's OPEN STREAMS (a streaming chunked
        push occupies server capacity from its first partial, long
        before its pending enters the pool's ledger).  ``extra`` counts
        slots already claimed but not yet submitted — a batched frame's
        earlier sub-ops (docs/batching.md: admission sheds per sub-op,
        so the probe must see the frame's own accepted ops)."""
        if self._admit_limit <= 0 or self._apply_pool is None:
            return False
        n = self._apply_pool.tenant_backlog(tenant) + extra
        if n < self._admit_limit:
            with self._streams_mu:
                n += sum(
                    1 for h in self._streams.values()
                    if getattr(h.pending.meta, "tenant", 0) == tenant
                )
        return n >= self._admit_limit

    # -- shared per-op intake (docs/batching.md) ------------------------------
    #
    # ONE implementation of the per-op intake steps — pull stamps,
    # hot-key accounting, payload decode, admission, replication
    # dedup/forward — used by BOTH _process_request and its batched
    # twin _process_batch, so the two paths cannot silently drift.

    def _owner_rank_of(self, key: int) -> Optional[int]:
        """Group rank owning ``key`` under the current routing (elastic
        table when one is applied, else the static uniform split)."""
        if self._owned is not None:
            with self._elastic_mu:
                table = self._table
            if table is not None:
                for e in table.entries:
                    if e.begin <= key < e.end:
                        return e.owner
            return None
        for i, rng in enumerate(self.po.get_server_key_ranges()):
            if rng.begin <= key < rng.end:
                return i
        return None

    def _intake_pull_stamp(self, meta: KVMeta) -> None:
        """Hot-cache stamp (kv/hot_cache.py): captured at INTAKE —
        every push counted before this point fully applied, so the
        snapshot the shards will take is guaranteed to include them;
        later pushes only make the value newer than the stamp claims
        (conservative, never stale).  Per sub-op on batched frames, so
        read-your-writes survives aggregation in both directions.

        Replica reads (docs/serving_reads.md): a pull for a range whose
        LIVE owner is another rank is answered in the PRIMARY's stamp
        currency — the newest forward stamp claimed at intake — so the
        worker can compare it against the push stamps it has seen from
        that primary (read-your-writes).  A down owner keeps today's
        failover semantics: the replica answers as the range's acting
        truth, stamping with its own counter."""
        if not (self._qos_stamps and meta.pull and not meta.push):
            return
        if (self._replica_reads and self._replicator is not None
                and meta.cmd == 0):
            owner = self._owner_rank_of(int(meta.key))
            my = self.po.my_group_rank()
            if owner is not None and owner != my:
                oid = server_rank_to_id(
                    owner * self.po.group_size + self.po.instance_idx)
                if not self.po.van.is_peer_down(oid):
                    # claimed may be 0 before the first stamped forward
                    # or backfill: advertise 1 ("the primary's initial
                    # version") — a worker that has seen any push from
                    # the primary then re-pulls there, a push-free
                    # reader accepts (and may cache) it.
                    meta.stamp = (
                        self._replicator.claimed_stamp(oid) or 1)
                    return
        with self._qos_mu:
            meta.stamp = self._push_version

    def _intake_hot_keys(self, keys: np.ndarray) -> None:
        """Hot-key accounting: exact per-key counts for small key
        sets; big bulk slices charge the slice's first key with the
        whole weight (slice granularity — a per-key Python loop over
        10k-key messages would tax the hot path)."""
        if not len(keys):
            return
        if len(keys) <= 64:
            for k in keys.tolist():
                self._hot_keys.add(int(k))
        else:
            self._hot_keys.add(int(keys[0]), len(keys))

    def _intake_decode(self, meta: KVMeta, data,
                       lazy_ok: bool) -> Tuple[KVPairs, Optional[tuple]]:
        """Parse one op's data segments into KVPairs, decoding codec
        push payloads — LAZILY (shard-side, docs/compression.md) when
        ``lazy_ok`` and the payload is fixed-k shard-decodable, else
        eagerly.  Returns ``(kvs, wire_payload)``; ``wire_payload``
        keeps a codec push's COMPRESSED bytes so replication forwards
        re-send them without a decompress+recompress round trip."""
        kvs = KVPairs()
        wire_payload = None
        ci = meta.codec
        if len(data) < 2:
            return kvs, None
        kvs.keys = data[0].astype_view(np.uint64).numpy()
        if (ci is not None and ci.raw_len > 0 and meta.push
                and len(data) >= 3):
            codec = codecs_mod.by_wire_id(ci.codec)
            codecs_mod.check_block(ci)
            lens_arr = (data[3].astype_view(np.int32).numpy()
                        if len(data) > 3 else None)
            codes_arr = data[1].astype_view(np.uint8).numpy()
            scales_arr = data[2].astype_view(np.float32).numpy()
            kvs.lens = lens_arr
            wire_payload = (data[1], data[2], lens_arr, ci)
            n_el = ci.raw_len // 4
            # Shard-side decode: a fixed-k push headed for the apply
            # pool defers its decode to the shard threads (each
            # decodes exactly its own keys' segments, in parallel) —
            # one whole-payload decode here would serialize the
            # receive pump and head-of-line-block priority ops behind
            # it.  Ragged / registered-buffer / serial-path / batched
            # sub-op pushes decode eagerly (batched ops are small by
            # construction, so the lazy path buys nothing there).
            lazy = (
                lazy_ok and lens_arr is None and not meta.pull
                and self._apply_pool is not None
                and getattr(codec, "_kind", -1) >= 0
                and len(kvs.keys) > 0
                and n_el % len(kvs.keys) == 0
                and (meta.sender, int(kvs.keys[0]))
                not in self._recv_buffers
            )
            if lazy:
                kvs.enc = (codes_arr, scales_arr, ci)
            else:
                t0 = time.monotonic()
                kvs.vals = codec.decode(
                    codes_arr, scales_arr, n_el, lens=lens_arr,
                    flags=ci.flags,
                )
                if meta.trace and self.po.tracer.active:
                    dur = time.monotonic() - t0
                    now = self.po.tracer.now_us()
                    self.po.tracer.span(
                        meta.trace, "codec_decode", now - dur * 1e6,
                        dur * 1e6,
                        args={"codec": codec.name,
                              "raw_mb": round(ci.raw_len / 2**20, 1)},
                    )
        else:
            kvs.vals = data[1].numpy()
            if len(data) > 2:
                kvs.lens = data[2].astype_view(np.int32).numpy()
        return kvs, wire_payload

    # Coalescing window for overload_shed flight events (seconds).
    _SHED_FLIGHT_WINDOW_S = 0.5

    def _record_shed_flight(self, tenant_id: int, sender: int, ts: int,
                            trace: int = 0, **detail) -> None:
        """Flight-record one shed, coalesced per tenant: sheds happen
        at request rate under a storm, and per-event recording would
        wrap the bounded ring with identical spam (evicting the
        failover/epoch/stall context a postmortem needs).  At most one
        event per tenant per window, carrying the suppressed count.
        Runs on the single processing thread — no lock."""
        ent = self._shed_flight.setdefault(tenant_id, [0.0, 0])
        now = time.monotonic()
        if now - ent[0] >= self._SHED_FLIGHT_WINDOW_S:
            if trace:
                # Active trace id in scope: pstrace --slowest prints
                # the shed inline with the trace it coalesced under.
                detail["trace"] = f"{trace:x}"
            self.po.flight.record(
                "overload_shed", severity="warn",
                tenant=self.tenants.name(tenant_id),
                sender=sender, ts=ts, coalesced=ent[1], **detail,
            )
            ent[0] = now
            ent[1] = 0
        else:
            ent[1] += 1

    def _intake_admission(self, meta: KVMeta, extra: int = 0) -> bool:
        """Per-tenant admission at intake (docs/qos.md): counts the
        request against its tenant and returns True when it must be
        SHED (the caller answers OPT_OVERLOAD / records the per-op
        code).  ``extra`` counts a batched frame's own earlier
        accepted sub-ops, so admission sheds PER SUB-OP."""
        if not (self._admit_limit > 0 and self._apply_pool is not None
                and meta.option != OPT_REPLICA and meta.cmd == 0):
            return False
        self._tenant_counter(meta.tenant, "requests").inc()
        if self._admission_overloaded(meta.tenant, extra=extra):
            self._c_shed.inc()
            self._tenant_counter(meta.tenant, "shed").inc()
            # Flight recorder (docs/observability.md): sheds are the
            # watchdog's primary overload signal; coalesced per tenant
            # (see _record_shed_flight).
            self._record_shed_flight(meta.tenant, meta.sender,
                                     meta.timestamp,
                                     trace=getattr(meta, "trace", 0))
            return True
        return False

    def _intake_replicate(self, meta: KVMeta, kvs: KVPairs,
                          wire_payload, copy: bool = False) -> bool:
        """Chain-replication intake of one push (docs/
        fault_tolerance.md): dedup a duplicate origin (a worker's
        failover retry racing the primary's forwarded copy, in either
        order) and chain-forward accepted worker pushes IN ARRIVAL
        ORDER on this (single) processing thread.  Returns True when
        the op is a pure-push duplicate — apply nothing, just ack; a
        dup WITH a pull half is mutated (push stripped) so the pull
        still serves."""
        if (self._replicator is None or not meta.push
                or not len(kvs.keys)):
            return False
        if meta.option == OPT_REPLICA:
            # Replica side: CLAIM the forward's stamp at intake —
            # before the dedup check, since a dedup hit means the
            # effect is already in (docs/serving_reads.md).  Pulls
            # intaken after this point may advertise the stamp: per-key
            # apply order == arrival order, so they observe this
            # forward's effect on every shared key.
            if getattr(meta, "stamp", 0):
                self._replicator.note_claimed(meta.sender, meta.stamp)
                if self._replicator.below_import_floor(meta):
                    # A backfill import's cut already contains this
                    # forward; register its origin (so a worker's
                    # failover retry of the same push still dedups)
                    # and skip the apply — += would double-add.
                    self._replicator.should_apply(meta)
                    self._replicator.note_applied(meta.sender,
                                                  meta.stamp)
                    return True
            return not self._replicator.should_apply(meta)
        if not self._replicator.should_apply(meta):
            # Duplicate origin (a failover retry racing the forwarded
            # copy): the ORIGINAL apply already bumped/assigned a push
            # version — stamp the ack with the CURRENT version, no
            # bump, so _qos_push_done cannot inflate the counter with
            # a version no forward will ever carry (replicas would lag
            # forever against it).
            if self._qos_stamps:
                with self._qos_mu:
                    meta.stamp = self._push_version
            if meta.pull:
                meta.push = False
                kvs.vals = np.empty(0, kvs.vals.dtype)
                return False
            return True
        if self._qos_stamps:
            # Pre-assign the push version at INTAKE (arrival order ==
            # forward order, single request thread) so the forward
            # carries it — the replica-read consistency currency
            # (docs/serving_reads.md).  _qos_push_done then no-ops
            # (stamp != 0) and the response piggybacks this stamp.
            with self._qos_mu:
                self._push_version += 1
                meta.stamp = self._push_version
        # Codec pushes forward their COMPRESSED wire bytes; a
        # registered-buffer payload is snapshotted (copy=True) —
        # the pump overwrites the shared buffer on the sender's
        # next push while the replica lane may still serialize.
        self._replicator.forward(meta, kvs, copy=copy,
                                 wire=wire_payload)
        return False

    def _stream_part(self, msg: Message) -> None:
        """One OPT_XFER_PART partial: feed the newly completed whole-key
        slice to this transfer's open stream (opening it on first
        touch).  Ineligible servers drop partials — the final complete
        message always follows and takes the normal path."""
        key = getattr(msg, "_xfer_key", None)
        if key is None or len(msg.data) < 2:
            return
        self._stream_ticks += 1
        if self._stream_ticks % 64 == 0:
            self._sweep_stale_streams()
        with self._streams_mu:
            h = self._streams.get(key)
        if h is None:
            m = msg.meta
            if not self._stream_eligible(m):
                return
            if (m.head == 0 and m.option != OPT_REPLICA
                    and self._admission_overloaded(m.tenant)):
                # Over the tenant's bound: don't open the stream —
                # partials drop, and the FINAL reassembled message
                # sheds atomically at the normal admission check
                # (nothing applied, OPT_OVERLOAD fast-fail).
                return
            meta = KVMeta(
                cmd=m.head, push=True, pull=False, sender=m.sender,
                timestamp=m.timestamp, customer_id=m.customer_id,
                key=m.key, addr=m.addr, val_len=m.val_len, option=0,
                priority=m.priority, trace=m.trace, tenant=m.tenant,
            )
            h = self._apply_pool.begin_stream(meta)
            self._c_push_reqs.inc()
            evicted = None
            with self._streams_mu:
                if len(self._streams) >= self._MAX_STREAMS:
                    victim = next(iter(self._streams))
                    evicted = self._streams.pop(victim)
                    log.warning(
                        f"stream table full: aborting transfer {victim}"
                    )
                self._streams[key] = h
            if evicted is not None:
                evicted.close(respond=False)
        kvs = KVPairs(
            keys=msg.data[0].astype_view(np.uint64).numpy(),
            vals=msg.data[1].numpy(),
        )
        if len(kvs.keys):
            self._hot_keys.add(int(kvs.keys[0]), len(kvs.keys))
        h.feed(kvs)

    def _process(self, msg: Message) -> None:
        if msg.meta.simple_app:
            return
        if not msg.meta.request:
            # With replication on, servers receive responses too (the
            # recovery restore's fetch).  Anything else is dropped: a
            # response must never run the request handler.  An ERROR-
            # marked response to one of our own requests (a migration
            # push whose import raised) is recorded so the migration
            # thread keeps the local copy instead of dropping the only
            # one.
            if msg.meta.option == OPT_APPLY_ERROR:
                self._migrate_nacks.add(msg.meta.timestamp)
            if self._replicator is not None:
                self._replicator.absorb_response(msg)
            return
        if self._restore_buffer is not None:  # unlocked fast-path probe
            with self._restore_mu:
                if self._restore_buffer is not None:
                    self._restore_buffer.append(msg)
                    return
        self._process_request(msg)

    def _process_request(self, msg: Message) -> None:
        if msg.meta.head == ROUTING_LOCAL_CMD:
            # Local cutover marker (docs/elasticity.md): the routing
            # hook posts the new table through the request queue so the
            # ownership flip serializes against every earlier request.
            self._apply_routing_update(getattr(msg, "_routing_table",
                                               None))
            return
        if (msg.meta.head == SNAPSHOT_LOCAL_CMD
                and hasattr(msg, "_snapshot_ctl")):
            # Local snapshot fence (docs/durability.md): runs on this
            # thread so the cut serializes against every earlier queued
            # request, exactly like the routing cutover above.
            self._run_snapshot(msg)
            return
        if msg.meta.option == OPT_XFER_PART:
            # Partial delivery of a chunked streaming transfer: feed it
            # to the apply pool (or drop it — the final reassembled
            # message always follows).
            self._stream_part(msg)
            return
        if msg.meta.batch is not None:
            # Multi-op batched frame (docs/batching.md): decode once,
            # fan the sub-ops into the apply pool as a group, answer
            # with one batched response frame.
            self._process_batch(msg)
            return
        if (msg.meta.head == MIGRATE_CMD and msg.meta.push
                and msg.meta.request
                and msg.meta.option != OPT_REPLICA):
            self._import_migration(msg)
            return
        if self._owned is not None and self._elastic_gate(msg):
            return  # parked at a pending range, or bounced WRONG_OWNER
        xfer = getattr(msg, "_xfer_key", None)
        if xfer is not None:
            with self._streams_mu:
                h = self._streams.pop(xfer, None)
            if h is not None:
                # Every key already applied via the streamed partials;
                # closing releases the response (emitted when the last
                # fed slice's shard work completes, behind the
                # per-sender order gate).
                h.close()
                return
        meta = KVMeta(
            cmd=msg.meta.head,
            push=msg.meta.push,
            pull=msg.meta.pull,
            sender=msg.meta.sender,
            timestamp=msg.meta.timestamp,
            customer_id=msg.meta.customer_id,
            key=msg.meta.key,
            addr=msg.meta.addr,
            val_len=msg.meta.val_len,
            option=msg.meta.option,
            priority=msg.meta.priority,
            trace=msg.meta.trace,
            codec=msg.meta.codec,
            tenant=msg.meta.tenant,
            # A replication forward's intake-assigned push stamp
            # (docs/serving_reads.md); 0 on worker requests, so the
            # push-side one-shot bump in _qos_push_done still engages
            # for them.
            stamp=msg.meta.stamp,
        )
        if meta.trace and self.po.tracer.active:
            recv_us = getattr(msg, "_recv_us", None)
            if recv_us is not None:
                # Server intake queue (docs/observability.md): wire
                # arrival (van receive stamp) → this request thread —
                # the customer-queue wait the critical path attributes
                # as server_queue.
                self.po.tracer.span(meta.trace, "server_queue", recv_us,
                                    args={"ts": meta.timestamp,
                                          "push": meta.push})
        self._intake_pull_stamp(meta)
        if meta.cmd == _BATCH_PROBE_CMD and meta.pull:
            # Batch capability probe (docs/batching.md): answered
            # BEFORE the handler, like HOT_KEYS_CMD — the vals carry
            # this build's batch wire version.  Builds predating the
            # aggregation plane route the unknown cmd into their
            # handler and error, which the prober reads as "incapable".
            # Probing also PROVES the sender parses EXT_BATCH frames —
            # it becomes eligible for aggregated responses.  val_len
            # carries the SENDER's wire version (0/1 from older
            # builds): only >= 2 decoders may receive per-op traces.
            self._batch_senders.add(meta.sender)
            if meta.val_len >= 2:
                self._batch_senders_v2.add(meta.sender)
            self.response(meta, KVPairs(
                keys=np.array([1], dtype=np.uint64),
                vals=np.array([_BATCH_WIRE_VERSION], dtype=np.float32),
            ))
            return
        if meta.cmd == HOT_KEYS_CMD and meta.pull:
            # Hot-key introspection (docs/qos.md): answer with the
            # kv.hot_keys top-k — keys + observed counts — so workers
            # can seed their pull caches.  Never touches the handler.
            top = self._hot_keys.top(max(1, min(meta.val_len or 16,
                                                128)))
            self.response(meta, KVPairs(
                keys=np.array([k for k, _ in top], dtype=np.uint64),
                vals=np.array([n for _, n in top], dtype=np.float32),
            ))
            return
        if meta.option == OPT_REPLICA and self.tenants.enabled:
            # Replica-side per-tenant accounting (docs/qos.md): a
            # forward carries its origin tenant's EXT_QOS label, so the
            # replica's rollups attribute the apply load to the TRUE
            # tenant instead of lumping every forward on tenant 0.
            self._tenant_counter(meta.tenant, "requests").inc()
        if self._intake_admission(meta):
            # Admission control (docs/qos.md): this tenant's bounded
            # queue is full — shed BEFORE replication/apply so the
            # request is atomically all-or-nothing, and fail the
            # waiting worker fast with the retryable OPT_OVERLOAD.
            self.response_overload(meta)
            return
        if meta.push:
            self._c_push_reqs.inc()
        if meta.pull:
            self._c_pull_reqs.inc()
        # Per-op intake (the _intake_* helpers): ONE implementation
        # shared with the batched twin _process_batch, so the two
        # paths cannot drift.  lazy_ok=True: only this path may defer
        # a codec push's decode to the shard threads.
        kvs, wire_payload = self._intake_decode(meta, msg.data,
                                                lazy_ok=True)
        self._intake_hot_keys(kvs.keys)
        reg = None
        if meta.push and len(kvs.keys):
            reg = self._recv_buffers.get((meta.sender, int(kvs.keys[0])))
            if reg is not None:
                if np.shares_memory(kvs.vals, reg):
                    # The transport already delivered in place (shm van
                    # register_recv_buffer hook) — alias only, no copy.
                    self.delivered_in_place += 1
                    kvs.vals = kvs.vals.view(reg.dtype)
                else:
                    # Fallback for transports without the hook: copy into
                    # the pre-registered buffer and alias it, so the
                    # app-level address-identity check of the reference
                    # benchmark (test_benchmark.cc:169-181) holds.
                    flat = reg.reshape(-1).view(np.uint8)
                    raw = kvs.vals.reshape(-1).view(np.uint8)
                    flat[: raw.nbytes] = raw
                    kvs.vals = reg.reshape(-1)[
                        : len(kvs.vals.reshape(-1).view(reg.dtype))
                    ]
        log.check(self._handle is not None, "KVServer handle not set")
        if self._replicator is not None:
            from .replication import REPLICA_FETCH_CMD

            if meta.cmd == REPLICA_FETCH_CMD:
                # A recovered primary fetching its range's state.
                self._replicator.handle_fetch(meta, kvs, self)
                return
        if self._intake_replicate(meta, kvs, wire_payload,
                                  copy=reg is not None):
            # Pure-push duplicate origin: apply nothing, still ack the
            # waiting worker.
            self.response(meta)
            return
        if self._apply_pool is not None:
            # Sharded apply: returns immediately — the response is
            # emitted (in per-sender arrival order) by whichever shard
            # thread completes the request last, so the receive pump
            # keeps draining while shards apply concurrently.
            # Registered-buffer pushes apply SYNCHRONOUSLY (wait=True):
            # their vals alias the shared per-(sender, key) buffer,
            # which the pump would overwrite with the sender's next
            # push while shards still read this one — the serial path's
            # implicit handler-before-next-copy guarantee, restored.
            self._apply_pool.submit(meta, kvs, wait=reg is not None)
            return
        t0 = time.monotonic()
        self._handle(meta, kvs, self)
        dur = time.monotonic() - t0
        self._h_serial_apply.observe(dur)
        if meta.trace and self.po.tracer.active:
            now = self.po.tracer.now_us()
            self.po.tracer.span(meta.trace, "apply", now - dur * 1e6,
                                dur * 1e6, args={"keys": len(kvs.keys),
                                                 "push": meta.push})

    # -- batched frames (kv/batching.py, docs/batching.md) --------------------

    def _process_batch(self, msg: Message) -> None:
        """One EXT_BATCH frame: decode once, run per-op intake
        (admission sheds PER SUB-OP, replication forwards/dedups per
        sub-op, per-op hot-cache stamps), then fan the admitted ops
        into the apply pool as a GROUP — shared shard dispatch, one
        batched response frame through the per-sender order gate."""
        env = msg.meta
        # An EXT_BATCH frame from this sender proves its build parses
        # batched frames (covers PS_BATCH_NEGOTIATE=0 clusters, where
        # no probe is ever sent): aggregated responses may flow back.
        # A frame CARRYING per-op traces further proves the v2 table —
        # traced responses may then merge toward it too.
        self._batch_senders.add(env.sender)
        if any(op.trace for op in env.batch.ops):
            self._batch_senders_v2.add(env.sender)
        subs = _split_batch_message(msg)
        if not subs:
            return
        # Conservative fallbacks (decline matrix, docs/batching.md):
        # elastic ownership gates and registered recv buffers are
        # per-op machinery the group apply does not carry — re-slice
        # and run each sub-op through the ordinary pipeline (per-op
        # responses; the worker accepts both response shapes).
        fallback = self._owned is not None
        if not fallback and self._recv_buffers:
            for sub in subs:
                if sub.meta.push and len(sub.data) >= 1:
                    k0 = sub.data[0].astype_view(np.uint64).numpy()
                    if len(k0) and (env.sender,
                                    int(k0[0])) in self._recv_buffers:
                        fallback = True
                        break
        if fallback:
            for sub in subs:
                self._process_request(sub)
            return
        env_meta = KVMeta(
            cmd=0, push=env.push, pull=env.pull, sender=env.sender,
            timestamp=subs[0].meta.timestamp,
            customer_id=env.customer_id, key=subs[0].meta.key,
            priority=env.priority, tenant=env.tenant,
        )
        metas: List[KVMeta] = []
        kvss: List[KVPairs] = []
        results: List[Optional[tuple]] = []
        admitted = 0
        # Per-op intake via the SHARED _intake_* helpers (one
        # implementation with _process_request, so the twins cannot
        # drift).  lazy_ok=False: batched sub-ops are small by
        # construction (PS_BATCH_BYTES), so the lazy shard-side decode
        # buys nothing here; a ragged (lens) sub-op — our combiner
        # never merges these, but a foreign encoder might — still
        # parses its lens so the pool's split declines it LOUDLY
        # (per-op error) instead of applying values at wrong per-key
        # boundaries.
        recv_us = getattr(msg, "_recv_us", None)
        tracer = self.po.tracer
        for sub in subs:
            sm = sub.meta
            meta = KVMeta(
                cmd=0, push=sm.push, pull=sm.pull, sender=env.sender,
                timestamp=sm.timestamp, customer_id=env.customer_id,
                key=sm.key, val_len=sm.val_len, option=0,
                priority=env.priority, codec=sm.codec, tenant=env.tenant,
                trace=sm.trace, stamp=sm.stamp,
            )
            if sm.trace and tracer.active and recv_us is not None:
                # Per-sub-op intake-queue span off the ENVELOPE's wire
                # arrival stamp (the frame arrived once; each traced
                # member attributes the same wait).
                tracer.span(sm.trace, "server_queue", recv_us,
                            args={"ts": sm.timestamp, "push": sm.push})
            kvs, wire_payload = self._intake_decode(meta, sub.data,
                                                    lazy_ok=False)
            self._intake_pull_stamp(meta)
            self._intake_hot_keys(kvs.keys)
            result = None
            if self._intake_admission(meta, extra=admitted):
                # Admission sheds SUB-OPS individually, never the
                # whole frame (docs/qos.md): this op fast-fails with a
                # per-op OPT_OVERLOAD code while its siblings apply.
                result = ("overload",)
            if result is None:
                if meta.push:
                    self._c_push_reqs.inc()
                if meta.pull:
                    self._c_pull_reqs.inc()
                # Per-sub-op chain forward/dedup, on this (single)
                # processing thread in op order — replicas see the
                # exact arrival order, and each forward carries its
                # op's own origin (ts, key) for exactly-once dedup.
                if self._intake_replicate(meta, kvs, wire_payload):
                    result = ("ok", None)  # pure-push dup: ack only
                else:
                    admitted += 1
            metas.append(meta)
            kvss.append(kvs)
            results.append(result)
        log.check(self._handle is not None, "KVServer handle not set")
        if self._apply_pool is not None:
            self._apply_pool.submit_batch(env_meta, metas, kvss, results)
            return
        # Serial path (PS_APPLY_SHARDS=0 / handler without
        # apply_shard): apply each admitted op inline, capture its
        # response, emit ONE batched frame — the per-frame saving is
        # the point even without shard concurrency.
        for i, (meta, kvs) in enumerate(zip(metas, kvss)):
            if results[i] is not None:
                continue
            cap = _OpCapture(self)
            t0 = time.monotonic()
            try:
                self._handle(meta, kvs, cap)
                results[i] = cap.result
            except Exception as exc:  # noqa: BLE001 - per-op fast-fail
                log.warning(
                    f"batched apply failed for ts={meta.timestamp} "
                    f"from {meta.sender}: {exc!r}"
                )
                results[i] = ("error",)
            self._h_serial_apply.observe(time.monotonic() - t0)
        self.response_batch(env_meta, metas, results)

    def response_batch(self, env: KVMeta, metas, results) -> None:
        """ONE response frame for a batched request (docs/batching.md):
        per-op result segments concatenated in op order, per-op
        error/overload codes and hot-cache stamps riding the EXT_BATCH
        table.  Push sub-ops bump the push version here — the moment
        their results leave — exactly like per-op responses; pull
        sub-ops carry the stamp captured at frame intake."""
        if env.option == OPT_REPLICA:
            return
        msg = Message()
        m = msg.meta
        m.app_id = self._customer.app_id
        m.customer_id = env.customer_id
        m.request = False
        m.head = 0  # batched ops are plain-cmd by construction
        m.timestamp = metas[0].timestamp
        m.recver = env.sender
        m.key = metas[0].key
        m.priority = env.priority
        m.tenant = getattr(env, "tenant", 0)
        ops = []
        tracer = self.po.tracer
        tr_active = tracer.active
        for meta, result in zip(metas, results):
            kind = result[0] if result is not None else "ok"
            option = 0
            codec_info = None
            nseg = 0
            if kind == "overload":
                option = OPT_OVERLOAD  # nothing applied: no stamp bump
            elif kind == "error":
                # A failed push may have applied partially: bump the
                # version anyway — conservative invalidation is
                # correct, a skipped one is not (kv/hot_cache.py).
                self._qos_push_done(meta)
                option = OPT_APPLY_ERROR
            else:
                self._qos_push_done(meta)
                res = result[1] if kind == "res" else None
                if meta.pull and res is not None and not res.empty():
                    ci = getattr(meta, "codec", None)
                    enc = None
                    if (ci is not None and ci.raw_len == 0
                            and isinstance(res.vals, np.ndarray)
                            and res.vals.dtype == np.float32
                            and res.vals.size > 0):
                        # Per-sub-op pull compression: the op asked for
                        # a codec via its table entry; the per-op
                        # CodecInfo rides back in the response table.
                        enc = self._encode_response(ci, meta, res)
                    if enc is not None:
                        codes, scales, codec_info = enc
                        msg.add_data(SArray(res.keys))
                        msg.add_data(SArray(codes))
                        msg.add_data(SArray(scales))
                        nseg = 3
                    else:
                        msg.add_data(SArray(res.keys))
                        msg.add_data(SArray(res.vals))
                        nseg = 2
                    if res.lens is not None:
                        # Ragged pull result (a custom handler's lens
                        # response on the serial path): the lens
                        # segment travels per-op, exactly like the
                        # unbatched response() — dropping it would hand
                        # the worker un-segmentable values.
                        msg.add_data(
                            SArray(np.asarray(res.lens, dtype=np.int32))
                        )
                        nseg += 1
            m.push = m.push or meta.push
            m.pull = m.pull or meta.pull
            op_trace = getattr(meta, "trace", 0)
            if op_trace and tr_active:
                # Per-op response-gate exit: the batched analog of
                # _response_msg's respond instant, echoed with the
                # op's id in the response table so the worker's spans
                # stay per-op.
                tracer.instant(op_trace, "respond",
                               args={"to": env.sender,
                                     "ts": meta.timestamp})
            ops.append(_BatchOp(
                push=meta.push, pull=meta.pull,
                timestamp=meta.timestamp, key=meta.key,
                val_len=meta.val_len, option=option,
                stamp=getattr(meta, "stamp", 0), nseg=nseg,
                codec=codec_info, trace=op_trace,
            ))
        m.batch = _BatchInfo(ops=tuple(ops))
        # Already one frame (batch is set, so it can never re-merge),
        # but it rides the sender's response lane for ORDER with any
        # interleaved single-frame responses to the same sender.
        self._send_response(msg)


class _OpCapture:
    """Server proxy for serial-path batched sub-ops: captures the
    handler's ``response`` into ``result`` (so the frame emits ONE
    batched response) and forwards everything else to the server."""

    __slots__ = ("_server", "result")

    def __init__(self, server: "KVServer"):
        self._server = server
        self.result = ("ok", None)

    def response(self, req, res=None) -> None:
        self.result = ("res", res) if res is not None else ("ok", None)

    def response_error(self, req) -> None:
        self.result = ("error",)

    def __getattr__(self, name):
        return getattr(self._server, name)


def _push_segs(meta: KVMeta, all_keys: np.ndarray, vals: np.ndarray,
               positions=None) -> List[np.ndarray]:
    """Per-key value views of a fixed-k push payload (zero copy) — the
    currency of the ``apply_shard`` protocol.  ``positions`` selects a
    shard's subset (indices into the request's full key array); the
    serial path passes None for all keys in order.
    """
    n = len(all_keys)
    if not meta.push or n == 0:
        return []
    log.check(len(vals) % n == 0, "bad push shape")
    k = len(vals) // n
    if positions is None:
        return [vals[i * k:(i + 1) * k] for i in range(n)]
    return [vals[int(p) * k:(int(p) + 1) * k] for p in positions]


def _pack_pull_vals(parts: List[np.ndarray],
                    val_len: Optional[int] = None) -> np.ndarray:
    """Single-pass gather of per-key store arrays into ONE preallocated
    response buffer (the old path validated, indexed, and
    ``np.concatenate``d — three passes and a temp list per pull).  With
    a registered ``val_len`` the output size is known without scanning
    and each key's length is checked as it lands."""
    if not parts:
        return np.empty(0, np.float32)
    dtype = parts[0].dtype
    for p in parts:
        if p.dtype != dtype:
            # Mixed per-key dtypes: promote like the old np.concatenate
            # did (assigning into the promoted buffer is lossless).
            dtype = np.result_type(*[q.dtype for q in parts])
            break
    if val_len is not None:
        out = np.empty(len(parts) * val_len, dtype)
        off = 0
        for p in parts:
            log.check(p.size == val_len,
                      f"stored value length {p.size} != registered "
                      f"val_len {val_len}")
            out[off:off + val_len] = p
            off += val_len
        return out
    total = 0
    for p in parts:
        total += p.size
    out = np.empty(total, dtype)
    off = 0
    for p in parts:
        out[off:off + p.size] = p
        off += p.size
    return out


class KVServerDefaultHandle:
    """push => store[key] += vals; pull => store[key] (kv_app.h:430-452).

    Pushes apply IN PLACE into an owned per-key array (the old path
    reallocated ``store[key] + seg`` on every push); pulls gather into
    one preallocated response buffer.  ``val_len`` (optional) registers
    a fixed per-key value count so pull responses size without scanning
    the store.  Shard-safe via ``apply_shard``: shard affinity (one key
    -> one shard thread) is what makes the lock-free in-place ``+=``
    sound under the sharded apply pool.
    """

    def __init__(self, val_len: Optional[int] = None):
        self.store: Dict[int, np.ndarray] = {}
        self.val_len = val_len
        # Per-(worker, key-slice) error-feedback residuals for codec
        # pull responses (docs/compression.md): created lazily by
        # KVServer._encode_response so the bank shares the store's
        # lifetime and the node's PS_CODEC_EF / telemetry settings.
        self.ef_bank = None

    def apply_shard(self, meta: KVMeta, keys: np.ndarray,
                    segs) -> Optional[List[np.ndarray]]:
        """Apply a push (``segs``: one value view per key, zero-copy
        slices of the received payload) and/or gather pull refs for
        exactly ``keys``.  Each key is only ever presented to one shard
        thread (or the single serial thread), so per-key state needs no
        locking."""
        store = self.store
        if meta.push:
            for key, seg in zip(keys, segs):
                key = int(key)
                cur = store.get(key)
                if cur is None:
                    store[key] = seg.copy()  # owned: later += is in place
                else:
                    # A key's dtype is fixed by its first push: the old
                    # reallocating path silently PROMOTED on mixed-dtype
                    # pushes; in-place would silently DOWNCAST instead —
                    # fail loudly rather than corrupt precision.
                    log.check(
                        cur.dtype == seg.dtype,
                        f"push dtype {seg.dtype} != stored dtype "
                        f"{cur.dtype} for key {key}",
                    )
                    # Large f32/f64 adds run GIL-free in the native
                    # core (bit-identical to numpy's in-place add) so
                    # apply shards overlap the receive pump's decode.
                    # _env: set by set_request_handle so a per-node
                    # PS_NATIVE=0 override disables this path too.
                    if not native.try_iadd(cur, seg,
                                           env=getattr(self, "_env",
                                                       None)):
                        cur += seg
        if meta.pull:
            parts = []
            for key in keys:
                arr = store.get(int(key))
                # A missing key must fail loudly: a zero-length chunk
                # would silently shift later keys' values in the
                # caller's buffer.
                log.check(arr is not None, f"pull of unknown key {key}")
                parts.append(arr)
            return parts
        return None

    def __call__(self, req_meta: KVMeta, req_data: KVPairs, server: KVServer):
        parts = self.apply_shard(
            req_meta, req_data.keys,
            _push_segs(req_meta, req_data.keys, req_data.vals),
        )
        if req_meta.pull:
            server.response(req_meta, KVPairs(
                keys=req_data.keys,
                vals=_pack_pull_vals(parts, self.val_len),
            ))
        else:
            server.response(req_meta)


class KVServerOptimizerHandle:
    """Server-side optimizer for the async-PS pattern (docs/overview.md
    of the reference: workers push gradients with no inter-worker
    barrier; the SERVER owns the optimizer and applies each push as it
    arrives; pulls return current parameters).

    push => params[key] = update(params[key], grad); pull => params[key].
    The engine path's equivalent is the fused Pallas handles
    (``server_handle="sgd_momentum"/"adam"``); this is the message-path
    (host/numpy) twin so both PS aggregation modes offer optimizers.

    ``kind``: "sgd" | "sgd_momentum" | "adam".  Unknown keys initialize
    to zeros on first push (or seed via ``init``).  Updates apply IN
    PLACE into owned param/slot arrays (no per-push reallocation), and
    the handle is shard-safe via ``apply_shard`` (shard affinity keys
    every per-key slot to one thread).
    """

    def __init__(self, kind: str = "sgd", lr: float = 0.01,
                 momentum: float = 0.9, betas=(0.9, 0.999),
                 eps: float = 1e-8):
        log.check(kind in ("sgd", "sgd_momentum", "adam"),
                  f"unknown optimizer {kind!r}")
        self.kind = kind
        self.lr = lr
        self.momentum = momentum
        self.betas = betas
        self.eps = eps
        self.store: Dict[int, np.ndarray] = {}
        self._m: Dict[int, np.ndarray] = {}
        self._v: Dict[int, np.ndarray] = {}
        self._t: Dict[int, int] = {}
        self.ef_bank = None  # codec pull-response EF (compression.md)

    def init(self, key: int, value: np.ndarray) -> None:
        self.store[int(key)] = np.asarray(value, np.float32).copy()

    def _apply(self, key: int, grad: np.ndarray) -> None:
        p = self.store.get(key)
        if p is None:
            p = np.zeros_like(grad)
            self.store[key] = p
        if self.kind == "sgd":
            p -= self.lr * grad
        elif self.kind == "sgd_momentum":
            m = self._m.get(key)
            if m is None:
                m = np.zeros_like(grad)
                self._m[key] = m
            m *= self.momentum
            m += grad
            p -= self.lr * m
        else:  # adam
            b1, b2 = self.betas
            t = self._t.get(key, 0) + 1
            self._t[key] = t
            m = self._m.get(key)
            if m is None:
                m = np.zeros_like(grad)
                self._m[key] = m
            v = self._v.get(key)
            if v is None:
                v = np.zeros_like(grad)
                self._v[key] = v
            m *= b1
            m += (1 - b1) * grad
            v *= b2
            v += (1 - b2) * grad * grad
            mhat = m / (1 - b1 ** t)
            vhat = v / (1 - b2 ** t)
            p -= self.lr * mhat / (np.sqrt(vhat) + self.eps)

    def apply_shard(self, meta: KVMeta, keys: np.ndarray,
                    segs) -> Optional[List[np.ndarray]]:
        """Shard-safe apply protocol (see KVServerDefaultHandle)."""
        if meta.push:
            for key, seg in zip(keys, segs):
                self._apply(int(key), seg.astype(np.float32, copy=False))
        if meta.pull:
            parts = []
            for key in keys:
                arr = self.store.get(int(key))
                log.check(arr is not None, f"pull of unknown key {key}")
                parts.append(arr)
            return parts
        return None

    # -- state iterator (docs/durability.md) ---------------------------------
    #
    # The export_range/import_range currency is (keys, flat vals,
    # per-key lens) — replication fetch, elastic range migration, and
    # cluster snapshots all move state through it.  The optimizer
    # handle PACKS ITS SLOTS into the same per-key record so every one
    # of those planes carries them for free (the PR 9 debt: migration
    # used to strand momentum/adam state on the old owner):
    #
    #   sgd           [param]                         (len n)
    #   sgd_momentum  [param, m, kind_bits]           (len 2n + 1)
    #   adam          [param, m, v, t_bits, kind_bits] (len 3n + 2)
    #
    # Missing slots export as zeros — bit-identical to the lazy
    # zeros-on-first-push initialization, so a restored handle's next
    # update is bit-exact vs an uninterrupted one.  The adam step
    # count travels as the int32 BIT PATTERN viewed as float32 (this
    # plane is never codec-quantized), so it round-trips exactly.
    #
    # Slot-carrying records are tagged TWICE — an explicit layout
    # marker, not a length heuristic: (1) a NEGATIVE per-key len (the
    # magnitude is still the record length; a params-only source —
    # plain-dict peer, a DefaultHandle-written snapshot — always
    # exports positive lens, so a parameter row can never be mistaken
    # for a packed record, and the generic dict-store import refuses
    # packed records loudly), and (2) a trailing kind_bits element
    # (the _KIND_CODES int32 bit pattern as float32) inside the
    # record, so a record packed by a DIFFERENT optimizer kind
    # refuses loudly even when the lengths happen to collide
    # (momentum n=2 and adam n=1 both pack to 4 floats without it).
    # Every consumer of this currency (generic import, snapshot range
    # filtering) reads lens through abs(); the files/wire carry
    # int32, so the sign survives the whole journey.

    _KIND_CODES = {"sgd_momentum": 0x70731, "adam": 0x70732}

    def export_range(self, begin: int, end: int):
        """Snapshot params + optimizer slots for keys in [begin, end)."""
        from .replication import _snapshot_items

        items = _snapshot_items(self.store, begin, end)
        pairs = sorted((k, p) for k, p in items if begin <= k < end)
        keys = np.asarray([k for k, _ in pairs], dtype=np.uint64)
        recs: List[np.ndarray] = []
        lens: List[int] = []
        for k, p in pairs:
            p = np.asarray(p, dtype=np.float32).reshape(-1)
            rec = [p]
            if self.kind in ("sgd_momentum", "adam"):
                m = self._m.get(k)
                rec.append(np.zeros_like(p) if m is None
                           else np.asarray(m, np.float32).reshape(-1))
            if self.kind == "adam":
                v = self._v.get(k)
                rec.append(np.zeros_like(p) if v is None
                           else np.asarray(v, np.float32).reshape(-1))
                rec.append(np.asarray([self._t.get(k, 0)],
                                      dtype=np.int32).view(np.float32))
            if self.kind != "sgd":
                rec.append(np.asarray([self._KIND_CODES[self.kind]],
                                      dtype=np.int32).view(np.float32))
            recs.append(np.concatenate(rec))
            # Negative len == "this record carries slots" (see the
            # layout comment above); plain sgd records are just the
            # params and stay positive.
            lens.append(-recs[-1].size if self.kind != "sgd"
                        else recs[-1].size)
        vals = (np.concatenate(recs) if recs
                else np.empty(0, np.float32))
        return keys, vals, np.asarray(lens, dtype=np.int32)

    def import_range(self, keys, vals, lens) -> None:
        """Load records written by :meth:`export_range` (same ``kind``
        on both sides — the cluster runs one handle type).  A record
        tagged slot-packed (negative len) whose length does not match
        THIS kind's packing fails loudly — silently mis-splitting it
        would corrupt the key.  Untagged (positive-len) records are a
        params-only source (plain-dict peer, a DefaultHandle-written
        snapshot) and import as params with fresh slots, exactly like
        a first push would initialize them."""
        off = 0
        n_keys = len(keys)
        for i, key in enumerate(keys):
            key = int(key)
            raw_len = (int(lens[i]) if lens is not None
                       else len(vals) // max(n_keys, 1))
            rec_len = abs(raw_len)
            rec = np.asarray(vals[off:off + rec_len], dtype=np.float32)
            off += rec_len
            if raw_len >= 0:
                # Params-only source: fresh slots, like a first push.
                self.store[key] = rec.copy()
                continue
            # Slot-packed: the trailing kind_bits element names the
            # WRITER's kind — refuse a mismatch loudly even when the
            # record lengths collide (see the layout comment).
            log.check(
                self.kind != "sgd",
                f"slot-packed record for key {key} but this handle "
                f"is kind='sgd' — mixed optimizer kinds cannot share "
                f"state",
            )
            src_code = (int(rec[-1:].view(np.int32)[0])
                        if rec_len > 0 else -1)
            log.check(
                src_code == self._KIND_CODES[self.kind],
                f"slot-packed record for key {key} was written by a "
                f"different optimizer kind (code {src_code:#x}, this "
                f"handle wants "
                f"{self._KIND_CODES[self.kind]:#x}/{self.kind}) — "
                f"mixed optimizer kinds cannot share state",
            )
            body = rec_len - 1  # sans kind_bits
            if self.kind == "adam":
                log.check(
                    body > 1 and (body - 1) % 3 == 0,
                    f"slot-packed record of length {rec_len} for key "
                    f"{key} does not match the adam [p,m,v,t] layout",
                )
                n = (body - 1) // 3
                self.store[key] = rec[:n].copy()
                self._m[key] = rec[n:2 * n].copy()
                self._v[key] = rec[2 * n:3 * n].copy()
                self._t[key] = int(
                    rec[3 * n:3 * n + 1].view(np.int32)[0])
            else:  # sgd_momentum (the only other slot-packing kind)
                log.check(
                    body > 0 and body % 2 == 0,
                    f"slot-packed record of length {rec_len} for key "
                    f"{key} does not match the sgd_momentum [p,m] "
                    f"layout",
                )
                n = body // 2
                self.store[key] = rec[:n].copy()
                self._m[key] = rec[n:2 * n].copy()

    def drop_keys(self, keys) -> None:
        """Migration drop: params AND slots leave together (a stranded
        slot would silently corrupt the key if the range ever migrated
        back).  A tiered param store drops cold keys O(1) via
        ``discard`` instead of deserializing bytes nobody reads."""
        drop = _store_drop_fn(self.store)
        for k in np.asarray(keys).reshape(-1).tolist():
            k = int(k)
            drop(k)
            self._m.pop(k, None)
            self._v.pop(k, None)
            self._t.pop(k, None)

    def __call__(self, req_meta: KVMeta, req_data: KVPairs,
                 server: KVServer):
        parts = self.apply_shard(
            req_meta, req_data.keys,
            _push_segs(req_meta, req_data.keys, req_data.vals),
        )
        if req_meta.pull:
            server.response(req_meta, KVPairs(
                keys=req_data.keys,
                vals=_pack_pull_vals(parts),
            ))
        else:
            server.response(req_meta)


def _store_drop_fn(store):
    """Key-drop callable for a handle's store: a tiered store's
    ``discard`` drops cold keys O(1) instead of deserializing segment
    bytes nobody will read; plain dicts fall back to ``pop``."""
    drop = getattr(store, "discard", None)
    if callable(drop):
        return drop
    return lambda k: store.pop(k, None)


def _as_kvs(keys, vals, lens, priority: int) -> KVPairs:
    keys = np.ascontiguousarray(np.asarray(keys, dtype=np.uint64))
    vals = np.ascontiguousarray(np.asarray(vals))
    lens_arr = None if lens is None else np.asarray(lens, dtype=np.int32)
    return KVPairs(keys=keys, vals=vals, lens=lens_arr, priority=priority)
