"""The reference's ``test_benchmark`` port, and the loopback fixtures.

Not a source of speed: what this system does on the chip is measured by
``BENCHMARK.json`` + ``benchmark/`` and explained in ``PERF.md``.  This
module is (1) the parity CLI below, whose ``--mode`` storms the documents
tell an operator to run over a real van and the tests drive through the
launcher, and (2) the in-process cluster fixtures (``_loopback_cluster``,
``_teardown_cluster``, ``kv_loopback_storm`` and the one-leg ``_*_run``
helpers) that tests and ``tools/ps*.py`` import.  What a leg prints on
the CPU is a count or a wall clock of the host plane, never a chip figure.

Parity with ``tests/test_benchmark.cc``: modes PUSH_THEN_PULL / PUSH_PULL /
PUSH_ONLY / PULL_ONLY (:25-30), ``len repeat mode`` arguments, NUM_KEY_PER_SERVER
keys per server (:407-414), goodput printed every LOG_DURATION rounds with
the same metric definitions (:388-396):

    goodput_gbps = 8 * len * total_key_num * iters / elapsed_ns
    latency_ns_per_key = elapsed / iters / total_key_num / 1000

The server uses an assign-and-echo handle (the reference's EmptyHandler
allocates per-key buffers on first push and echoes them on pull,
:131-203), with val/len consistency checks baked in.  Runs over any van;
launch e.g.::

    python -m pslite_tpu.tracker.local -n 1 -s 1 --van shm -- \
        python -m pslite_tpu.benchmark --len 1024000 --repeat 10 --mode push_pull
"""

from __future__ import annotations

import argparse
import os
import time
from typing import Optional

import numpy as np

MODES = ("push_then_pull", "push_pull", "push_only", "pull_only",
         "chunk_hol", "lane_goodput", "quantized_push", "multi_tenant",
         "dlrm_serve", "small_op_storm", "serving_fanin",
         "durable_serve", "replica_read")


def _recv_buffer_mode() -> bool:
    """ENABLE_RECV_BUFFER (reference test_benchmark.cc:268-320)."""
    return bool(int(os.environ.get("ENABLE_RECV_BUFFER", "0")))


class BenchmarkHandle:
    """Assign on push (allocating on first touch), echo on pull.

    Pushes are stored as whole slice blocks (one copy), with the per-key
    store holding views into the block; pulls of the same slice echo the
    block with no per-pull allocation — matching the reference
    EmptyHandler's preallocated per-key buffers (test_benchmark.cc:131-203)
    so the benchmark times the transport, not handler concatenation.
    (The one copy is load-bearing: a loopback van delivers views of the
    sender's own array, so adopting ``data.vals`` zero-copy would alias
    a buffer the worker may mutate between pushes.)"""

    def __init__(self):
        self.store = {}
        self._blocks = {}
        self._gen = 0  # any push invalidates blocks cached before it

    def __call__(self, meta, data, server):
        from .kv.kv_app import KVPairs
        from .utils import logging as log

        sig = (
            (len(data.keys), int(data.keys[0])) if len(data.keys) else None
        )
        if meta.push:
            n = len(data.keys)
            log.check(n > 0 and len(data.vals) % n == 0,
                      "inconsistent val/len in push")
            block = np.array(data.vals)
            self._gen += 1
            self._blocks[sig] = (np.array(data.keys), block, self._gen)
            k = len(block) // n
            for i, key in enumerate(data.keys):
                self.store[int(key)] = block[i * k : (i + 1) * k]
        # A fused push+pull request (ZPushPull) must get vals back, or
        # the push_pull mode would time half the traffic it reports.
        if meta.pull:
            cached = self._blocks.get(sig)
            if (
                cached is not None
                and cached[2] == self._gen  # no overlapping push since
                and np.array_equal(cached[0], data.keys)
            ):
                block = cached[1]
            else:  # different key set / stale block: assemble from store
                block = np.concatenate(
                    [self.store[int(key)] for key in data.keys]
                )
            server.response(meta, KVPairs(keys=data.keys, vals=block))
        else:
            server.response(meta)


def run_chunk_hol(worker, args) -> None:
    """``--mode chunk_hol`` (docs/chunking.md): sequential large pushes
    from a background thread while the main thread samples small-pull
    latency against the same server — the pull request shares the
    per-peer lane (and socket) with the push payload, so its latency IS
    the head-of-line wait.  Run once with ``PS_CHUNK_BYTES`` set and
    once with ``0`` to price the chunking win; one process per node, so
    no shared-GIL convoy pollutes the numbers."""
    import threading

    nk = args.num_keys
    val_len = args.len // 4
    big_keys = np.arange(100, 100 + nk, dtype=np.uint64)
    big_vals = np.ones(nk * val_len, np.float32)
    small_key = np.array([7], dtype=np.uint64)
    small_vals = np.ones(256, np.float32)
    small_out = np.zeros_like(small_vals)
    worker.wait(worker.push(big_keys, big_vals))
    worker.wait(worker.push(small_key, small_vals))
    worker.wait(worker.pull(small_key, small_out, priority=1))
    push_wall = [0.0]

    def pusher():
        t0 = time.perf_counter()
        for _ in range(args.repeat):
            worker.wait(worker.push(big_keys, big_vals, priority=0))
        push_wall[0] = time.perf_counter() - t0

    t = threading.Thread(target=pusher, daemon=True)
    lats = []
    t.start()
    while t.is_alive():
        t0 = time.perf_counter()
        worker.wait(worker.pull(small_key, small_out, priority=1))
        lats.append((time.perf_counter() - t0) * 1e3)
    t.join()
    lats.sort()
    gbps = (8.0 * args.repeat * big_vals.nbytes
            / max(push_wall[0], 1e-9) / 1e9)
    p50 = lats[len(lats) // 2] if lats else 0.0
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else 0.0
    print(
        f"CHUNK_HOL samples={len(lats)} pull_p50_ms={p50:.3f} "
        f"pull_p99_ms={p99:.3f} push_gbps={gbps:.3f}",
        flush=True,
    )


def run_lane_goodput(worker, args, tag: str = "LANE_GOODPUT",
                     codec: Optional[str] = None) -> None:
    """``--mode lane_goodput`` (docs/native_core.md): PIPELINED large
    pushes — up to ``PS_BENCH_PIPELINE`` (default 3) outstanding — so
    the wall clock measures the data plane's sustained single-lane
    throughput instead of the per-push wait chain (wire + apply + RTT)
    that ``chunk_hol``'s sequential pushes serialize on.  A foreground
    thread samples small-pull latency concurrently, so the same run
    prices the priority tail under the bulk storm.

    ``codec`` (the ``quantized_push`` mode, docs/compression.md) runs
    the same storm with the pushes codec-encoded; the printed
    ``push_gbps`` stays defined over the RAW payload bytes, so it IS
    the effective goodput (pre-compression bytes delivered per
    second)."""
    import threading

    nk = args.num_keys
    val_len = args.len // 4
    big_keys = np.arange(100, 100 + nk, dtype=np.uint64)
    # Realistic gradient-like payload: constant vals would quantize
    # losslessly and flatter the codec legs.
    big_vals = np.random.default_rng(11).normal(
        size=nk * val_len
    ).astype(np.float32)
    small_key = np.array([7], dtype=np.uint64)
    small_vals = np.ones(256, np.float32)
    small_out = np.zeros_like(small_vals)
    # Warm the path end to end before timing: codec legs additionally
    # need the codec buffer pools (worker codes / server decode
    # buffers) and the core's span threads populated — the first cold
    # encodes/decodes pay page faults worth tens of ms that would
    # otherwise read as steady-state tail (seen as 26-31 ms first
    # decodes in the trace tier vs 2-3 ms warm).
    for _ in range(4 if codec else 1):
        worker.wait(worker.push(big_keys, big_vals, codec=codec))
    worker.wait(worker.push(small_key, small_vals))
    worker.wait(worker.pull(small_key, small_out, priority=1))
    depth = int(os.environ.get("PS_BENCH_PIPELINE", "3"))
    push_wall = [0.0]

    def pusher():
        t0 = time.perf_counter()
        pending = []
        for _ in range(args.repeat):
            pending.append(worker.push(big_keys, big_vals, priority=0,
                                       codec=codec))
            if len(pending) >= depth:
                worker.wait(pending.pop(0))
        for ts in pending:
            worker.wait(ts)
        push_wall[0] = time.perf_counter() - t0

    t = threading.Thread(target=pusher, daemon=True)
    lats = []
    t.start()
    while t.is_alive():
        t0 = time.perf_counter()
        worker.wait(worker.pull(small_key, small_out, priority=1))
        lats.append((time.perf_counter() - t0) * 1e3)
    t.join()
    lats.sort()
    gbps = (8.0 * args.repeat * big_vals.nbytes
            / max(push_wall[0], 1e-9) / 1e9)
    p50 = lats[len(lats) // 2] if lats else 0.0
    p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))] if lats else 0.0
    print(
        f"{tag} samples={len(lats)} pull_p50_ms={p50:.3f} "
        f"pull_p99_ms={p99:.3f} push_gbps={gbps:.3f}",
        flush=True,
    )


def run_quantized_push(worker, args) -> None:
    """``--mode quantized_push`` (docs/compression.md): the
    ``lane_goodput`` storm with the bulk pushes encoded by the codec
    named in ``PS_BENCH_CODEC`` (empty = uncompressed baseline leg).
    Effective goodput keeps the raw-bytes definition, so the
    compressed/uncompressed ratio is the codec tier's end-to-end win."""
    codec = os.environ.get("PS_BENCH_CODEC", "").strip() or None
    run_lane_goodput(worker, args, tag="QUANTIZED_PUSH", codec=codec)


def _pctl_ms(lats_s: list) -> tuple:
    """(p50, p99) of a latency list, in milliseconds."""
    if not lats_s:
        return 0.0, 0.0
    s = sorted(lats_s)
    return (s[len(s) // 2] * 1e3,
            s[min(len(s) - 1, int(len(s) * 0.99))] * 1e3)


def run_multi_tenant(worker, args) -> None:
    """``--mode multi_tenant`` (docs/qos.md): a serving tenant and a
    bulk tenant sharing one real tcp server.  Worker rank 0 is the
    SERVING tenant: it publishes a small table and samples small-pull
    latency (tenant ``serve``, plain priority — the weighted-fair
    lanes, intake, and apply shards are what protect it).  Worker
    rank 1 is the BULK tenant: it offers multi-MiB pushes at ~10x the
    server's capacity (a deep non-waiting pipeline, tenant ``train``),
    counts OPT_OVERLOAD sheds (retryable fast-fails, never hangs), and
    verifies its applied pushes landed bit-exact.  ``PS_MT_BULK=0``
    turns rank 1 into an idle bystander — the uncontended baseline leg
    over the identical cluster shape."""
    import threading  # noqa: F401  (parity with sibling modes)

    from . import postoffice
    from .kv.kv_app import OverloadError
    from .message import Role

    po = postoffice(Role.WORKER)
    rank = po.my_rank()
    serve_s = float(os.environ.get("PS_MT_SERVE_SECONDS", "4"))
    if rank == 0:
        # Serving tenant: small table, steady small pulls.
        keys = np.arange(8, dtype=np.uint64)
        vals = np.ones(8 * 256, np.float32) * 3.0
        worker.wait(worker.push(keys, vals, tenant="serve"))
        one = np.array([3], dtype=np.uint64)
        out = np.zeros(256, np.float32)
        # Serving ops ride the EXPRESS band (priority 1) AND the serve
        # tenant: express keeps each interactive pull ahead of bulk
        # quanta in every queue, while the tenant label carries the
        # weighted share, per-tenant telemetry, and admission quota
        # (docs/qos.md — priority and tenancy compose, they don't
        # compete).
        t_end = time.perf_counter() + 0.5
        while time.perf_counter() < t_end:  # warm the path
            worker.wait(worker.pull(one, out, tenant="serve",
                                    priority=1))
        lats = []
        t_end = time.perf_counter() + serve_s
        while time.perf_counter() < t_end:
            t0 = time.perf_counter()
            worker.wait(worker.pull(one, out, tenant="serve",
                                    priority=1))
            lats.append(time.perf_counter() - t0)
        from .utils import logging as log

        log.check(np.all(out == 3.0), "serving pull returned bad values")
        p50, p99 = _pctl_ms(lats)
        print(f"MULTI_TENANT role=serve samples={len(lats)} "
              f"pull_p50_ms={p50:.3f} pull_p99_ms={p99:.3f}",
              flush=True)
        return
    # Bulk tenant (rank 1).
    if not int(os.environ.get("PS_MT_BULK", "1")):
        time.sleep(serve_s + 1.0)  # idle bystander: baseline leg
        print("MULTI_TENANT role=bulk applied=0 shed=0 "
              "push_gbps=0.000 store_exact=True", flush=True)
        return
    nk = 8
    val_len = int(os.environ.get("PS_MT_BULK_MB", "4")) * (1 << 20) // 4 // nk
    bulk_keys = np.arange(1000, 1000 + nk, dtype=np.uint64)
    bulk_vals = np.ones(nk * val_len, np.float32)
    depth = int(os.environ.get("PS_MT_DEPTH", "12"))
    applied = shed = 0
    pending: list = []

    def _settle(ts) -> None:
        nonlocal applied, shed
        try:
            worker.wait(ts)
            applied += 1
        except OverloadError:
            shed += 1

    t0 = time.perf_counter()
    t_end = t0 + serve_s + 1.5
    while time.perf_counter() < t_end:
        pending.append(worker.push(bulk_keys, bulk_vals,
                                   tenant="train"))
        if len(pending) >= depth:
            _settle(pending.pop(0))
    for ts in pending:
        _settle(ts)
    wall = time.perf_counter() - t0
    gbps = 8.0 * applied * bulk_vals.nbytes / max(wall, 1e-9) / 1e9
    # Bit-exact accounting: the += store must hold EXACTLY one unit per
    # non-shed push — a shed that half-applied, or a hung wait, shows
    # up right here.
    out = np.zeros_like(bulk_vals)
    worker.wait(worker.pull(bulk_keys, out, tenant="train"))
    exact = bool(np.all(out == np.float32(applied)))
    print(f"MULTI_TENANT role=bulk applied={applied} shed={shed} "
          f"push_gbps={gbps:.3f} store_exact={exact}", flush=True)


def run_dlrm_serve(worker, args) -> None:
    """``--mode dlrm_serve`` (docs/qos.md): the DLRM inference path
    over the message-path PS — a Zipf single-row embedding pull storm
    (models/dlrm.py), bit-exactness spot-checked every 64 pulls.  With
    ``PS_HOT_CACHE=1`` the head of the curve answers locally; the
    printed hit rate comes from the worker's cache counters."""
    from .models.dlrm import (DLRMConfig, push_embedding_table,
                              serve_embedding_storm)

    cfg = DLRMConfig(
        num_rows=int(os.environ.get("PS_DLRM_ROWS", "1024")),
        emb_dim=int(os.environ.get("PS_DLRM_DIM", "16")),
    )
    n_pulls = args.repeat
    push_embedding_table(worker, cfg, tenant="serve")
    if worker.hot_cache is not None:
        # Honest top-k seeding: a short UNMEASURED warm storm teaches
        # the server's kv.hot_keys tracker the real Zipf head (the
        # table push alone charges its first key with the whole bulk
        # weight), THEN the fetched top-k restricts admission and the
        # cache is cleared — the measured storm prices exactly the
        # seeded-from-the-server configuration the tier advertises.
        serve_embedding_storm(worker, cfg, min(200, n_pulls), seed=3,
                              tenant="serve")
        worker.seed_hot_cache(k=64)
        worker.hot_cache.clear()
        worker.po.metrics.counter("kv.hot_cache.hits").reset()
        worker.po.metrics.counter("kv.hot_cache.misses").reset()
    lats = serve_embedding_storm(worker, cfg, n_pulls, seed=7,
                                 tenant="serve")
    hits = worker.po.metrics.counter("kv.hot_cache.hits").value
    misses = worker.po.metrics.counter("kv.hot_cache.misses").value
    rate = hits / max(hits + misses, 1)
    p50, p99 = _pctl_ms(lats)
    print(f"DLRM_SERVE samples={len(lats)} pull_p50_ms={p50:.4f} "
          f"pull_p99_ms={p99:.4f} hit_rate={rate:.3f} exact=True",
          flush=True)


def run_durable_serve(worker, args) -> None:
    """``--mode durable_serve`` (docs/durability.md): the beyond-RAM
    serving path — publish an embedding table (``PS_DUR_ROWS`` x
    ``PS_DUR_DIM`` floats; size it ~4x the server's
    ``PS_STORE_RAM_MB``), run an UNMEASURED Zipf warm storm so the
    server's ``kv.hot_keys`` top-k learns the real head and the tiered
    store promotes it, then measure the Zipf single-row pull storm.
    Every 64th pull is verified bit-exact inside
    ``serve_embedding_storm`` — a tier serving stale bytes fails the
    mode loudly.  Compare ``PS_STORE_RAM_MB`` set against 0 (all-RAM)
    by running this identical mode under each."""
    from .models.dlrm import (DLRMConfig, push_embedding_table,
                              serve_embedding_storm)

    cfg = DLRMConfig(
        num_rows=int(os.environ.get("PS_DUR_ROWS", "1024")),
        emb_dim=int(os.environ.get("PS_DUR_DIM", "1024")),
    )
    n_pulls = args.repeat
    push_embedding_table(worker, cfg)
    # Honest placement: the warm storm teaches kv.hot_keys the Zipf
    # head (the bulk table push alone charges its first key with the
    # whole weight) and lets the tier settle hot-in-RAM/cold-on-disk
    # BEFORE the measured window.
    serve_embedding_storm(worker, cfg, min(300, n_pulls), seed=3)
    lats = serve_embedding_storm(worker, cfg, n_pulls, seed=7)
    p50, p99 = _pctl_ms(lats)
    print(f"DURABLE_SERVE samples={len(lats)} pull_p50_ms={p50:.4f} "
          f"pull_p99_ms={p99:.4f} exact=True", flush=True)


def run_small_op_storm(worker, args) -> None:
    """``--mode small_op_storm`` (docs/batching.md): the ops/s regime —
    a depth-bounded pipeline of 4 KiB pushes against one tcp server
    (msgs/s is the headline), then a LOW-LOAD sequential push+wait loop
    (single-op p50 must stay within noise of an unbatched build).
    ``_small_op_run`` runs this identical mode under
    ``PS_BATCH_BYTES=262144`` or ``0``; the store is verified bit-exact
    at applied-count (vals of 1.0 — exact float adds) either way."""
    secs = float(os.environ.get("PS_SOB_SECONDS", "3"))
    depth = int(os.environ.get("PS_SOB_DEPTH", "256"))
    op_bytes = int(os.environ.get("PS_SOB_OP_BYTES", "4096"))
    nk = int(os.environ.get("PS_SOB_KEYS", "1"))
    val_len = max(1, op_bytes // 4 // nk)
    keys = np.arange(nk, dtype=np.uint64)
    # Each op pushes its own ORDINAL as the payload: the benchmark
    # server's assign handle keeps the LAST applied value, so the
    # final pull proves both value bit-exactness and per-key apply
    # order through whatever batching the wire did.  Buffers cycle
    # through a pool deeper than the pipeline (queued frames hold
    # references — don't-mutate-until-wait), so the issue loop prices
    # the transport, not the allocator.
    seq = 0
    pool = [np.empty(nk * val_len, np.float32) for _ in range(depth + 64)]

    def _op_vals(v: float) -> np.ndarray:
        buf = pool[int(v) % len(pool)]
        buf.fill(np.float32(v))
        return buf

    # Warm the path (connection, capability probe, pools).
    for _ in range(32):
        seq += 1
        worker.wait(worker.push(keys, _op_vals(seq)))
    pending: list = []
    n_ops = 0
    t0 = time.perf_counter()
    t_end = t0 + secs
    while time.perf_counter() < t_end:
        seq += 1
        pending.append(worker.push(keys, _op_vals(seq)))
        n_ops += 1
        if len(pending) >= depth:
            worker.wait(pending.pop(0))
    for ts in pending:
        worker.wait(ts)
    wall = time.perf_counter() - t0
    rate = n_ops / max(wall, 1e-9)
    # Low-load single-op latency: sequential push+wait — with the
    # combiner idle, each op must dispatch at the next pickup with no
    # timer latency (the PS_BATCH_WINDOW_US=0 contract).
    lats = []
    t_end = time.perf_counter() + min(1.0, secs / 2)
    while time.perf_counter() < t_end:
        seq += 1
        v = _op_vals(seq)
        t1 = time.perf_counter()
        worker.wait(worker.push(keys, v))
        lats.append(time.perf_counter() - t1)
    p50, p99 = _pctl_ms(lats)
    out = np.zeros(nk * val_len, np.float32)
    worker.wait(worker.pull(keys, out))
    exact = bool(np.all(out == np.float32(seq)))
    frames = worker.po.metrics.counter("van.batched_frames").value
    bops = worker.po.metrics.counter("van.batch_ops").value
    opf = bops / frames if frames else 0.0
    print(f"SMALL_OP ops={n_ops} secs={wall:.3f} msgs_per_s={rate:.1f} "
          f"p50_ms={p50:.3f} p99_ms={p99:.3f} ops_per_frame={opf:.1f} "
          f"store_exact={exact}", flush=True)


def run_serving_fanin(worker, args) -> None:
    """``--mode serving_fanin`` (docs/batching.md): the DLRM serving
    FAN-OUT regime — each request is ``PS_SF_FANOUT`` independent
    single-row embedding lookups (Zipf rows, table SPREAD across every
    server), issued via ``KVWorker.multi_get`` with the hot-key cache
    COLD.  ``_serving_fanin_run`` runs this identical mode under
    ``PS_BATCH_BYTES=262144`` or ``0``: aggregated, a request costs
    ~one EXT_BATCH frame per contacted server each way; unaggregated
    it costs one frame per LOOKUP each way.  Requests/s is the
    headline; frames/request (from the van's recv counter) proves the
    ~1-RTT fan-in; every 32nd request is verified bit-exact; a LOW-
    LOAD sequential single-pull loop guards the unbatched-latency
    contract."""
    from .models.dlrm import (DLRMConfig, embedding_row,
                              push_embedding_table, serve_fanout_storm,
                              spread_row_keys)

    secs = float(os.environ.get("PS_SF_SECONDS", "3"))
    fanout = int(os.environ.get("PS_SF_FANOUT", "64"))
    cfg = DLRMConfig(
        num_rows=int(os.environ.get("PS_SF_ROWS", "2048")),
        emb_dim=int(os.environ.get("PS_SF_DIM", "16")),
    )
    depth = int(os.environ.get("PS_SF_DEPTH", "8"))
    servers = worker.po.num_servers
    push_embedding_table(worker, cfg, spread=True)
    # Warm the path (connections, capability probes, frame pools).
    serve_fanout_storm(worker, cfg, 16, fanout=fanout, seed=1)
    van_recv = worker.po.metrics.counter("van.recv_messages")
    recv0 = van_recv.value
    # Depth-bounded request pipeline (a serving worker handles DEPTH
    # concurrent requests, like small_op_storm's op pipeline): each
    # outstanding request owns its row set and destination buffers;
    # the oldest is waited (and every 32nd verified bit-exact against
    # embedding_row) before its slot recycles.
    from collections import deque

    from .models.dlrm import serving_keys

    row_keys = spread_row_keys(cfg)
    outs_pool = [
        [np.zeros(cfg.emb_dim, np.float32) for _ in range(fanout)]
        for _ in range(depth)
    ]
    # Bounded row pool, reused modulo: sized well past one request's
    # correlation horizon but independent of how many requests the
    # window issues (an eager per-request pool both ballooned memory
    # at large fan-outs and crashed on exhaustion).
    pool_reqs = 4096
    all_rows = serving_keys(cfg, pool_reqs * fanout, seed=7)
    lats = []
    pending: deque = deque()
    free = list(range(depth))
    n_req = 0

    def _retire(check: bool) -> None:
        t_iss, handle, rows, slot = pending.popleft()
        handle.wait()
        lats.append(time.perf_counter() - t_iss)
        if check:
            outs = outs_pool[slot]
            for j, r in enumerate(rows):
                if not np.array_equal(outs[j],
                                      embedding_row(cfg, int(r))):
                    raise RuntimeError(
                        f"fan-out pull of row {r} returned wrong values"
                    )
        free.append(slot)

    t0 = time.perf_counter()
    t_end = t0 + secs
    while time.perf_counter() < t_end:
        base = (n_req % pool_reqs) * fanout
        rows = all_rows[base:base + fanout]
        slot = free.pop()
        key_lists = [row_keys[int(r):int(r) + 1] for r in rows]
        t1 = time.perf_counter()
        handle = worker.multi_get(key_lists, outs=outs_pool[slot])
        pending.append((t1, handle, rows, slot))
        n_req += 1
        if len(pending) >= depth:
            _retire(check=n_req % 32 == 0)
    while pending:
        _retire(check=False)
    wall = time.perf_counter() - t0
    frames_per_req = (van_recv.value - recv0) / max(n_req, 1)
    p50, p99 = _pctl_ms(lats)
    # Low-load single-pull guard: sequential pull+wait of Zipf rows —
    # a lone op must dispatch at the next combiner pickup with no
    # timer latency (the PS_BATCH_WINDOW_US=0 contract).
    row_keys = spread_row_keys(cfg)
    out = np.zeros(cfg.emb_dim, np.float32)
    low = []
    t_end = time.perf_counter() + min(1.0, secs / 2)
    row = 0
    while time.perf_counter() < t_end:
        row = (row + 17) % cfg.num_rows
        t1 = time.perf_counter()
        worker.wait(worker.pull(row_keys[row:row + 1], out))
        low.append(time.perf_counter() - t1)
    low_p50, _ = _pctl_ms(low)
    exact = bool(np.array_equal(out, embedding_row(cfg, row)))
    print(f"SERVING_FANIN reqs={n_req} secs={wall:.3f} "
          f"reqs_per_s={n_req / max(wall, 1e-9):.1f} "
          f"fanout={fanout} servers={servers} "
          f"p50_ms={p50:.3f} p99_ms={p99:.3f} "
          f"frames_per_req={frames_per_req:.2f} "
          f"low_p50_ms={low_p50:.4f} store_exact={exact}", flush=True)


def run_replica_read(worker, args) -> None:
    """``--mode replica_read`` (docs/serving_reads.md): the read-heavy
    serving regime — every worker aims a Zipf block storm entirely at
    server rank 0's key range, so with ``PS_REPLICA_READS`` on the
    pulls spread across that range's whole replica chain while k=1
    funnels every read through one rank.  Periodic read-your-writes
    probes (push a delta to a per-worker probe block, then IMMEDIATELY
    pull it back) count violations, which must stay 0,
    and every 32nd storm pull is verified bit-exact against the
    worker-held table."""
    from collections import deque

    from .base import WORKER_GROUP

    secs = float(os.environ.get("PS_RR_SECONDS", "3"))
    rows = int(os.environ.get("PS_RR_ROWS", "2048"))
    dim = int(os.environ.get("PS_RR_DIM", "16"))
    batch = int(os.environ.get("PS_RR_BATCH", "16"))
    depth = int(os.environ.get("PS_RR_DEPTH", "8"))
    k = worker.po.env.find_int("PS_KV_REPLICATION", 1)
    servers = worker.po.num_servers
    n_w = max(worker.po.num_workers, 1)
    wrank = worker.po.my_group_rank()
    keys = np.arange(rows, dtype=np.uint64)  # all in rank 0's range
    table = np.stack([np.full(dim, 1.0 + r, np.float32)
                      for r in range(rows)])
    # The default handle's push ADDS: every worker pushes the base
    # table, so the served value is n_w * table (integer-valued fp32,
    # bit-exact).
    worker.wait(worker.push(keys, table.reshape(-1)))
    worker.po.barrier(0, WORKER_GROUP)
    expected = table * n_w
    # Cross-worker settle: a replica may not have applied the OTHER
    # workers' base pushes yet (this worker's stamp floor only covers
    # its own writes), so wait for the storm rows to read complete
    # everywhere before the bit-exact checks arm.
    warm = np.zeros(batch * dim, np.float32)
    deadline = time.perf_counter() + 10.0
    while True:
        warm[:] = 0
        worker.wait(worker.pull(keys[:batch], warm))
        if np.array_equal(warm.reshape(batch, dim), expected[:batch]):
            break
        if time.perf_counter() > deadline:
            raise RuntimeError("base table never settled on replicas")
        time.sleep(0.05)
    worker.po.barrier(0, WORKER_GROUP)
    # Zipf block starts, precomputed; storm rows stay clear of every
    # worker's probe block at the table's top (those values change
    # mid-storm — an in-flight storm pull of a probe row would
    # spuriously mismatch the local expectation).
    rng = np.random.RandomState(7 + wrank)
    zipf = np.minimum(rng.zipf(1.3, size=65536) - 1,
                      rows - 8 * batch - 1).astype(np.int64)
    outs_pool = [np.zeros(batch * dim, np.float32)
                 for _ in range(depth)]
    pending: deque = deque()
    free = list(range(depth))
    lats: list = []
    n_req = 0
    violations = 0

    def _retire(check: bool) -> None:
        t_iss, ts, start, slot = pending.popleft()
        worker.wait(ts)
        lats.append(time.perf_counter() - t_iss)
        if check:
            got = outs_pool[slot].reshape(batch, dim)
            if not np.array_equal(got, expected[start:start + batch]):
                raise RuntimeError(
                    f"storm pull of rows [{start}, {start + batch}) "
                    f"returned wrong values")
        free.append(slot)

    # Per-worker probe block: only THIS worker writes it, so its own
    # push-stamp floor is exactly the read-your-writes frontier.
    p0 = rows - (wrank + 1) * batch
    probe_keys = keys[p0:p0 + batch]
    probe_expected = np.ascontiguousarray(expected[p0:p0 + batch])
    probe_delta = np.ones(batch * dim, np.float32)
    probe_out = np.zeros(batch * dim, np.float32)
    t0 = time.perf_counter()
    t_end = t0 + secs
    zi = 0
    while time.perf_counter() < t_end:
        n_req += 1
        if n_req % 64 == 0:
            # Read-your-writes probe: any replica whose applied stamp
            # trails this push must be rejected and re-pulled from the
            # primary — a violation here is a stale read.
            probe_expected += 1.0
            worker.wait(worker.push(probe_keys, probe_delta))
            probe_out[:] = 0
            worker.wait(worker.pull(probe_keys, probe_out))
            if not np.array_equal(probe_out.reshape(batch, dim),
                                  probe_expected):
                violations += 1
            continue
        start = int(zipf[zi % len(zipf)])
        zi += 1
        slot = free.pop()
        t1 = time.perf_counter()
        ts = worker.pull(keys[start:start + batch], outs_pool[slot])
        pending.append((t1, ts, start, slot))
        if len(pending) >= depth:
            _retire(check=n_req % 32 == 0)
    while pending:
        _retire(check=False)
    wall = time.perf_counter() - t0
    p50, p99 = _pctl_ms(lats)
    fallbacks = worker.po.metrics.counter("replica_read.fallbacks").value
    spread = worker.po.metrics.counter("replica_read.spread").value
    out = np.zeros(batch * dim, np.float32)
    worker.wait(worker.pull(keys[:batch], out))
    exact = bool(np.array_equal(out.reshape(batch, dim),
                                expected[:batch]))
    print(f"REPLICA_READ reqs={n_req} secs={wall:.3f} "
          f"reqs_per_s={n_req / max(wall, 1e-9):.1f} k={k} "
          f"servers={servers} ryw_violations={violations} "
          f"fallbacks={fallbacks} spread={spread} p50_ms={p50:.3f} "
          f"p99_ms={p99:.3f} exact={exact}", flush=True)
    worker.po.barrier(0, WORKER_GROUP)


def run_worker(args) -> None:
    from . import postoffice
    from .kv.kv_app import KVWorker
    from .message import Role

    po = postoffice(Role.WORKER)
    worker = KVWorker(0, 0)
    if args.mode == "chunk_hol":
        run_chunk_hol(worker, args)
        return
    if args.mode == "lane_goodput":
        run_lane_goodput(worker, args)
        return
    if args.mode == "quantized_push":
        run_quantized_push(worker, args)
        return
    if args.mode == "multi_tenant":
        run_multi_tenant(worker, args)
        return
    if args.mode == "dlrm_serve":
        run_dlrm_serve(worker, args)
        return
    if args.mode == "small_op_storm":
        run_small_op_storm(worker, args)
        return
    if args.mode == "serving_fanin":
        run_serving_fanin(worker, args)
        return
    if args.mode == "durable_serve":
        run_durable_serve(worker, args)
        return
    if args.mode == "replica_read":
        run_replica_read(worker, args)
        return
    ranges = po.get_server_key_ranges()
    keys_per_server = args.num_keys
    val_len = args.len // 4  # fp32 elements per key
    keys = np.sort(
        np.concatenate(
            [
                np.arange(keys_per_server, dtype=np.uint64) + r.begin
                for r in ranges
            ]
        )
    )
    total_keys = len(keys)
    vals = np.random.default_rng(po.my_rank()).normal(
        size=total_keys * val_len
    ).astype(np.float32)
    outs = None

    def timed(fn, iters):
        t0 = time.perf_counter_ns()
        for _ in range(iters):
            fn()
        return time.perf_counter_ns() - t0

    def report(tag, elapsed_ns, iters, bytes_per_iter):
        goodput = 8.0 * bytes_per_iter * iters / max(elapsed_ns, 1)
        lat = elapsed_ns / max(iters, 1) / total_keys / 1000.0
        print(
            f"{tag}: {goodput:.3f} Gbps, avg latency {lat:.3f} us/key",
            flush=True,
        )

    # ENABLE_RECV_BUFFER: pulls land in a transport-registered buffer,
    # delivery-in-place counted.
    if _recv_buffer_mode():
        outs = worker.alloc_pull_buffer(keys, val_len)
        if outs is None:
            print("RECV_BUFFER unsupported on this van; plain pulls",
                  flush=True)
    if outs is None:
        outs = np.zeros_like(vals)

    # Warm up (registration / first-touch, as the reference's first rounds).
    worker.wait(worker.push(keys, vals))
    worker.wait(worker.pull(keys, outs))

    payload = total_keys * val_len * 4
    log_every = int(os.environ.get("LOG_DURATION", "10"))
    done = 0
    while done < args.repeat:
        iters = min(log_every, args.repeat - done)
        if args.mode == "push_then_pull":
            e1 = timed(lambda: worker.wait(worker.push(keys, vals)), iters)
            report("push", e1, iters, payload)
            e2 = timed(lambda: worker.wait(worker.pull(keys, outs)), iters)
            report("pull", e2, iters, payload)
        elif args.mode == "push_pull":
            e = timed(
                lambda: worker.wait(worker.push_pull(keys, vals, outs)),
                iters,
            )
            report("push_pull", e, iters, 2 * payload)
        elif args.mode == "push_only":
            e = timed(lambda: worker.wait(worker.push(keys, vals)), iters)
            report("push", e, iters, payload)
        else:  # pull_only
            e = timed(lambda: worker.wait(worker.pull(keys, outs)), iters)
            report("pull", e, iters, payload)
        done += iters

    # Correctness: the last pull must echo the last push (assign handle).
    if args.mode in ("push_then_pull", "push_pull"):
        worker.wait(worker.push(keys, vals))
        worker.wait(worker.pull(keys, outs))
        np.testing.assert_allclose(outs, vals, rtol=1e-6)
        print("CHECK_OK", flush=True)
    if _recv_buffer_mode():
        # In-place deliveries observed (the identity check of
        # test_benchmark.cc:169-181, surfaced as a counter).
        print(f"RECV_BUFFER_HITS {worker.zpull_hits}", flush=True)


def fanout_wall_times(n_peers: int, delay_s: float,
                      rounds: int = 1) -> tuple:
    """Wall times of an N-peer data fan-out over a stub transport whose
    ``send_msg`` costs ``delay_s`` per message: ``(laned, serialized)``
    seconds (best of ``rounds``).

    Prices the Van's per-peer send-lane scheduler ALONE — no sockets,
    no backend, no scheduler bootstrap.  The serialized number replays
    the identical sends with ``PS_SEND_LANES=0``, the pre-lane
    one-message-at-a-time regime (what the old van-wide send lock
    enforced), so ``serialized / laned`` is the fan-out overlap factor.
    """
    from .environment import Environment
    from .message import Message
    from .vans.van import Van

    class _StubPo:
        def __init__(self, env):
            self.env = env

        @staticmethod
        def role_str() -> str:
            return "bench"

    class _SleepWireVan(Van):
        def send_msg(self, msg) -> int:
            time.sleep(delay_s)
            return msg.meta.data_size

    def _run(lanes: bool) -> float:
        van = _SleepWireVan(_StubPo(Environment(
            {"PS_SEND_LANES": "1" if lanes else "0"}
        )))
        best = None
        for _ in range(rounds):
            t0 = time.perf_counter()
            for peer in range(n_peers):
                m = Message()
                m.meta.sender = 1
                m.meta.recver = peer
                van.send(m)
            van._drain_send_lanes(timeout_s=60.0)
            wall = time.perf_counter() - t0
            best = wall if best is None else min(best, wall)
            van._lane_stop = False  # re-arm lanes for the next round
            van._lane_abort = False
        van.profiler.close()
        return best

    return _run(True), _run(False)


def apply_storm_rates(num_shards: int, n_workers: int = 4,
                      msgs_per_worker: int = 8, keys_per_msg: int = 8,
                      val_len: int = 1 << 20, rounds: int = 2) -> float:
    """Msgs/s of a server-side push storm through the apply path with
    ``PS_APPLY_SHARDS=num_shards`` (0 = the serial inline path), over a
    stub responder — no sockets, no scheduler bootstrap: prices the
    apply engine alone (the server_apply analog of
    :func:`fanout_wall_times`).

    ``n_workers`` stub workers enqueue pre-built push requests into ONE
    dispatcher thread (the ``Customer._receiving`` analog), which either
    runs the handle inline (serial, today's regime) or feeds the shard
    pool.  Every message pushes the SAME overlapping key set, so each
    apply is the ``store[key] += seg`` hot path and per-key ordering
    rides shard affinity; the clock stops when the last response is
    emitted.  Best of ``rounds``.

    Sizing note: per-key values default to the reference headline's
    MB-class blocks — numpy releases the GIL inside the add loops, but
    sub-MB segments spend comparable time in GIL handoff churn and the
    shards convoy instead of overlapping.
    """
    import threading

    from .kv.apply_shards import ApplyShardPool
    from .kv.kv_app import (KVMeta, KVPairs, KVServerDefaultHandle,
                            _push_segs)
    from .utils.queues import ThreadsafeQueue

    total = n_workers * msgs_per_worker
    keys = np.arange(keys_per_msg, dtype=np.uint64)
    payloads = [
        np.full(keys_per_msg * val_len, 1.0 + w, np.float32)
        for w in range(n_workers)
    ]

    best = None
    for _ in range(rounds):
        handle = KVServerDefaultHandle()
        done = threading.Event()

        class _StubServer:
            def __init__(self):
                self.responses = 0
                self._mu = threading.Lock()

            def response(self, req, res=None):
                with self._mu:
                    self.responses += 1
                    if self.responses >= total:
                        done.set()

            def response_error(self, req):
                self.response(req)

        server = _StubServer()
        pool = (ApplyShardPool(handle, num_shards, server)
                if num_shards > 0 else None)
        # Seed the store so every timed push takes the += path.
        seed_meta = KVMeta(push=True)
        seed_vals = np.zeros(keys_per_msg * val_len, np.float32)
        handle.apply_shard(seed_meta, keys,
                           _push_segs(seed_meta, keys, seed_vals))
        queue: ThreadsafeQueue = ThreadsafeQueue()

        def dispatcher():
            while True:
                item = queue.wait_and_pop()
                if item is None:
                    return
                meta, kvs = item
                if pool is not None:
                    pool.submit(meta, kvs)
                else:
                    handle(meta, kvs, server)

        def feeder(w: int):
            kvs = KVPairs(keys=keys, vals=payloads[w])
            for i in range(msgs_per_worker):
                queue.push((KVMeta(push=True, sender=9 + 2 * w,
                                   timestamp=i), kvs))

        disp = threading.Thread(target=dispatcher, daemon=True)
        disp.start()
        feeders = [threading.Thread(target=feeder, args=(w,), daemon=True)
                   for w in range(n_workers)]
        t0 = time.perf_counter()
        for t in feeders:
            t.start()
        finished = done.wait(timeout=300)
        dt = time.perf_counter() - t0
        for t in feeders:
            t.join(timeout=10)
        queue.push(None)
        disp.join(timeout=10)
        if pool is not None:
            pool.stop()
        if not finished:
            continue  # keep an earlier successful round's rate
        rate = total / max(dt, 1e-9)
        best = rate if best is None else max(best, rate)
    return best if best is not None else 0.0


def _loopback_cluster(num_workers: int, num_servers: int, ns: str,
                      env_extra: Optional[dict] = None,
                      van_type: str = "loopback") -> list:
    """Boot an in-process cluster and return its started Postoffices as
    ``[scheduler, *servers, *workers]`` — the shared fixture of the
    host-plane tests, ``kv_loopback_storm`` and the ``tools/ps*.py``
    demos.  The default transport is the loopback van;
    ``van_type="tcp"`` runs real sockets over 127.0.0.1."""
    import threading

    from .environment import Environment
    from .message import Role
    from .postoffice import Postoffice

    if van_type == "loopback":
        host, port = "lo", 42000 + os.getpid() % 1000
    else:
        from .utils.network import get_available_port

        host, port = "127.0.0.1", get_available_port()
    env_map = {
        "DMLC_NUM_WORKER": str(num_workers),
        "DMLC_NUM_SERVER": str(num_servers),
        "DMLC_PS_ROOT_URI": host,
        "DMLC_PS_ROOT_PORT": str(port),
        "DMLC_NODE_HOST": host,
        "PS_VAN_TYPE": van_type,
        "PS_LOOPBACK_NS": f"{ns}-{os.getpid()}",
    }
    if env_extra:
        env_map.update(env_extra)
    nodes = [Postoffice(Role.SCHEDULER, env=Environment(dict(env_map)))]
    nodes += [Postoffice(Role.SERVER, env=Environment(dict(env_map)))
              for _ in range(num_servers)]
    nodes += [Postoffice(Role.WORKER, env=Environment(dict(env_map)))
              for _ in range(num_workers)]
    threads = [threading.Thread(target=po.start, args=(0,), daemon=True)
               for po in nodes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    return nodes


def _teardown_cluster(nodes: list, workers: list, servers: list) -> None:
    for w in workers:
        try:
            w.stop()
        except Exception:
            pass
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    for po in nodes:
        try:
            po.van.stop()
        except Exception:
            pass


# Counters whose WINDOWED rates kv_loopback_storm reports (deltas over
# the measured storm interval — docs/observability.md).
_WINDOWED_COUNTERS = (
    "van.sent_messages", "van.recv_messages", "kv.pushes", "kv.pulls",
    "kv.server_push_requests", "kv.server_pull_requests",
    "apply.sharded_requests", "apply.global_requests",
    "qos.shed_requests", "resender.retransmits",
)


def _windowed_rates(pre: dict, post: dict, wall_s: float) -> dict:
    """``{counter: delta/wall}`` for the curated counter set — only
    counters the node actually has, negative deltas (registry reset)
    dropped."""
    out = {}
    for name in _WINDOWED_COUNTERS:
        if name not in post:
            continue
        delta = post[name] - pre.get(name, 0)
        if delta >= 0:
            out[name] = round(delta / max(wall_s, 1e-9), 2)
    return out


def _condense_snapshot(snap: dict) -> dict:
    """Registry snapshot condensed for a storm's record: counters plus
    histogram quantiles (the raw buckets stay out of the JSON)."""
    m = snap.get("metrics", snap)
    return {
        "counters": m.get("counters", {}),
        "gauges": m.get("gauges", {}),
        "histograms": {
            name: {q: h.get(q) for q in
                   ("count", "p50", "p90", "p99", "max")}
            for name, h in m.get("histograms", {}).items()
        },
        "topk": m.get("topk", {}),
    }


def kv_loopback_storm(n_workers: int = 2, n_servers: int = 2,
                      msgs_per_worker: int = 50, keys_per_msg: int = 8,
                      val_len: int = 1024, telemetry: bool = True,
                      env_extra: Optional[dict] = None) -> dict:
    """A full message-path push/pull storm over a live loopback cluster
    (real bootstrap, real wire format, real apply pool) — what the
    telemetry-overhead guard (``tests/test_bench_smoke.py``) compares
    on.

    The returned ``wall_s`` clocks ONLY the storm (bootstrap excluded);
    ``telemetry`` is the per-node snapshot of every node after the
    storm ({} when disabled), each carrying a ``windowed_per_s``
    sub-dict: counter DELTAS over the measured storm interval divided
    by the wall — true windowed rates (docs/observability.md), not the
    uptime averages that fold bootstrap time into every denominator.
    """
    from .kv.kv_app import KVServer, KVServerDefaultHandle, KVWorker

    env = {"PS_TELEMETRY": "1" if telemetry else "0"}
    if env_extra:
        env.update(env_extra)
    nodes = _loopback_cluster(n_workers, n_servers, "kv-storm", env)
    servers = []
    workers = []
    try:
        for po in nodes[1:1 + n_servers]:
            srv = KVServer(0, postoffice=po)
            srv.set_request_handle(KVServerDefaultHandle())
            servers.append(srv)
        workers = [KVWorker(0, 0, postoffice=po)
                   for po in nodes[1 + n_servers:]]
        span = (1 << 64) // max(keys_per_msg, 1)
        keys = np.arange(keys_per_msg, dtype=np.uint64) * span + 3
        vals = np.ones(keys_per_msg * val_len, np.float32)
        outs = [np.zeros_like(vals) for _ in workers]
        # Pre-storm counter baseline: the windowed rates below are
        # deltas over the MEASURED interval only (bootstrap excluded).
        pre_counters = {}
        if telemetry:
            for po in nodes:
                s = po.telemetry_snapshot()
                pre_counters[f"{s['role']}{s['node_id']}"] = dict(
                    s["metrics"].get("counters", {})
                )
        t0 = time.perf_counter()
        for i in range(msgs_per_worker):
            tss = [w.push(keys, vals) for w in workers]
            for w, ts in zip(workers, tss):
                w.wait(ts)
            if i % 10 == 9:
                for w, out in zip(workers, outs):
                    w.wait(w.pull(keys, out))
        wall = time.perf_counter() - t0
        total = n_workers * msgs_per_worker
        tel = {}
        if telemetry:
            for po in nodes:
                snap = po.telemetry_snapshot()
                name = f"{snap['role']}{snap['node_id']}"
                cond = _condense_snapshot(snap)
                cond["windowed_per_s"] = _windowed_rates(
                    pre_counters.get(name, {}),
                    snap["metrics"].get("counters", {}),
                    wall,
                )
                tel[name] = cond
        return {
            "wall_s": round(wall, 4),
            "msgs": total,
            "msgs_per_s": round(total / max(wall, 1e-9), 1),
            "telemetry": tel,
        }
    finally:
        _teardown_cluster(nodes, workers, servers)


def _chunk_run(push_mb: int, n_pushes: int,
               chunk_bytes: str, extra_env: dict = None,
               mode: str = "chunk_hol") -> dict:
    """One chunked or monolithic leg: a REAL 1w+1s tcp cluster
    via the local tracker (one process per node — an in-process cluster
    would measure the shared-GIL convoy, not the transport), running
    ``--mode chunk_hol``: sequential ``push_mb``-MiB pushes from a
    background thread while the foreground samples small-pull latency
    against the same server.  The pull request rides the same per-peer
    lane and socket as the push payload, so its latency IS the
    head-of-line wait (docs/chunking.md)."""
    import re
    import subprocess
    import sys

    n_keys = 16
    cmd = [
        sys.executable, "-m", "pslite_tpu.tracker.local",
        "-n", "1", "-s", "1", "--van", "tcp", "--",
        sys.executable, "-m", "pslite_tpu.benchmark",
        "--mode", mode,
        "--len", str(push_mb * (1 << 20) // n_keys),
        "--num-keys", str(n_keys),
        "--repeat", str(n_pushes),
    ]
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PS_CHUNK_BYTES=chunk_bytes,
        # Cap kernel-buffered bytes (both legs, so the comparison is
        # fair): without it the already-accepted send/recv buffers —
        # not the lane — add a fixed term to the priority pull's wait.
        PS_TCP_SNDBUF=str(256 << 10),
        PS_TCP_RCVBUF=str(256 << 10),
        # Room for several in-flight 64 MiB reassembly buffers: blocks
        # falling out of the pool would re-pay the fresh-page fault tax
        # the pool exists to amortize (same setting both legs).
        PS_RECV_POOL_MB="512",
    )
    env.update(extra_env or {})
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    tag = mode.upper()
    m = re.search(
        tag + r" samples=(\d+) pull_p50_ms=([0-9.]+) "
        r"pull_p99_ms=([0-9.]+) push_gbps=([0-9.]+)", r.stdout,
    )
    if m is None:
        raise RuntimeError(
            f"{mode} leg produced no result (rc={r.returncode}): "
            f"{r.stdout[-500:]}\n{r.stderr[-500:]}"
        )
    return {
        "pull_samples": int(m.group(1)),
        "pull_p50_ms": float(m.group(2)),
        "pull_p99_ms": float(m.group(3)),
        "push_gbps": float(m.group(4)),
    }


def _dlrm_run(n_pulls: int, cache: bool) -> dict:
    """One leg of the DLRM Zipf serving storm (real 1w+1s tcp cluster,
    ``--mode dlrm_serve``), hot cache on or off."""
    import re
    import subprocess
    import sys

    cmd = [
        sys.executable, "-m", "pslite_tpu.tracker.local",
        "-n", "1", "-s", "1", "--van", "tcp", "--",
        sys.executable, "-m", "pslite_tpu.benchmark",
        "--mode", "dlrm_serve", "--len", "1024",
        "--repeat", str(n_pulls),
    ]
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PS_HOT_CACHE="1" if cache else "0",
        PS_TENANTS="serve:8,train:1",
    )
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    m = re.search(
        r"DLRM_SERVE samples=(\d+) pull_p50_ms=([0-9.]+) "
        r"pull_p99_ms=([0-9.]+) hit_rate=([0-9.]+) exact=True",
        r.stdout)
    if m is None:
        raise RuntimeError(
            f"dlrm_serve leg produced no result (rc={r.returncode}): "
            f"{r.stdout[-600:]}\n{r.stderr[-600:]}"
        )
    return {
        "samples": int(m.group(1)),
        "pull_p50_ms": float(m.group(2)),
        "pull_p99_ms": float(m.group(3)),
        "hit_rate": float(m.group(4)),
    }


def admission_probe(n_pushes: int = 64, limit: int = 4) -> dict:
    """Deterministic admission-control demonstration over an
    in-process loopback cluster (docs/qos.md): a bulk tenant floods a
    tiny-limit server with non-waited pushes; every wait() completes
    fast — applied or OverloadError, never a hang — and the store ends
    bit-exact at (applied x payload)."""
    import numpy as np

    from .kv.kv_app import (KVServer, KVServerDefaultHandle, KVWorker,
                            OverloadError)

    env = {"PS_TENANTS": "serve:8,train:1",
           "PS_TENANT_QUEUE_LIMIT": str(limit)}
    nodes = _loopback_cluster(1, 1, ns=f"mt-admit-{os.getpid()}",
                              env_extra=env)
    sched, srv_po, w_po = nodes
    servers, workers = [], []
    t0 = time.perf_counter()
    try:
        srv = KVServer(0, postoffice=srv_po)
        srv.set_request_handle(KVServerDefaultHandle())
        servers.append(srv)
        w = KVWorker(0, 0, postoffice=w_po)
        workers.append(w)
        keys = np.arange(8, dtype=np.uint64)
        # Small MONOLITHIC pushes (below PS_CHUNK_BYTES): each is one
        # apply-pool pending, so a fast burst outruns the shard
        # threads and the tenant's bounded queue trips — the shed
        # path under test.
        vals = np.ones(8 * 1024, np.float32)
        tss = [w.push(keys, vals, tenant="train")
               for _ in range(n_pushes)]
        applied = shed = 0
        for ts in tss:
            try:
                w.wait(ts)
                applied += 1
            except OverloadError:
                shed += 1
        out = np.zeros_like(vals)
        w.wait(w.pull(keys, out, tenant="train"))
        exact = bool(np.all(out == np.float32(applied)))
    finally:
        _teardown_cluster(nodes, workers, servers)
    return {
        "offered": n_pushes,
        "applied": applied,
        "shed": shed,
        "store_exact": exact,
        "wall_s": round(time.perf_counter() - t0, 2),
    }


def _small_op_run(secs: float, batch: bool) -> dict:
    """One batched or unbatched small-op leg: a REAL 1w+1s tcp
    cluster (one process per node) running ``--mode small_op_storm``.
    The batched leg runs the combiner tuned for 4 KiB ops (256 KiB
    frame cap ~= 64-op frames); the baseline leg is ``PS_BATCH_BYTES=0``
    — frame-for-frame the pre-batching build."""
    import re
    import subprocess
    import sys

    cmd = [
        sys.executable, "-m", "pslite_tpu.tracker.local",
        "-n", "1", "-s", "1", "--van", "tcp", "--",
        sys.executable, "-m", "pslite_tpu.benchmark",
        "--mode", "small_op_storm", "--repeat", "1",
    ]
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PS_SOB_SECONDS=str(secs),
    )
    if batch:
        env.update(
            PS_BATCH_BYTES=str(256 << 10),
            PS_BATCH_MIN_OPS="256",
            PS_BATCH_HOLD_US="12000",
        )
    else:
        env["PS_BATCH_BYTES"] = "0"
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    m = re.search(
        r"SMALL_OP ops=(\d+) secs=([0-9.]+) msgs_per_s=([0-9.]+) "
        r"p50_ms=([0-9.]+) p99_ms=([0-9.]+) ops_per_frame=([0-9.]+) "
        r"store_exact=(True|False)", r.stdout)
    if m is None:
        raise RuntimeError(
            f"small_op leg produced no result (rc={r.returncode}): "
            f"{r.stdout[-600:]}\n{r.stderr[-600:]}"
        )
    return {
        "ops": int(m.group(1)),
        "msgs_per_s": float(m.group(3)),
        "p50_ms": float(m.group(4)),
        "p99_ms": float(m.group(5)),
        "ops_per_frame": float(m.group(6)),
        "store_exact": m.group(7) == "True",
    }


def _serving_fanin_run(secs: float, batch: bool,
                       servers: int = 2) -> dict:
    """One aggregated or plain fan-in leg: a REAL 1w+Ns tcp cluster
    (one process per node) running ``--mode serving_fanin``.  The
    aggregated leg runs the op combiner + response combiner tuned for
    the 64-lookup fan-out; the baseline leg is ``PS_BATCH_BYTES=0`` —
    one frame per lookup each way, the pre-fan-in build."""
    import re
    import subprocess
    import sys

    cmd = [
        sys.executable, "-m", "pslite_tpu.tracker.local",
        "-n", "1", "-s", str(servers), "--van", "tcp", "--",
        sys.executable, "-m", "pslite_tpu.benchmark",
        "--mode", "serving_fanin", "--repeat", "1",
    ]
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PS_SF_SECONDS=str(secs),
        PS_HOT_CACHE="0",  # the acceptance runs the cache COLD
    )
    if batch:
        env.update(PS_BATCH_BYTES=str(256 << 10))
    else:
        env["PS_BATCH_BYTES"] = "0"
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=600,
                       env=env)
    m = re.search(
        r"SERVING_FANIN reqs=(\d+) secs=([0-9.]+) "
        r"reqs_per_s=([0-9.]+) fanout=(\d+) servers=(\d+) "
        r"p50_ms=([0-9.]+) p99_ms=([0-9.]+) "
        r"frames_per_req=([0-9.]+) low_p50_ms=([0-9.]+) "
        r"store_exact=(True|False)", r.stdout)
    if m is None:
        raise RuntimeError(
            f"serving_fanin leg produced no result (rc={r.returncode}): "
            f"{r.stdout[-600:]}\n{r.stderr[-600:]}"
        )
    return {
        "reqs": int(m.group(1)),
        "reqs_per_s": float(m.group(3)),
        "fanout": int(m.group(4)),
        "servers": int(m.group(5)),
        "p50_ms": float(m.group(6)),
        "p99_ms": float(m.group(7)),
        "frames_per_req": float(m.group(8)),
        "low_p50_ms": float(m.group(9)),
        "store_exact": m.group(10) == "True",
    }


def register_push_buffers(server, args) -> None:
    """ENABLE_RECV_BUFFER server side (test_benchmark.cc:268-320):
    pre-pin the receive buffer each worker's push slice lands in.  A
    sliced push carries this server's whole key block in ONE message
    identified by the slice's first key, so the buffer spans the block
    (num_keys * val_len values per worker)."""
    from . import postoffice
    from .base import WORKER_GROUP
    from .message import Role

    po = postoffice(Role.SERVER)
    r = po.get_server_key_ranges()[po.my_rank()]
    val_len = args.len // 4
    for wid in po.get_node_ids(WORKER_GROUP):
        server.register_recv_buffer(
            int(wid), int(r.begin),
            np.zeros(args.num_keys * val_len, np.float32),
        )


def _start_thread_cpu_sampler(role: str) -> None:
    """``PS_BENCH_RUSAGE=1``: a daemon thread prints per-thread CPU
    seconds (``/proc/self/task/*/stat``) every 2 s to stderr — Python
    threads resolved to their ``threading`` names via ``native_id``,
    native core threads by their pthread name (psl-io / psl-lane-N /
    psl-pipe).  Diagnostic only: attributes a leg's bottleneck thread
    without an external profiler (the bench children live in their own
    PID namespace on some CI sandboxes, so outside-in sampling can't
    see them)."""
    if not int(os.environ.get("PS_BENCH_RUSAGE", "0")):
        return
    import glob
    import sys
    import threading

    hz = os.sysconf("SC_CLK_TCK")

    def dump():
        while True:
            time.sleep(2.0)
            names = {
                t.native_id: t.name
                for t in threading.enumerate()
                if t.native_id is not None
            }
            rows = []
            for st in glob.glob("/proc/self/task/[0-9]*/stat"):
                try:
                    head, tail = open(st).read().rsplit(")", 1)
                    comm = head.split("(", 1)[1]
                    f = tail.split()
                    cpu = (int(f[11]) + int(f[12])) / hz
                    tid = int(st.split("/")[4])
                except (OSError, ValueError, IndexError):
                    continue  # thread exited mid-scan
                if cpu >= 0.05:
                    rows.append((cpu, names.get(tid, comm), tid))
            rows.sort(reverse=True)
            print(
                f"BENCH_THREAD_CPU role={role} "
                + " ".join(f"{n}:{c:.1f}s" for c, n, _ in rows[:12]),
                file=sys.stderr, flush=True,
            )

    threading.Thread(target=dump, daemon=True,
                     name="bench-rusage").start()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--len", type=int, default=1024000,
                    help="bytes per key (default 1024000)")
    ap.add_argument("--repeat", type=int, default=10)
    ap.add_argument("--mode", choices=MODES, default="push_pull")
    ap.add_argument("--num-keys", type=int,
                    default=int(os.environ.get("NUM_KEY_PER_SERVER", "40")))
    args = ap.parse_args(argv)

    from . import KVServer, finalize, start_ps

    role = os.environ["DMLC_ROLE"]
    _start_thread_cpu_sampler(role)
    start_ps()
    server = None
    if role in ("server", "joint"):
        server = KVServer(0)
        if args.mode in ("chunk_hol", "lane_goodput", "quantized_push",
                         "multi_tenant", "dlrm_serve", "serving_fanin",
                         "durable_serve", "replica_read"):
            # Shard-capable handle: the apply pool (and the streaming
            # apply of chunked pushes) is part of what these modes price.
            from .kv.kv_app import KVServerDefaultHandle

            server.set_request_handle(KVServerDefaultHandle())
        else:
            server.set_request_handle(BenchmarkHandle())
        if _recv_buffer_mode():
            register_push_buffers(server, args)
    if role in ("worker", "joint"):
        run_worker(args)
    finalize()
    if server is not None:
        if _recv_buffer_mode():
            print(f"SERVER_RECV_BUFFER_HITS {server.delivered_in_place}",
                  flush=True)
        server.stop()
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
