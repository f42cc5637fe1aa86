"""The engine path's own tracing: ``StageClock`` counters, the ``ps.*``
``TraceAnnotation`` spans and the ``jax.named_scope``s of the programs
(``pslite_tpu/utils/profiling.py``, docs/observability.md "engine path").
"""

import glob
import os
import time

import numpy as np
import pytest

from pslite_tpu.utils import profiling
from pslite_tpu.utils.profiling import (COMPLETED, ENGINE_OP, KV_OP, STAGES,
                                        StageClock)

ISSUING = ("route", "select", "prep", "launch", "dispatch")
SLOT = 1 << StageClock.SLOT_SHIFT        # ns
SLOT_S = SLOT / 1e9


# -- (a) the clock alone ----------------------------------------------------


def _op(clock, t_end, ns=10):
    """One whole op ending at ``t_end`` ns, every stage ``ns`` long."""
    clock.note((KV_OP, t_end, ns, ns, -1))
    clock.note((ENGINE_OP, t_end, ns, ns, ns))
    clock.note((COMPLETED, t_end, ns, ns, -1))


def test_cumulative_sums_and_calls():
    clock = StageClock()
    clock.note((KV_OP, 1000, 3, 5, -1))
    clock.note((KV_OP, 2000, -1, 7, -1))  # a sparse op routes nothing
    clock.note((ENGINE_OP, 2000, 11, 13, 17))
    clock.note((COMPLETED, 2500, 19, 23, -1))
    assert clock.backlog() == 4
    assert clock.totals() == {
        "route": (3, 1), "select": (11, 1), "prep": (13, 1),
        "launch": (17, 1), "dispatch": (12, 2),
        "complete.wait": (19, 1), "complete.copy": (23, 1)}
    assert clock.backlog() == 0 and tuple(clock.totals()) == STAGES


@pytest.mark.parametrize("lo, hi, slots", [
    (10.0 * SLOT_S, 14.0 * SLOT_S, 4),          # on the borders
    (9.5 * SLOT_S, 14.9 * SLOT_S, 4),           # the ragged ends are cut
    (10.2 * SLOT_S, 13.1 * SLOT_S, 2),          # fewer than 3 whole slots
    (10.2 * SLOT_S, 10.9 * SLOT_S, 0),          # none
    (10.2 * SLOT_S, 11.5 * SLOT_S, 0),          # a border, no whole slot
])
def test_window_over_hand_made_stamps(lo, hi, slots):
    clock = StageClock()
    # Slot s holds s ops (s = 8..15), each stage 10 ns long.
    for s in range(8, 16):
        for k in range(s):
            _op(clock, s * SLOT + 1000 + k)
    stages, n, seconds = clock.window(lo, hi)
    assert n == slots and seconds == pytest.approx(slots * SLOT_S)
    if not slots:
        assert stages == {}
        return
    first = -(-int(lo * 1e9) // SLOT)
    ops = sum(range(first, first + slots))
    assert stages == {name: (10 * ops, ops) for name in STAGES}


def test_window_ends_in_a_slot_no_op_has_ended_in_yet():
    clock = StageClock()
    for s in (5, 6, 7):
        _op(clock, s * SLOT + 5)
    # Nothing was stamped after slot 7: the totals now are slot 8's start.
    stages, n, _ = clock.window(5 * SLOT_S, 8.5 * SLOT_S)
    assert n == 3 and stages["launch"] == (30, 3)
    # Before the first op and after the last there is nothing to read.
    assert clock.window(2 * SLOT_S, 4 * SLOT_S)[1] == 0
    assert clock.window(9 * SLOT_S, 12 * SLOT_S)[0]["launch"] == (0, 0)


def test_slots_roll_over_quiet_slots_and_prune_at_keep():
    clock = StageClock()
    _op(clock, 3 * SLOT + 1)
    _op(clock, 7 * SLOT + 1)             # slots 4..6 saw no op
    clock.fold()
    assert sorted(clock._marks) == [3, 4, 5, 6, 7]
    assert clock.window(4 * SLOT_S, 7 * SLOT_S)[0]["prep"] == (0, 0)
    assert clock.window(3 * SLOT_S, 7 * SLOT_S)[0]["prep"] == (10, 1)
    for s in range(8, 8 + 3 * StageClock.KEEP):
        _op(clock, s * SLOT + 1)
    clock.fold()
    assert len(clock._marks) == StageClock.KEEP
    newest = 7 + 3 * StageClock.KEEP
    assert max(clock._marks) == newest
    assert min(clock._marks) == newest - StageClock.KEEP + 1
    # A window that reaches behind the oldest mark reads nothing.
    assert clock.window(4 * SLOT_S, newest * SLOT_S)[1] == 0
    stages, n, _ = clock.window((newest - 100) * SLOT_S, newest * SLOT_S)
    assert n == 100 and stages["dispatch"] == (1000, 100)
    # A long pause is bounded work: one jump of a million slots.
    _op(clock, (newest + 10**6) * SLOT + 1)
    # Another thread's late record falls into the current slot.
    _op(clock, (newest + 10**6 - 1) * SLOT + 1)
    clock.fold()
    assert len(clock._marks) == StageClock.KEEP
    assert clock._slot == newest + 10**6


def test_the_completion_thread_folds_now_and_then(worker):
    clock = profiling.stage_clock()
    keys = np.arange(2, dtype=np.uint64)
    worker.register_dense("fold", keys, 8)
    vals = np.ones(16, dtype=np.float32)
    folded = 0
    for _ in range(1100):                        # its ts passes a 1024
        worker.wait(worker.push(keys, vals))
        folded += clock.backlog() <= 1
    assert 1 <= folded <= 4 and clock.backlog() < 3 * 1100


def test_unread_notes_are_bounded():
    clock = StageClock()
    for i in range(StageClock.PENDING + 10):
        clock.note((COMPLETED, 5 * SLOT + i, 1, 1, -1))
    assert clock.backlog() == StageClock.PENDING   # the oldest 10 are gone
    assert clock.totals()["complete.copy"] == (StageClock.PENDING,) * 2


def test_the_noop_clock_under_ps_telemetry_0(monkeypatch):
    monkeypatch.setattr(profiling, "_clock", None)
    monkeypatch.setenv("PS_TELEMETRY", "0")
    clock = profiling.stage_clock()
    assert clock is profiling.stage_clock()
    assert not isinstance(clock, StageClock)
    _op(clock, 5 * SLOT)
    clock.program_built()
    clock.state_created(5)
    clock.fold()
    assert clock.totals() == {}
    assert clock.window(0.0, 100.0) == ({}, 0, 0.0)

    class Registry:
        def gauge(self, *a, **kw):
            raise AssertionError("the no-op clock exports nothing")

    clock.export(Registry())
    monkeypatch.setattr(profiling, "_clock", None)
    monkeypatch.delenv("PS_TELEMETRY")
    assert isinstance(profiling.stage_clock(), StageClock)


# -- (b) and (c): a tiny dense and a tiny sparse loop through KVWorker -------

jax = pytest.importorskip("jax")

from pslite_tpu import KVWorker  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402


@pytest.fixture()
def worker():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.start()
    yield KVWorker(0, 0, postoffice=c.workers[0])
    c.finalize()


def _calls(before, after):
    return {s: after[s][1] - before[s][1] for s in STAGES}


def _ns(before, after):
    return {s: after[s][0] - before[s][0] for s in STAGES}


def _dense_loop(worker, name, rounds=3):
    """push_pull, push, pull of one bucket, ``rounds`` times: 3 ops each."""
    keys = np.arange(4, dtype=np.uint64) + 40
    worker.register_dense(name, keys, 16)
    vals = np.ones(4 * 16, dtype=np.float32)
    out = np.zeros_like(vals)
    stamps = []
    for _ in range(rounds):
        stamps.append(worker.push_pull(keys, vals, out))
        stamps.append(worker.push(keys, vals))
        stamps.append(worker.pull(keys, out))
    for ts in stamps:
        worker.wait(ts)
    return stamps


def _sparse_loop(worker, name, rounds=3):
    eng = worker.po.van.sparse_engine
    eng.register_sparse(name, num_rows=64, dim=8)
    W = eng.num_shards
    idx = np.tile(np.arange(4, dtype=np.int32), (W, 1))
    grads = np.ones((W, 4, 8), dtype=np.float32)
    out = np.zeros((W, 4, 8), dtype=np.float32)
    stamps = []
    for _ in range(rounds):
        stamps.append(worker.pull_sparse(name, idx, out=out))
        stamps.append(worker.push_sparse(name, idx, grads))
    for ts in stamps:
        worker.wait(ts)
    return stamps


def test_every_stage_counts_every_op_of_a_dense_loop(worker):
    clock = profiling.stage_clock()
    _dense_loop(worker, "warm", rounds=1)        # compile outside the loop
    before = clock.totals()
    t0 = time.perf_counter_ns()
    stamps = _dense_loop(worker, "dense")
    wall = time.perf_counter_ns() - t0
    after = clock.totals()
    assert _calls(before, after) == {s: len(stamps) for s in STAGES}
    ns = _ns(before, after)
    assert all(ns[s] > 0 for s in STAGES)        # a pull adds 0 to prep
    assert sum(ns[s] for s in ISSUING) <= wall


def test_a_sparse_loop_routes_nothing(worker):
    clock = profiling.stage_clock()
    _sparse_loop(worker, "warm", rounds=1)
    before = clock.totals()
    t0 = time.perf_counter_ns()
    stamps = _sparse_loop(worker, "emb")
    wall = time.perf_counter_ns() - t0
    after = clock.totals()
    want = {s: len(stamps) for s in STAGES}
    want["route"] = 0
    assert _calls(before, after) == want
    ns = _ns(before, after)
    assert ns["route"] == 0
    assert all(ns[s] > 0 for s in STAGES if s != "route")
    assert sum(ns[s] for s in ISSUING) <= wall


def test_grouped_and_replayed_calls_are_one_op_with_one_launch(worker):
    clock = profiling.stage_clock()
    eng = worker.engine
    for name in ("g0", "g1", "g2"):
        eng.register_dense(name, np.arange(2, dtype=np.uint64), 8)
    W = eng.num_shards
    g = np.ones((W, 16), dtype=np.float32)
    before = clock.totals()
    eng.push_pull_group(["g0", "g1", "g2"], [g, g, g])
    eng.replay("g0", np.ones((5, 16), dtype=np.float32), keep="last")
    eng.block()
    calls = _calls(before, clock.totals())
    assert calls["select"] == calls["prep"] == calls["launch"] == 2
    assert calls["route"] == calls["dispatch"] == 0    # no KVWorker in it
    payload = 16 * 4
    assert eng.push_bytes == (3 + 5) * payload
    assert eng.pull_bytes == (3 + 1) * payload


def test_program_cache_and_state_creation_counters(worker):
    clock = profiling.stage_clock()
    eng = worker.engine
    eng.register_dense("st", np.arange(2, dtype=np.uint64), 8)
    g = np.ones((eng.num_shards, 16), dtype=np.float32)
    gauges = lambda: worker.po.metrics.snapshot()["gauges"]  # noqa: E731
    before, m0, s0 = gauges(), clock.programs_built, clock.state_create_ns
    eng.push_pull("st", g, handle="adam:1e-3")
    assert clock.programs_built == m0 + 1 and clock.state_create_ns > s0
    s1 = clock.state_create_ns
    eng.push_pull("st", g, handle="adam:1e-3")
    eng.block()
    assert clock.programs_built == m0 + 1 and clock.state_create_ns == s1
    after = gauges()
    assert after["engine.programs.misses"] \
        == before["engine.programs.misses"] + 1
    assert after["engine.programs.hits"] == before["engine.programs.hits"] + 1
    assert after["engine.state_create.s"] > before["engine.state_create.s"]


def test_the_registry_snapshot_carries_the_stages(worker):
    _dense_loop(worker, "reg", rounds=1)
    gauges = worker.po.metrics.snapshot()["gauges"]
    totals = profiling.stage_clock().totals()
    for stage in STAGES:
        assert gauges[f"engine.stage.{stage}.calls"] == totals[stage][1]
        assert gauges[f"engine.stage.{stage}.ns"] == totals[stage][0]
    assert gauges["engine.stage.launch.calls"] >= 3
    assert gauges["engine.programs.misses"] >= 3
    assert gauges["engine.programs.hits"] >= 0
    assert gauges["engine.state_create.s"] >= 0.0
    assert {"compile_cache.hits", "compile_cache.misses"} <= set(gauges)


def test_an_op_the_message_path_takes_stamps_nothing(worker):
    clock = profiling.stage_clock()
    before = clock.totals()
    keys = np.array([7777], dtype=np.uint64)      # no registered bucket
    assert worker._engine_op(worker.engine.push, (np.ones(4),), keys) is None
    assert clock.totals() == before


# -- (c) the spans, in a trace ------------------------------------------------


def _events(trace_dir):
    """Every ``ps.*`` event of the trace: (name, start, end, stats)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, "the profiler wrote no trace"
    profile = jax.profiler.ProfileData.from_file(paths[0])
    found = []
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("ps."):
                    found.append((ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns,
                                  dict(ev.stats)))
    return found


def _all_events(trace_dir):
    """Every event of the trace's host planes: (name, start, end)."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(paths[0])
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in profile.planes for line in plane.lines
            for ev in line.events]


def test_spans_of_both_loops_in_one_trace(worker, tmp_path):
    assert not profiling.tracing()
    _dense_loop(worker, "warm", rounds=1)
    _sparse_loop(worker, "warmemb", rounds=1)
    with profiling.device_trace(str(tmp_path)):
        assert profiling.tracing()
        dense = _dense_loop(worker, "traced", rounds=2)
        sparse = _sparse_loop(worker, "tracedemb", rounds=2)
    events = _events(str(tmp_path))
    assert {e[0] for e in events} == {profiling.OP_SPAN,
                                      *profiling.COMPLETE_SPANS}

    ops = [e for e in events if e[0] == profiling.OP_SPAN]
    assert len(ops) == len(dense) + len(sparse)
    by_ts = {int(e[3]["ts"]): e for e in ops}
    assert sorted(by_ts) == sorted(dense + sparse)
    assert {e[3]["name"] for e in ops} == {"traced", "tracedemb"}

    # jax's own event of the jitted call, the core of ``launch``, lies
    # inside a ps.kv.op (thread lines of a CPU trace are all named
    # "python", so by interval, not by line): one for each op and one
    # more for the reshape of a sparse pull.
    calls = [e for e in _all_events(str(tmp_path))
             if e[0].startswith("PjitFunction(")]
    inside = [c for c in calls
              if any(o[1] <= c[1] and c[2] <= o[2] for o in ops)]
    assert len(inside) >= len(ops)

    # The completion thread's spans carry the ts of one ps.kv.op, start
    # after it started, and the copy follows the wait.
    wait, copy = profiling.COMPLETE_SPANS
    waits = [e for e in events if e[0] == wait]
    copies = {int(e[3]["ts"]): e for e in events if e[0] == copy}
    assert len(waits) == len(copies) == len(ops)
    for _, start, end, stats in waits:
        op = by_ts[int(stats["ts"])]
        assert stats["name"] == op[3]["name"]
        assert start >= op[1]
        assert copies[int(stats["ts"])][1] >= end


def test_no_span_is_made_while_no_session_runs(worker, monkeypatch):
    """The hot path holds C calls only: a span is a few microseconds an
    op on the chip's host (PERF.md, PR 24)."""
    made = []

    class Spy(profiling.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    import pslite_tpu.kv.kv_app as kv_app

    monkeypatch.setattr(kv_app, "TraceAnnotation", Spy)
    stamps = _dense_loop(worker, "quiet", rounds=1)
    assert stamps and not made


# -- (d) the names on the device side -----------------------------------------


def test_lowered_programs_carry_the_scopes(worker):
    import jax.numpy as jnp

    eng = worker.engine
    W = eng.num_shards
    handle = "adam:1e-3"
    prog = eng._program("push_pull_st", 16 * W, jnp.float32, handle)
    vec = jnp.zeros(16 * W, jnp.float32)
    text = prog.lower(vec, vec, vec, jnp.zeros(W, jnp.float32),
                      jnp.zeros((W, 16 * W), jnp.float32)
                      ).as_text(debug_info=True)
    for scope in ("ps.push.reduce", "ps.update", "ps.pull.gather"):
        assert scope in text, scope
    assert "adam_update" in text

    sp = worker.po.van.sparse_engine
    table = sp.register_sparse("scoped", num_rows=64, dim=8)
    idx = jnp.zeros((W, 4), jnp.int32)
    push = sp._sparse_program("push", table, 4)
    text = push.lower(sp._stores["scoped"], idx,
                      jnp.zeros((W, 4, 8), jnp.float32)
                      ).as_text(debug_info=True)
    assert "ps.sparse.push.scatter_add" in text and "ps.sparse.route" in text
    pull = sp._sparse_program("pull", table, 4)
    text = pull.lower(sp._stores["scoped"], idx).as_text(debug_info=True)
    assert "ps.sparse.pull.gather" in text and "ps.sparse.route" in text
