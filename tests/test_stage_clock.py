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


# -- (a') the occupancy account, on hand-made notes ---------------------------

MS = 1_000_000
STEP = 10 * MS
READY = profiling.READY_NS
PARTS = ["starved." + part for part in profiling.STARVED_PARTS]


def _launch(clock, begin, launch=300_000, select=20_000, prep=30_000,
            route=10_000, sparse=False, kv=True):
    """An op whose launch stage is ``[begin, begin + launch)``: the
    engine's note and, 2 us after the engine has returned, ``KVWorker``'s
    (``sparse``: no route, and the stages run prep, select)."""
    end = begin + launch
    clock.note((ENGINE_OP, end, select, prep, launch))
    if kv:
        clock.note((KV_OP, end + 17_000, -1 if sparse else route, 15_000, -1))
    return end


def _waited(clock, known, wait, copy=5_000):
    """A wait that returned at ``known`` after ``wait`` ns blocked."""
    clock.note((COMPLETED, known + copy, wait, copy, -1))


def _sparse_step(clock, t0):
    """The sparse driver's order: issue, issue, wait, wait.  The pull's
    launch begins 100 us into the step, the last wait returns 6 ms in."""
    _launch(clock, t0 + 100_000, sparse=True)
    _launch(clock, t0 + 450_000, launch=250_000, sparse=True)
    _waited(clock, t0 + 3 * MS, wait=2_400_000)
    _waited(clock, t0 + 6 * MS, wait=2_990_000, copy=7_000)


# One spell a step: from 6 ms into a step to 0.1 ms into the next.
SPARSE_SPELL = {"starved.prelaunch": 4_100_000, "starved.launch": 300_000,
                "starved.complete.copy": 7_000, "starved.route": 0,
                "starved.select": 20_000, "starved.prep": 30_000,
                "starved.outside": 4_043_000, "spells": 1}


def _times(account, n):
    return {name: n * value for name, value in account.items()}


def _subset(account, like):
    return {name: account[name] for name in like}


def test_a_closed_loop_of_two_ops_a_step_reads_its_spells_to_the_ns():
    clock, base = StageClock(), 50 * SLOT
    for s in range(9):
        _sparse_step(clock, base + s * STEP)
    account = clock.occupancy_totals()
    # The process's first launch ends no spell: 8 of 9.
    assert _subset(account, SPARSE_SPELL) == _times(SPARSE_SPELL, 8)
    assert sum(account[p] for p in PARTS) == account["starved.prelaunch"]
    # Both waits blocked, every step: none found its result ready.
    assert account["ready_at_wait"] == 0 and account["completed"] == 18
    assert account["resets"] == 0
    assert tuple(account) == profiling.OCCUPANCY + ("completed",)


def test_forty_ops_then_forty_waits_is_one_spell_a_step():
    """A dense step of many buckets: something is outstanding from the
    first launch to the last wait; only the first wait blocks."""
    clock, base = StageClock(), 50 * SLOT
    for s in range(5):
        t0 = base + s * 40 * MS
        for i in range(40):
            _launch(clock, t0 + 60_000 + i * 400_000)
        _waited(clock, t0 + 20 * MS, wait=3_700_000)
        for i in range(1, 40):
            _waited(clock, t0 + 20 * MS + i * 8_000, wait=READY - 1 if i % 2
                    else 6_000, copy=2_000)
    account = clock.occupancy_totals()
    # From the last wait (20.312 ms) to the next step's first launch.
    dense_spell = {"starved.prelaunch": 40 * MS + 60_000 - 20_312_000,
                   "starved.launch": 300_000, "starved.complete.copy": 2_000,
                   "starved.route": 10_000, "starved.select": 20_000,
                   "starved.prep": 30_000, "spells": 1}
    assert _subset(account, dense_spell) == _times(dense_spell, 4)
    assert sum(account[p] for p in PARTS) == account["starved.prelaunch"]
    assert (account["ready_at_wait"], account["completed"]) == (5 * 39, 200)
    # At READY_NS itself a wait counts as one that blocked.
    _waited(clock, base + SLOT, wait=READY)
    assert clock.occupancy_totals()["ready_at_wait"] == 5 * 39


def test_the_issuing_thread_inside_the_ops_stages_when_the_spell_begins():
    """A completion on another thread that lands inside the next op's own
    stages: the spell is cut from the launch backwards, so the stage next
    to the launch is whole, the one before it cut, and nothing is left
    for the copy or the caller.  Dense: route, select, prep, launch."""
    clock, base = StageClock(), 50 * SLOT
    _launch(clock, base)
    _waited(clock, base + STEP - 45_000, wait=MS, copy=90_000)
    _launch(clock, base + STEP)       # select began 50 us, prep 30 us before
    account = clock.occupancy_totals()
    assert _subset(account, ["starved.prelaunch", *PARTS]) == {
        "starved.prelaunch": 45_000, "starved.prep": 30_000,
        "starved.select": 15_000, "starved.route": 0,
        "starved.complete.copy": 0, "starved.outside": 0}
    # A sparse op runs prep, then select: select is the whole one.
    clock = StageClock()
    _launch(clock, base, sparse=True)
    _waited(clock, base + STEP - 45_000, wait=MS, copy=90_000)
    _launch(clock, base + STEP, sparse=True)
    account = clock.occupancy_totals()
    assert (account["starved.select"], account["starved.prep"]) \
        == (20_000, 25_000)
    # An engine called with no KVWorker notes no route and keeps the
    # dense order.
    clock = StageClock()
    _launch(clock, base, kv=False)
    _waited(clock, base + STEP - 70_000, wait=MS, copy=1_000)
    _launch(clock, base + STEP, kv=False)
    account = clock.occupancy_totals()
    assert _subset(account, PARTS) == {
        "starved.prep": 30_000, "starved.select": 20_000, "starved.route": 0,
        "starved.complete.copy": 1_000, "starved.outside": 19_000}


def _loop_notes(steps, base):
    recorder = StageClock()
    for s in range(steps):
        _sparse_step(recorder, base + s * STEP)
    return list(recorder._pending)


@pytest.mark.parametrize("every", [1, 2, 5, 6, 7, 1000])
def test_hazard_a_spell_that_lies_across_two_folds(every):
    """However the folds cut the notes (after every note, in the middle
    of an op's two notes, between the wait that begins a spell and the
    launch that ends it), the account reads what one fold reads."""
    notes = _loop_notes(9, 50 * SLOT)
    clock = StageClock()
    for i, note in enumerate(notes):
        clock.note(note)
        if (i + 1) % every == 0:
            clock.fold()
    account = clock.occupancy_totals()
    assert _subset(account, SPARSE_SPELL) == _times(SPARSE_SPELL, 8)
    assert clock.totals()["launch"] == (9 * 550_000, 18)
    assert clock._since == 50 * SLOT + 8 * STEP + 6 * MS   # the open one


def test_hazard_a_completed_note_older_than_the_fold_before():
    """The ``kv-engine-complete`` thread notes an op when its copy is
    done: its result was known (``t_end - complete.copy``) before notes
    that an earlier fold has already taken.  The account's clock does not
    run backwards: such a completion takes effect at the newest moment
    already accounted, not before it."""
    clock, base = StageClock(), 50 * SLOT
    _launch(clock, base)                              # A, with ``out``
    _launch(clock, base + 400_000)                    # A2, waited for
    _waited(clock, base + 900_000, wait=50_000)       # A2 known at 0.9 ms
    clock.fold()
    assert len(clock._open) == 1                      # A is outstanding
    # A was known at 0.7 ms; its copy into ``out`` lasted until 2 ms.
    clock.note((COMPLETED, base + 2 * MS, 200_000, 1_300_000, -1))
    _launch(clock, base + 3_500_000)
    account = clock.occupancy_totals()
    # Nothing outstanding from 0.9 ms (not from 0.7: A2 was, until then).
    assert account["starved.prelaunch"] == 3_500_000 - 900_000
    assert account["spells"] == 1
    # What the copy covered of the spell is the copy's, up to 2 ms.
    assert account["starved.complete.copy"] == 2 * MS - 900_000
    # A late LAUNCH (its op's note made after a fold that took a later
    # completion) cannot end a spell before the spell began.
    clock = StageClock()
    _launch(clock, base)
    _waited(clock, base + MS, wait=500_000)
    clock.fold()
    clock.note((COMPLETED, base + 2 * MS, 1_000, 1_000, -1))  # counts for nothing
    clock.fold()
    _launch(clock, base + 600_000, launch=900_000)    # began before 1 ms
    account = clock.occupancy_totals()
    assert (account["starved.prelaunch"], account["starved.launch"]) \
        == (0, 500_000)


def test_hazard_an_op_never_waited_for_stops_counting():
    """The rule: an ``ENGINE_OP`` with no ``COMPLETED`` to follow (an
    engine called with no ``KVWorker``, a dropped timestamp) is forgotten
    by the first fold whose notes begin ``FORGET_NS`` after its launch
    ended; until then the account reads occupied, and it never invents a
    spell from the forgetting itself."""
    clock, base = StageClock(), 50 * SLOT
    orphan_end = _launch(clock, base, kv=False)       # never waited for
    for s in range(1, 6):
        _sparse_step(clock, base + s * STEP)
    account = clock.occupancy_totals()
    assert account["spells"] == 0 and len(clock._open) == 1
    # Within FORGET_NS of it: still occupied.
    late = orphan_end + profiling.FORGET_NS - 10 * STEP
    for s in range(3):
        _sparse_step(clock, late + s * STEP)
    assert clock.occupancy_totals()["spells"] == 0
    # Past it: forgotten; the first launch after ends no spell (nobody
    # knows when the device fell idle), the steps after read as ever.
    later = orphan_end + profiling.FORGET_NS + STEP
    for s in range(4):
        _sparse_step(clock, later + s * STEP)
    account = clock.occupancy_totals()
    assert len(clock._open) == 0
    assert _subset(account, SPARSE_SPELL) == _times(SPARSE_SPELL, 3)
    # A stream of engine ops alone (no completion is ever known) reads no
    # spell, and what it keeps is bounded by FORGET_NS of launches.
    clock = StageClock()
    for i in range(3 * 4096):
        _launch(clock, base + i * (profiling.FORGET_NS >> 12), kv=False)
        if i % 1024 == 1023:
            clock.fold()
    assert clock.occupancy_totals()["spells"] == 0
    assert len(clock._open) <= 4096 + 1024


def test_hazard_notes_dropped_past_pending_start_the_account_anew():
    clock, base = StageClock(), 50 * SLOT
    _launch(clock, base, kv=False)
    clock.fold()
    assert len(clock._open) == 1
    # Its COMPLETED note is the oldest of a backlog that overflows.
    _waited(clock, base + MS, wait=500_000)
    steps = StageClock.PENDING // 4
    for s in range(1, steps + 1):
        _launch(clock, base + s * STEP, kv=False)
        _waited(clock, base + s * STEP + MS, wait=500_000)
        _launch(clock, base + s * STEP + 2 * MS, kv=False)
        _waited(clock, base + s * STEP + 3 * MS, wait=500_000)
    assert clock.backlog() == StageClock.PENDING
    account = clock.occupancy_totals()
    assert account["resets"] == 1
    # Anew: without the reset the first launch stays counted and the
    # count never falls to 0 until it is forgotten.  The first launch of
    # the backlog ends no spell, each of the others one of 1 or 7.7 ms.
    assert account["spells"] == 2 * steps - 1
    assert account["starved.prelaunch"] \
        == steps * MS + (steps - 1) * (STEP - 3 * MS)
    assert len(clock._open) == 0
    # A backlog that stays under the bound resets nothing.
    _sparse_step(clock, base + (steps + 2) * STEP)
    assert clock.occupancy_totals()["resets"] == 1


def test_hazard_several_workers_share_the_clock():
    """The account is the process's, one device set: the process is
    starved only while no worker of it has anything outstanding."""
    clock, base = StageClock(), 50 * SLOT
    _launch(clock, base)                              # worker A
    _launch(clock, base + MS)                         # worker B
    _waited(clock, base + 2 * MS, wait=MS)            # A's
    _launch(clock, base + 3 * MS)                     # A again: B's is out
    _waited(clock, base + 4 * MS, wait=500_000)       # A's
    _waited(clock, base + 5 * MS, wait=3 * MS)        # B's: nothing is out
    _launch(clock, base + 6 * MS)                     # B again
    account = clock.occupancy_totals()
    assert (account["spells"], account["starved.prelaunch"]) == (1, MS)


def test_a_completion_with_nothing_outstanding_counts_for_nothing():
    clock, base = StageClock(), 50 * SLOT
    _waited(clock, base, wait=1_000)                  # its launch unknown
    _waited(clock, base + MS, wait=1_000)
    _launch(clock, base + 2 * MS)
    _waited(clock, base + 3 * MS, wait=MS)
    _launch(clock, base + 5 * MS)
    account = clock.occupancy_totals()
    assert (account["spells"], account["starved.prelaunch"]) == (1, 2 * MS)
    assert account["ready_at_wait"] == 2 and account["completed"] == 3


def test_occupancy_over_whole_slots_answers_as_window_does():
    clock = StageClock()
    # Slot s holds s closed steps of one op (s = 8..15), 1 ms each: every
    # launch but the process's first ends a spell of 0.3 ms.
    for s in range(8, 16):
        for k in range(s):
            t0 = s * SLOT + k * MS
            _launch(clock, t0 + 300_000, launch=100_000)
            _waited(clock, t0 + 900_000, wait=400_000)
    account, n, seconds = clock.occupancy(9.5 * SLOT_S, 14.9 * SLOT_S)
    assert n == 4 and seconds == pytest.approx(4 * SLOT_S)
    steps = 10 + 11 + 12 + 13
    assert account["spells"] == account["completed"] == steps
    # Inside a slot a spell is 0.4 ms; the first of a slot reaches back
    # over what was left of the slot before.
    assert account["starved.launch"] == steps * 100_000
    assert account["starved.prelaunch"] == (steps - 4) * 400_000 + sum(
        SLOT - (s - 1) * MS + 400_000 for s in (10, 11, 12, 13))
    assert sum(account[p] for p in PARTS) == account["starved.prelaunch"]
    assert clock.occupancy(10.2 * SLOT_S, 10.9 * SLOT_S) == ({}, 0, 0.0)
    assert clock.occupancy(2 * SLOT_S, 4 * SLOT_S)[1] == 0
    # The stages are read as before, beside it.
    stages, n, _ = clock.window(9.5 * SLOT_S, 14.9 * SLOT_S)
    assert n == 4 and tuple(stages) == STAGES
    assert stages["launch"] == (steps * 100_000, steps)


def test_the_account_leaves_the_stages_as_they_were():
    """Totals, ``window()`` and the marks' stage part with the account
    beside them: what a clock without it reads (the cases above this
    section pass untouched; here, on the same notes, the vector's first
    ``2 * len(STAGES)`` entries against a sum in Python, note by note)."""
    notes = _loop_notes(40, 70 * SLOT - 13 * STEP)      # across a border
    clock = StageClock()
    for note in notes:
        clock.note(note)
    want = {name: [0, 0] for name in STAGES}
    stage_of = {KV_OP: ("route", "dispatch"),
                ENGINE_OP: ("select", "prep", "launch"),
                COMPLETED: ("complete.wait", "complete.copy")}
    for kind, _, *ns in notes:
        for name, value in zip(stage_of[kind], ns):
            if value >= 0:
                want[name][0] += value
                want[name][1] += 1
    assert clock.totals() == {k: tuple(v) for k, v in want.items()}
    assert len(clock._totals) == (2 * len(STAGES) + len(profiling.OCCUPANCY)
                                  + len(profiling.ROUTED)
                                  + len(profiling.GROUPED)
                                  + len(profiling.LAUNCHED)
                                  * len(profiling.LAUNCH_OPS)
                                  + len(profiling.POOLED))
    assert all(len(mark) == len(clock._totals)
               for mark in clock._marks.values())


def test_the_noop_clock_keeps_no_account(monkeypatch):
    monkeypatch.setattr(profiling, "_clock", None)
    monkeypatch.setenv("PS_TELEMETRY", "0")
    clock = profiling.stage_clock()
    _sparse_step(clock, 5 * SLOT)
    _sparse_step(clock, 5 * SLOT + STEP)
    assert clock.occupancy_totals() == {}
    assert clock.occupancy(0.0, 100.0) == ({}, 0, 0.0)
    monkeypatch.setattr(profiling, "_clock", None)


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


@pytest.fixture()
def fresh_worker(monkeypatch):
    """A worker whose engines note into a clock of their own: the
    process's holds whatever the tests before it left outstanding (an
    engine called alone is never waited for through ``KVWorker``)."""
    monkeypatch.setattr(profiling, "_clock", StageClock())
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.start()
    yield KVWorker(0, 0, postoffice=c.workers[0])
    c.finalize()


def _grown(before, after):
    return {name: after[name] - before[name] for name in after}


def _check_account(grown, wall):
    assert sum(grown[p] for p in PARTS) == grown["starved.prelaunch"]
    assert all(value >= 0 for value in grown.values())
    assert grown["starved.prelaunch"] + grown["starved.launch"] <= wall
    assert grown["ready_at_wait"] <= grown["completed"]
    assert grown["resets"] == 0


def test_a_live_closed_loop_of_two_ops_reads_one_spell_a_step(fresh_worker):
    """The sparse driver's step (issue, issue, wait, wait; nothing to copy
    and no callback, so each wait completes its op on the waiting
    thread): every step's first launch ends the spell that the step
    before's last wait began."""
    worker, clock = fresh_worker, profiling.stage_clock()
    eng = worker.po.van.sparse_engine
    eng.register_sparse("occ", num_rows=64, dim=8)
    W = eng.num_shards
    idx = np.tile(np.arange(4, dtype=np.int32), (W, 1))
    grads = np.ones((W, 4, 8), dtype=np.float32)

    def step():
        pulled = worker.pull_sparse("occ", idx, out=None)
        pushed = worker.push_sparse("occ", idx, grads)
        worker.wait(pulled)
        worker.wait(pushed)

    step()                                        # compiles
    before = clock.occupancy_totals()
    t0 = time.perf_counter_ns()
    for _ in range(12):
        step()
    wall = time.perf_counter_ns() - t0
    grown = _grown(before, clock.occupancy_totals())
    assert grown["spells"] == 12 and grown["completed"] == 24
    assert grown["starved.route"] == 0            # a sparse call routes nothing
    assert min(grown["starved.select"], grown["starved.prep"],
               grown["starved.complete.copy"], grown["starved.outside"]) > 0
    assert grown["starved.launch"] > 0
    _check_account(grown, wall)
    # The operator's view: the gauges are the totals.
    account = clock.occupancy_totals()
    gauges = worker.po.metrics.snapshot()["gauges"]
    for name in profiling.OCCUPANCY:
        unit = ".ns" if name.startswith("starved.") else ""
        assert gauges[f"engine.occupancy.{name}{unit}"] == account[name]
    assert sum(gauges[f"engine.occupancy.{p}.ns"] for p in PARTS) \
        == gauges["engine.occupancy.starved.prelaunch.ns"]
    assert "engine.occupancy.completed" not in gauges   # complete.wait.calls


def test_a_live_step_of_many_ops_then_their_waits_is_one_spell(fresh_worker):
    """The dense driver's step: a ``push_pull`` a bucket, then a ``wait``
    a bucket.  Something is outstanding from the first launch to the last
    wait, whatever the device does meanwhile."""
    worker, clock = fresh_worker, profiling.stage_clock()
    eng = worker.engine
    W = eng.num_shards
    buckets = []
    for b in range(10):
        keys = np.arange(2, dtype=np.uint64) + 100 * (b + 1)
        worker.register_dense(f"b{b}", keys, 8)
        buckets.append(keys)
    import jax.numpy as jnp

    g = jnp.ones((W, eng.bucket(f"b0").padded_len), jnp.float32)

    def step():
        stamps = [worker.push_pull(keys, g, None) for keys in buckets]
        for ts in stamps:
            worker.wait(ts)

    step()
    before = clock.occupancy_totals()
    t0 = time.perf_counter_ns()
    for _ in range(6):
        step()
    wall = time.perf_counter_ns() - t0
    grown = _grown(before, clock.occupancy_totals())
    assert grown["spells"] == 6 and grown["completed"] == 60
    assert grown["starved.route"] > 0
    _check_account(grown, wall)


def test_a_live_loop_completed_on_the_pool_thread_keeps_the_account_sound(
        fresh_worker):
    """Ops with ``out`` complete on ``kv-engine-complete``: its notes come
    late and from another thread.  Nothing negative, the parts add up, no
    more spells than launches."""
    worker, clock = fresh_worker, profiling.stage_clock()
    _dense_loop(worker, "warm", rounds=1)
    before = clock.occupancy_totals()
    t0 = time.perf_counter_ns()
    for i in range(5):
        stamps = _dense_loop(worker, f"pool{i}", rounds=2)
        if i % 2:
            clock.fold()
    wall = time.perf_counter_ns() - t0
    grown = _grown(before, clock.occupancy_totals())
    assert grown["completed"] == 5 * len(stamps)
    assert 5 <= grown["spells"] <= 5 * len(stamps)
    _check_account(grown, wall)


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
    # Two looks into the program cache, one miss: the hits are the calls
    # of ``select`` less the misses, which a reader of the two subtracts.
    assert after["engine.stage.select.calls"] \
        == before["engine.stage.select.calls"] + 2
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
    assert gauges["engine.stage.select.calls"] \
        >= gauges["engine.programs.misses"]
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
    # A program routed by owner takes the table's overflow count last.
    count = [sp._overflow_count("scoped")] * sp._routed(4)
    push = sp._sparse_program("push", table, 4)
    text = push.lower(sp._stores["scoped"], idx,
                      jnp.zeros((W, 4, 8), jnp.float32), *count
                      ).as_text(debug_info=True)
    assert "ps.sparse.push.scatter_add" in text and "ps.sparse.route" in text
    pull = sp._sparse_program("pull", table, 4)
    text = pull.lower(sp._stores["scoped"], idx,
                      *count).as_text(debug_info=True)
    assert "ps.sparse.pull.gather" in text and "ps.sparse.route" in text
