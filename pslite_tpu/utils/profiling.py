"""Per-message event tracing.

Equivalent of the reference's ``USE_PROFILING`` van tracing
(``src/van.cc:29-77, 440-457``): when ``ENABLE_PROFILING`` is set, every
push/pull send/recv appends ``key,event,timestamp_us`` to a role-tagged file
(``PROFILE_PATH`` or ``pslite_profile_van_<role>_<ts>``).  For device-side
timelines use ``jax.profiler`` traces; this file-based log covers the
control/DCN plane the same way the reference covers its NICs.

The collective (engine) path is covered from the inside instead: its
stages (``STAGES``) are stamped into the process's :class:`StageClock`
(always on, cheap, windowable after the fact), and while a
``jax.profiler`` session runs every op is wrapped in
``jax.profiler.TraceAnnotation`` spans, which land in the same
``.xplane.pb`` as the device's own timeline (:class:`device_trace`).
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, Optional, Tuple

import numpy as np

try:
    from jax.profiler import TraceAnnotation
except ImportError:  # jax-less host: the message path still runs

    class TraceAnnotation:  # type: ignore[no-redef]
        def __init__(self, name: str, **kwargs):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

        @staticmethod
        def is_enabled() -> bool:
            return False


class MonotonicAnchor:
    """One wall-clock anchor plus monotonic offsets: timestamps that
    can never go backwards within a stream (NTP steps used to corrupt
    durations) yet merge across nodes on a shared wall timeline.  THE
    single timebase of the event log (Profiler) and the distributed
    tracer (telemetry/tracing.py) — two private copies of this formula
    would skew cross-file merge alignment if they ever drifted."""

    __slots__ = ("wall_ns", "mono_ns")

    def __init__(self):
        self.wall_ns = time.time_ns()
        self.mono_ns = time.monotonic_ns()

    def now_ns(self) -> int:
        return self.wall_ns + (time.monotonic_ns() - self.mono_ns)


class Profiler:
    # Events between explicit flushes: small enough that a crash loses
    # at most a syscall's worth of tail, large enough to stay off the
    # per-event hot path.
    _FLUSH_EVERY = 256

    def __init__(self, env, role: str):
        self._enabled = bool(env.find_int("ENABLE_PROFILING", 0))
        self._fh = None
        self._mu = threading.Lock()
        self._since_flush = 0
        self._anchor = MonotonicAnchor()
        if self._enabled:
            path = env.find("PROFILE_PATH")
            if not path:
                path = f"pslite_profile_van_{role}_{int(time.time())}"
            self._fh = open(path, "a")

    @property
    def enabled(self) -> bool:
        return self._enabled

    @property
    def closed(self) -> bool:
        """True when an enabled profiler's log was closed (Van.stop);
        a restarted van re-creates the profiler instead of silently
        dropping every event of its second life."""
        return self._enabled and self._fh is None

    def _ts_us(self) -> int:
        return self._anchor.now_ns() // 1000

    def _write(self, line: str) -> None:
        with self._mu:
            if self._fh is None:
                return
            self._fh.write(line)
            self._since_flush += 1
            if self._since_flush >= self._FLUSH_EVERY:
                self._fh.flush()
                self._since_flush = 0

    def record(self, key: int, event: str, push: bool) -> None:
        if not self._enabled or self._fh is None:
            return
        kind = "push" if push else "pull"
        self._write(f"{key},{event}_{kind},{self._ts_us()}\n")

    def close(self) -> None:
        if self._fh is not None:
            with self._mu:
                if self._fh is not None:
                    self._fh.flush()
                    self._fh.close()
                    self._fh = None


# -- the engine path's stages ----------------------------------------------

# One op of the collective path (KVWorker.push_pull / push / pull /
# push_sparse / pull_sparse = _engine_op -> CollectiveEngine / SparseEngine ->
# back in _engine_op -> _engine_complete) passes through these stages, the
# first five on the issuing thread, the last two on the
# ``kv-engine-complete`` thread, or in the first ``wait`` of an op that
# carries neither ``out`` nor ``callback`` (``complete.wait`` is blocked on
# the device, not host work).  The one definition the counters, the spans
# and the benchmark's readers share.
STAGES = ("route", "select", "prep", "launch", "dispatch",
          "complete.wait", "complete.copy")
# While a ``jax.profiler`` session runs (``tracing()``), ``KVWorker`` wraps
# the issuing thread's part of an op in ``OP_SPAN`` and the completion
# thread's two stages in ``COMPLETE_SPANS``, all three with the op's
# timestamp ``ts`` and its bucket or table ``name``; jax's own
# ``PjitFunction(<program>)`` event inside ``ps.kv.op`` is the core of
# ``launch``.  No span is made otherwise: on the chip's host a ``with
# TraceAnnotation`` costs 2.7 us where it stands in an op, a Python-level
# call about as much (PERF.md, PR 24), so the hot path holds C calls only.
OP_SPAN = "ps.kv.op"
COMPLETE_SPANS = ("ps.kv.complete.wait", "ps.kv.complete.copy")
tracing = TraceAnnotation.is_enabled

stamp = time.perf_counter_ns
# What a layer notes about one op, five integers: kind, t_end, and the
# nanoseconds of its stages (a stage the op did not have: -1).
KV_OP, ENGINE_OP, COMPLETED = 0, 1, 2
_STAGES_OF = ((0, 4, None),   # KV_OP: route, dispatch, -1
              (1, 2, 3),      # ENGINE_OP: select, prep, launch
              (5, 6, None))   # COMPLETED: complete.wait, complete.copy, -1
# A fourth kind carries no stage: ``(SPARSE_ROUTE, t_end, slots, -1, -1)``,
# noted by ``SparseEngine`` once an op, BEFORE its ``ENGINE_OP`` note (the
# account pairs an ``ENGINE_OP`` note with the ``KV_OP`` note next after
# it).  ``slots`` are the rows of the batch workspace one shard's program
# works on in that op (the push combines them, the pull gathers them): W x n
# while every shard is sent every worker's batch, known from shapes when the
# op is bound.  Their sum and the ops that noted them ride behind the
# occupancy account in the totals' vector (``ROUTED``), so the slot marks
# carry them and :meth:`StageClock.routed` answers for a window.
SPARSE_ROUTE = 3
ROUTED = ("slots", "ops")
# A fifth kind, stageless as the fourth: ``(SPARSE_GROUP, t_end, tables, -1,
# -1)``, noted by ``SparseEngine`` once a GROUPED op (``pull_group`` /
# ``push_group``: several tables in one program and one launch), before its
# ``ENGINE_OP`` note too.  ``tables`` is how many the op carried, known from
# the call's own arguments.  Their sum and the ops that noted them ride behind
# ``ROUTED`` in the totals' vector (``GROUPED``); :meth:`StageClock.grouped`
# answers for a window.  A one-table op notes none.
SPARSE_GROUP = 4
GROUPED = ("tables", "ops")
# A sixth kind opens the ``launch`` stage: ``(LAUNCH, t_end, call ns, launch
# ns, arrays << LAUNCH_SHIFT | op)``, noted by both engines once an op, before
# its ``ENGINE_OP`` note too and with that note's ``t_end`` and ``launch`` ns
# (the same integer, so the op kinds' launches add up to the stage exactly).
# ``call`` is the time inside the jitted call alone, two ``stamp()``s around
# that one expression: ``launch - call`` is this repo's own Python under the
# stage (the lock, first-time state, rebinding store and state, the cut of
# the pulled array, the byte counters).  ``arrays`` is how many arrays the
# program was handed and how many it returned (a scalar placed on the device
# is one: the runtime sees an array), and ``op`` an index into ``LAUNCH_OPS``
# (a grouped or replayed op is its kind).  Both are the same in every op of
# a bound record, which holds them as one integer (:func:`launched`).  What
# the kinds sum to rides behind ``GROUPED`` in the totals' vector, ``LAUNCHED``
# a kind; :meth:`StageClock.launches` answers for a window.
LAUNCH = 5
# A seventh kind, stageless as the fourth: ``(SPARSE_POOL, t_end, bags,
# lookups, -1)``, noted by ``SparseEngine`` once a POOLED op (``pool="sum"``
# with a bag of more than one id: a lookup is the sum of a bag's rows, a
# gradient a bag's), before its ``ENGINE_OP`` note too.  ``bags`` and
# ``lookups`` are what the op carried over all workers and tables (the bags
# of one id of its other tables among them), known from shapes when the op is
# bound.  Their sums and the ops that noted them ride last in the totals'
# vector (``POOLED``); :meth:`StageClock.pooled` answers for a window.  An op
# that pools nothing notes none.
SPARSE_POOL = 6
POOLED = ("bags", "lookups", "ops")
LAUNCH_OPS = ("dense.push_pull", "dense.push", "dense.pull", "sparse.pull",
              "sparse.push")
LAUNCHED = ("calls", "ns", "call.ns", "arrays")  # the launches, their ns
LAUNCH_SHIFT = 3  # room for eight kinds under the arrays

StageWindow = Tuple[Dict[str, Tuple[int, int]], int, float]

# -- the occupancy account ---------------------------------------------------

# What the HOST knows of the device, folded from the same notes: the process
# is *starved* from the moment every op it launched has been waited for (the
# count of launched-and-not-completed ops falls to 0 at ``t_end -
# complete.copy`` of a ``COMPLETED`` note) until the next launch stage begins
# (``t_end - launch`` of an ``ENGINE_OP`` note): ``starved.prelaunch``, all
# of it the host's own Python with nothing on the device; and, counted
# apart, through that launch stage to its end: ``starved.launch``, an upper
# bound, since the device starts somewhere inside it.  The prelaunch part is
# split by what the issuing thread was in: the stages of the op that ends
# the spell (``prep``, ``select``, ``route``), the ``complete.copy`` of the
# op that began it, and ``outside`` (the caller's own time, and the
# microsecond between two layers' stamps).  It cannot hold the runtime's
# wake-up: the spell begins when ``block_until_ready`` has returned.  Where
# a caller issues many ops before it waits once, something is outstanding
# all the while by this account, whatever the device does: there
# ``ready_at_wait`` speaks, the ``COMPLETED`` notes whose ``complete.wait``
# was under ``READY_NS`` (the result was there when first waited for).
STARVED_PARTS = ("complete.copy", "route", "select", "prep", "outside")
OCCUPANCY = ("starved.prelaunch", "starved.launch",
             *("starved." + part for part in STARVED_PARTS),
             "spells", "ready_at_wait", "resets")
_OCC = 2 * len(STAGES)  # where the account begins in the totals' vector
# A wait that found its result ready: 100 us lies 15 x above
# ``block_until_ready`` on a finished array (6 us an op in the BERT cell,
# ``wait_ms`` 2.83 over 467 ops) and 2.6 x under the fastest wake-up of a
# wait that did block (0.26 ms; PERF.md, PR 36).
READY_NS = 100_000
# An op never waited for (an engine called with no ``KVWorker``; a caller
# that drops its timestamps) has no ``COMPLETED`` note: it stops counting
# once its launch ended this long before the first event of a fold.  4.3 s:
# no op of this system runs for seconds (the longest step of the
# benchmark's cells is 0.6 s), and the count is held as a stack, a
# completion taking off the newest launch, so that a surplus stays the
# oldest entry and ages out instead of looking fresh for ever.
FORGET_NS = 1 << 32
_READY, _RESETS = (_OCC + OCCUPANCY.index(name)
                   for name in ("ready_at_wait", "resets"))
_ROUTED = _OCC + len(OCCUPANCY)  # where ``ROUTED`` begins in the vector
_GROUPED = _ROUTED + len(ROUTED)  # and ``GROUPED``
_LAUNCHED = _GROUPED + len(GROUPED)  # and ``LAUNCHED``, kind after kind
_KINDS = np.arange(len(LAUNCH_OPS))
_POOLED = _LAUNCHED + len(LAUNCHED) * len(LAUNCH_OPS)  # and ``POOLED``, last
# A spell's row: OCCUPANCY up to and with ``spells``.
_PRELAUNCH, _LAUNCH = 0, 1
_COPY, _ROUTE, _SELECT, _PREP, _OUTSIDE = (
    OCCUPANCY.index("starved." + part) for part in STARVED_PARTS)
_SPELLS = OCCUPANCY.index("spells")

OccupancyWindow = Tuple[Dict[str, int], int, float]
RoutedWindow = Tuple[Tuple[int, int], int, float]
GroupedWindow = RoutedWindow
PooledWindow = Tuple[Tuple[int, int, int], int, float]
Launches = Dict[str, Tuple[int, int, int, int]]  # a kind: ``LAUNCHED``
LaunchesWindow = Tuple[Launches, int, float]
_NO_TIMES = np.empty(0, dtype=np.int64)


def launched(op: str, arrays: int) -> int:
    """The last integer of a ``LAUNCH`` note: ``arrays`` over the index of
    ``op`` in ``LAUNCH_OPS``.  Worked out where an op is bound, or once a
    module for an op that counts its own few arrays."""
    return arrays << LAUNCH_SHIFT | LAUNCH_OPS.index(op)


class StageClock:
    """Host nanoseconds and calls per stage of the engine path, for the
    whole process (host time on its threads is one resource: in-process
    clusters with several worker nodes share the clock, and tests take
    differences).

    Always on, so what an op pays is C calls only: ``stamp()`` at the
    stage borders and one :attr:`note` per layer (a bound
    ``deque.append``) of ``(kind, t_end, ns, ns, ns)``.  The notes are
    folded into the totals, a few thousand at a time with numpy, by
    whoever reads and by whoever completes a ``KVWorker`` op now and then
    (a fold in Python, note by note, cost the issuing thread more through
    the GIL than the notes themselves).  Past ``PENDING`` unfolded notes
    the oldest are dropped: only a caller of the engines alone that never
    reads can get there.

    Beside the cumulative totals the clock keeps a copy of them from the
    start of every ``1 << SLOT_SHIFT`` ns (1.07 s) slot of the
    ``time.perf_counter`` clock, the last ``KEEP``: a reader that runs
    after the fact and knows only when its window was (the benchmark's
    per-layer readers) asks :meth:`window`.  Slots begin at whole
    multiples of their width; a stage is put down to the slot its op
    ended in.

    From the same notes :meth:`fold` keeps the occupancy account
    (``OCCUPANCY``, above): its totals ride in the same vector behind the
    stages', so the slot marks carry them and :meth:`occupancy` answers
    for a window as :meth:`window` does for the stages.  It is the
    process's, as the clock is: several workers of one process share one
    device set, and "nothing outstanding" means none of them has.
    """

    SLOT_SHIFT = 30
    KEEP = 128
    PENDING = 1 << 16

    def __init__(self):
        self._pending = collections.deque(maxlen=self.PENDING)
        self.note = self._pending.append
        self.backlog = self._pending.__len__  # notes not yet folded
        self._fold_mu = threading.Lock()
        # ns, calls of each stage; then the occupancy account; then what
        # the sparse ops routed, what the grouped ones carried, the
        # launch stage by op kind, and what the pooled ops carried
        self._totals = [0] * (_POOLED + len(POOLED))
        # slot -> the totals at its start
        self._marks: Dict[int, Tuple[int, ...]] = {}
        self._slot = -1  # the newest slot an op ended in
        self.programs_built = 0
        self.ops_bound = 0
        self.state_create_ns = 0
        # The account's state from fold to fold: the launch ends of the
        # ops counted outstanding (a stack, oldest first), the start of
        # the open spell and what is left of the ``complete.copy`` that
        # began it (-1: none is open), and the newest moment accounted.
        self._open = _NO_TIMES
        self._since, self._since_copy = -1, 0
        self._horizon = 0

    def op_bound(self) -> None:
        """A dense bucket's first ``push_pull`` or ``push`` under a handle
        (or its first after a reshard or a new registration) built the
        record its other ops look up (``CollectiveEngine._bind``)."""
        self.ops_bound += 1

    def program_built(self) -> None:
        """A look into an engine's program cache missed (stage
        ``select``) and a program was built; the hits are the calls of
        ``select`` less these."""
        self.programs_built += 1

    def state_created(self, ns: int) -> None:
        """First-time optimizer state of a bucket or table (``launch``)."""
        self.state_create_ns += ns

    def fold(self) -> None:
        """Take the notes into the totals and the slot marks."""
        pending, tot = self._pending, self._totals
        with self._fold_mu:
            n = len(pending)  # notes made meanwhile wait for the next fold
            if not n:
                return
            take = pending.popleft
            rec = np.fromiter(
                itertools.chain.from_iterable(take() for _ in range(n)),
                dtype=np.int64, count=5 * n).reshape(n, 5)
            slots = rec[:, 1] >> self.SLOT_SHIFT
            # A deque at its bound has dropped its oldest notes.
            closing, spells = self._account(rec, dropped=n >= self.PENDING)
            ended_in = slots[closing]
            for slot in np.unique(slots):  # ascending: a few at most
                if slot != self._slot:
                    self._roll(int(slot))
                of_slot = rec[slots == slot]
                for kind, stages in enumerate(_STAGES_OF):
                    ns = of_slot[of_slot[:, 0] == kind, 2:]
                    for col, stage in enumerate(stages):
                        if stage is not None:
                            had = ns[:, col] >= 0
                            tot[2 * stage] += int(ns[had, col].sum())
                            tot[2 * stage + 1] += int(had.sum())
                waits = of_slot[of_slot[:, 0] == COMPLETED, 2]
                tot[_READY] += int((waits < READY_NS).sum())
                routed = of_slot[of_slot[:, 0] == SPARSE_ROUTE, 2]
                tot[_ROUTED] += int(routed.sum())
                tot[_ROUTED + 1] += len(routed)
                grouped = of_slot[of_slot[:, 0] == SPARSE_GROUP, 2]
                tot[_GROUPED] += int(grouped.sum())
                tot[_GROUPED + 1] += len(grouped)
                pooled = of_slot[of_slot[:, 0] == SPARSE_POOL, 2:4]
                if len(pooled):
                    tot[_POOLED] += int(pooled[:, 0].sum())
                    tot[_POOLED + 1] += int(pooled[:, 1].sum())
                    tot[_POOLED + 2] += len(pooled)
                calls = of_slot[of_slot[:, 0] == LAUNCH, 2:]
                if len(calls):
                    code = calls[:, 2]
                    of_kind = ((code & ((1 << LAUNCH_SHIFT) - 1))[:, None]
                               == _KINDS).astype(np.int64)
                    sums = of_kind.T @ np.stack(
                        (np.ones_like(code), calls[:, 1], calls[:, 0],
                         code >> LAUNCH_SHIFT), axis=1)  # kinds x LAUNCHED
                    for i, total in enumerate(sums.ravel().tolist()):
                        tot[_LAUNCHED + i] += total
                # A spell is put down to the slot of the op that ended it.
                if len(ended_in):
                    ended = spells[ended_in == slot].sum(axis=0).tolist()
                    for i, total in enumerate(ended):
                        tot[_OCC + i] += total

    def _account(self, rec, dropped: bool):
        """The occupancy account over one fold's notes ``rec``: the rows
        of the ``ENGINE_OP`` notes whose launch ended a starved spell, and
        for each the spell's ``[prelaunch, launch, *STARVED_PARTS, 1]``.

        Notes come from several threads and not in time order, and an
        event lies before its note (a launch begins ``launch`` ns before
        its op's ``t_end``), so the events are sorted here, and the
        account's clock never runs backwards: an event older than what
        the folds before have accounted (a ``COMPLETED`` note of the
        ``kv-engine-complete`` thread whose copy outlasted the next op's
        launch) takes effect at that horizon, or, while a spell is open,
        at its start.  ``dropped`` (notes lost past ``PENDING``): the
        account starts anew, with nothing outstanding and no spell open,
        and counts that in ``resets``; it does not drift."""
        if dropped:
            self._open = _NO_TIMES
            self._since = -1
            self._totals[_RESETS] += 1
        eng = np.flatnonzero(rec[:, 0] == ENGINE_OP)
        done = np.flatnonzero(rec[:, 0] == COMPLETED)
        if not len(eng) and not len(done):
            return eng, np.zeros((0, _SPELLS + 1), dtype=np.int64)
        # When a launch stage began, when a result was known.
        t = np.concatenate((rec[eng, 1] - rec[eng, 4],
                            rec[done, 1] - rec[done, 3]))
        # An op never waited for is forgotten by the first fold whose
        # events begin FORGET_NS after its launch ended.  Where it was
        # the last one counted the account is as at the start: nobody
        # knows when the device fell idle, so the next launch ends no
        # spell.
        alive = self._open >= t.min() - FORGET_NS
        if not alive.all():
            self._open = self._open[alive]
        c0, since = len(self._open), self._since
        t = np.maximum(t, since if not c0 and since >= 0 else self._horizon)
        # When a launch stage ended; a completion's own moment again.
        ends = t.copy()
        ends[:len(eng)] = np.maximum(rec[eng, 1], t[:len(eng)])
        step = np.ones(len(t), dtype=np.int64)
        step[len(eng):] = -1
        # By time, at one moment the launch first (one key: half the
        # time of a lexsort).
        order = np.argsort(2 * t - (step > 0), kind="stable")
        t, step, ends = t[order], step[order], ends[order]
        count = c0 + np.cumsum(step)
        if count.min() < 0:
            # A completion with nothing counted outstanding (its launch
            # forgotten, or dropped) counts for nothing.
            count -= np.minimum.accumulate(np.minimum(count, 0))
        before = np.concatenate(((c0,), count[:-1]))
        closes = np.flatnonzero((step > 0) & (before == 0))
        opens = np.flatnonzero((step < 0) & (count == 0) & (before == 1))
        starts = t[opens]  # and what its copy still had to run from there
        copies = rec[done[order[opens] - len(eng)], 1] - starts
        if not c0:
            if since >= 0:  # the spell that was open ends in this fold
                starts = np.concatenate(((since,), starts))
                copies = np.concatenate(((self._since_copy,), copies))
            else:  # the process's first launch ends no spell
                closes = closes[1:]
        closing = eng[order[closes]]  # rows of rec
        k = len(closing)
        spells = np.zeros((k, _SPELLS + 1), dtype=np.int64)
        if k:
            spells[:, _PRELAUNCH] = left = t[closes] - starts[:k]
            spells[:, _LAUNCH] = ends[closes] - t[closes]
            route, sparse = self._route_of(rec, closing)
            select, prep = rec[closing, 2], rec[closing, 3]
            # Back from the launch: the stage next to it (a sparse op
            # runs prep, select; a dense one select, prep), the other,
            # the route, what the copy still covered; the rest is none
            # of the program's.
            rows = np.arange(k)
            for col, ns in ((np.where(sparse, _SELECT, _PREP),
                             np.where(sparse, select, prep)),
                            (np.where(sparse, _PREP, _SELECT),
                             np.where(sparse, prep, select)),
                            (_ROUTE, route), (_COPY, copies[:k])):
                took = np.minimum(np.maximum(ns, 0), left)
                spells[rows, col] = took
                left = left - took
            spells[:, _OUTSIDE] = left
            spells[:, _SPELLS] = 1
        # What stays counted: a launch stays while the count has not come
        # back under its level (a completion takes off the newest).
        if count[-1]:
            low = np.minimum.accumulate(count[::-1])[::-1]
            stays = (step > 0) & (low == count)
            self._open = np.concatenate(
                (self._open[:min(c0, int(count.min()))], ends[stays]))
        else:
            self._open = _NO_TIMES
        self._horizon = max(self._horizon, int(t[-1]))
        if len(starts) > k:
            self._since, self._since_copy = int(starts[k]), int(copies[k])
        elif len(self._open):
            self._since = -1
        return closing, spells

    @staticmethod
    def _route_of(rec, closing):
        """For the ``ENGINE_OP`` notes at rows ``closing``: the ``route``
        ns of the op's ``KV_OP`` note and whether it is a sparse op's
        (no route).  The issuing thread makes the two notes 15 us apart,
        so the op's ``KV_OP`` is the note next after its ``ENGINE_OP``,
        with a dispatch that began (``t_end - dispatch``) when the engine
        had ended; an engine called with no ``KVWorker`` has none, nor has
        an op between whose two notes another thread's note or a fold
        came: no route then (it falls to ``outside``), and the dense
        order of stages."""
        after = rec[np.minimum(closing + 1, len(rec) - 1)]
        own = (after[:, 0] == KV_OP) & (closing + 1 < len(rec)) & (
            after[:, 1] - after[:, 3] >= rec[closing, 1])
        return (np.where(own, np.maximum(after[:, 2], 0), 0),
                own & (after[:, 2] < 0))

    def _roll(self, slot: int) -> None:
        if slot < self._slot:
            return  # another thread's late record: the current slot has it
        snap = tuple(self._totals)
        first = slot if self._slot < 0 else max(self._slot + 1,
                                                slot - self.KEEP + 1)
        for s in range(first, slot + 1):
            self._marks[s] = snap
        while len(self._marks) > self.KEEP:
            self._marks.pop(next(iter(self._marks)))
        self._slot = slot

    # -- reading ----------------------------------------------------------

    def totals(self) -> Dict[str, Tuple[int, int]]:
        """``{stage: (ns, calls)}`` since the process started."""
        self.fold()
        tot = self._totals
        return {name: (tot[2 * i], tot[2 * i + 1])
                for i, name in enumerate(STAGES)}

    def _mark(self, slot: int) -> Optional[Tuple[int, ...]]:
        mark = self._marks.get(slot)
        if mark is None and slot > self._slot >= 0:
            return tuple(self._totals)  # no op has ended since it started
        return mark

    def _between(self, t_lo: float, t_hi: float):
        """The totals' growth over the whole slots inside ``[t_lo,
        t_hi]``, the slots and their seconds; 0 slots where none lies
        inside or a mark is gone."""
        self.fold()
        width = 1 << self.SLOT_SHIFT
        first = (int(t_lo * 1e9) + width - 1) >> self.SLOT_SHIFT
        last = int(t_hi * 1e9) >> self.SLOT_SHIFT  # the slot t_hi is in
        lo, hi = self._mark(first), self._mark(last)
        if last <= first or lo is None or hi is None:
            return (), 0, 0.0
        return ([b - a for a, b in zip(lo, hi)], last - first,
                (last - first) * width / 1e9)

    def window(self, t_lo: float, t_hi: float) -> StageWindow:
        """``({stage: (ns, calls)}, slots, seconds)`` over the whole slots
        inside ``[t_lo, t_hi]``, seconds on the ``time.perf_counter``
        clock; 0 slots where none lies inside or a mark is gone."""
        grown, slots, seconds = self._between(t_lo, t_hi)
        if not slots:
            return {}, 0, 0.0
        return ({name: (grown[2 * i], grown[2 * i + 1])
                 for i, name in enumerate(STAGES)}, slots, seconds)

    def occupancy_totals(self) -> Dict[str, int]:
        """The occupancy account since the process started: nanoseconds
        for the ``starved.*`` keys (the ``STARVED_PARTS`` add up to
        ``starved.prelaunch`` exactly), counts for ``spells``,
        ``ready_at_wait`` and ``resets``, and ``completed``, the
        ``COMPLETED`` notes that ``ready_at_wait`` is a share of."""
        self.fold()
        return self._occupancy(self._totals)

    @staticmethod
    def _occupancy(vector) -> Dict[str, int]:
        account = dict(zip(OCCUPANCY, vector[_OCC:_ROUTED]))
        account["completed"] = vector[2 * STAGES.index("complete.wait") + 1]
        return account

    def occupancy(self, t_lo: float, t_hi: float) -> OccupancyWindow:
        """:meth:`occupancy_totals` over the whole slots inside ``[t_lo,
        t_hi]``, as :meth:`window` answers for the stages: ``(account,
        slots, seconds)``."""
        grown, slots, seconds = self._between(t_lo, t_hi)
        return (self._occupancy(grown) if slots else {}), slots, seconds

    def routed_totals(self) -> Tuple[int, int]:
        """``(slots, ops)`` of the sparse ops since the process started
        (``SPARSE_ROUTE``): the batch-workspace rows a shard worked on,
        summed over the ops, and the ops."""
        self.fold()
        return tuple(self._totals[_ROUTED:_GROUPED])

    def routed(self, t_lo: float, t_hi: float) -> RoutedWindow:
        """:meth:`routed_totals` over the whole slots inside ``[t_lo,
        t_hi]``, as :meth:`window` answers for the stages: ``((slots, ops),
        slots of the clock, seconds)``."""
        return self._pair_between(_ROUTED, t_lo, t_hi)

    def _pair_between(self, at: int, t_lo: float, t_hi: float, n: int = 2):
        """The ``n`` sums at ``at`` of the totals' vector (``ROUTED``,
        ``GROUPED``: a sum and its ops; ``POOLED``) over the whole slots
        inside ``[t_lo, t_hi]``."""
        grown, slots, seconds = self._between(t_lo, t_hi)
        if not slots:
            return (0,) * n, 0, 0.0
        return tuple(grown[at:at + n]), slots, seconds

    def grouped_totals(self) -> Tuple[int, int]:
        """``(tables, ops)`` of the grouped sparse ops since the process
        started (``SPARSE_GROUP``): the tables they carried, summed over the
        ops, and the ops."""
        self.fold()
        return tuple(self._totals[_GROUPED:_LAUNCHED])

    def grouped(self, t_lo: float, t_hi: float) -> GroupedWindow:
        """:meth:`grouped_totals` over the whole slots inside ``[t_lo,
        t_hi]``, as :meth:`routed` answers for the slots: ``((tables, ops),
        slots of the clock, seconds)``."""
        return self._pair_between(_GROUPED, t_lo, t_hi)

    def pooled_totals(self) -> Tuple[int, int, int]:
        """``(bags, lookups, ops)`` of the pooled sparse ops since the
        process started (``SPARSE_POOL``): the bags and the lookups they
        carried, summed over the ops, and the ops."""
        self.fold()
        return tuple(self._totals[_POOLED:_POOLED + len(POOLED)])

    def pooled(self, t_lo: float, t_hi: float) -> PooledWindow:
        """:meth:`pooled_totals` over the whole slots inside ``[t_lo,
        t_hi]``, as :meth:`routed` answers for the slots: ``((bags, lookups,
        ops), slots of the clock, seconds)``."""
        return self._pair_between(_POOLED, t_lo, t_hi, len(POOLED))

    def launches_totals(self) -> Launches:
        """``{op kind: (calls, ns, call ns, arrays)}`` since the process
        started (``LAUNCH``, ``LAUNCH_OPS``, ``LAUNCHED``): the ``launch``
        stage by the kind of op that paid it (the kinds' calls and ns add
        up to the stage's exactly), of it the time inside the jitted calls,
        and the arrays those were handed and returned."""
        self.fold()
        return self._launches(self._totals)

    @staticmethod
    def _launches(vector) -> Launches:
        n = len(LAUNCHED)
        return {op: tuple(vector[_LAUNCHED + n * k:_LAUNCHED + n * (k + 1)])
                for k, op in enumerate(LAUNCH_OPS)}

    def launches(self, t_lo: float, t_hi: float) -> LaunchesWindow:
        """:meth:`launches_totals` over the whole slots inside ``[t_lo,
        t_hi]``, as :meth:`window` answers for the stages: ``(kinds, slots,
        seconds)``."""
        grown, slots, seconds = self._between(t_lo, t_hi)
        return (self._launches(grown) if slots else {}), slots, seconds

    def export(self, registry) -> None:
        """Lazily sampled gauges in a node's ``Registry``, so
        ``METRICS_PULL`` / ``tools/psmon.py`` show the stages."""
        for name in STAGES:
            registry.gauge(f"engine.stage.{name}.ns",
                           fn=lambda name=name: self.totals()[name][0])
            registry.gauge(f"engine.stage.{name}.calls",
                           fn=lambda name=name: self.totals()[name][1])
        for name in OCCUPANCY:
            unit = ".ns" if name.startswith("starved.") else ""
            registry.gauge(f"engine.occupancy.{name}{unit}",
                           fn=lambda name=name:
                           self.occupancy_totals()[name])
        # Is the job launch-bound, in which op, and is it the runtime
        # (``call``) or this repo's Python around it.
        for op in LAUNCH_OPS:
            for i, name in enumerate(LAUNCHED):
                registry.gauge(f"engine.launch.{op}.{name}",
                               fn=lambda op=op, i=i:
                               self.launches_totals()[op][i])
        registry.gauge("engine.programs.misses",
                       fn=lambda: self.programs_built)
        registry.gauge("engine.state_create.s",
                       fn=lambda: self.state_create_ns / 1e9)


class _NullStageClock:
    """``PS_TELEMETRY=0``: a note goes nowhere, nothing is kept and
    nothing is exported."""

    note = staticmethod(tuple.__len__)  # a C call that keeps nothing

    def op_bound(self) -> None:
        pass

    def program_built(self) -> None:
        pass

    def state_created(self, ns) -> None:
        pass

    def fold(self) -> None:
        pass

    def totals(self) -> Dict[str, Tuple[int, int]]:
        return {}

    def window(self, t_lo: float, t_hi: float) -> StageWindow:
        return {}, 0, 0.0

    def occupancy_totals(self) -> Dict[str, int]:
        return {}

    def occupancy(self, t_lo: float, t_hi: float) -> OccupancyWindow:
        return {}, 0, 0.0

    def routed_totals(self) -> Tuple[int, int]:
        return 0, 0

    def routed(self, t_lo: float, t_hi: float) -> RoutedWindow:
        return (0, 0), 0, 0.0

    def grouped_totals(self) -> Tuple[int, int]:
        return 0, 0

    def grouped(self, t_lo: float, t_hi: float) -> GroupedWindow:
        return (0, 0), 0, 0.0

    def pooled_totals(self) -> Tuple[int, int, int]:
        return 0, 0, 0

    def pooled(self, t_lo: float, t_hi: float) -> PooledWindow:
        return (0, 0, 0), 0, 0.0

    def launches_totals(self) -> Launches:
        return {}

    def launches(self, t_lo: float, t_hi: float) -> LaunchesWindow:
        return {}, 0, 0.0

    def export(self, registry) -> None:
        pass


_clock_mu = threading.Lock()
_clock = None


def stage_clock():
    """The process's one :class:`StageClock`: both engines of a van and
    ``KVWorker`` note into it, and it is how a reader that is handed no
    engine finds it.  The no-op clock under ``PS_TELEMETRY=0``."""
    global _clock
    if _clock is None:
        from .. import environment

        with _clock_mu:
            if _clock is None:
                on = environment.get().find_bool("PS_TELEMETRY", True)
                _clock = StageClock() if on else _NullStageClock()
    return _clock


class device_trace:
    """Device-side timeline capture (jax.profiler / XPlane).

    The TPU counterpart of the van's per-message event log: wrap the hot
    region and open the trace in TensorBoard/XProf::

        with device_trace("/tmp/ps_trace"):
            engine.push_pull("grads", g)
            engine.block()
    """

    def __init__(self, log_dir: str):
        self._log_dir = log_dir
        self._ctx = None

    def __enter__(self):
        import jax

        self._ctx = jax.profiler.trace(self._log_dir)
        self._ctx.__enter__()
        return self

    def __exit__(self, *exc):
        return self._ctx.__exit__(*exc)
