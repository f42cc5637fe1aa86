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

StageWindow = Tuple[Dict[str, Tuple[int, int]], int, float]


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
    """

    SLOT_SHIFT = 30
    KEEP = 128
    PENDING = 1 << 16

    def __init__(self):
        self._pending = collections.deque(maxlen=self.PENDING)
        self.note = self._pending.append
        self.backlog = self._pending.__len__  # notes not yet folded
        self._fold_mu = threading.Lock()
        self._totals = [0] * (2 * len(STAGES))  # ns, calls of each stage
        # slot -> the totals at its start
        self._marks: Dict[int, Tuple[int, ...]] = {}
        self._slot = -1  # the newest slot an op ended in
        self.programs_built = 0
        self.ops_bound = 0
        self.state_create_ns = 0

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

    def window(self, t_lo: float, t_hi: float) -> StageWindow:
        """``({stage: (ns, calls)}, slots, seconds)`` over the whole slots
        inside ``[t_lo, t_hi]``, seconds on the ``time.perf_counter``
        clock; 0 slots where none lies inside or a mark is gone."""
        self.fold()
        width = 1 << self.SLOT_SHIFT
        first = (int(t_lo * 1e9) + width - 1) >> self.SLOT_SHIFT
        last = int(t_hi * 1e9) >> self.SLOT_SHIFT  # the slot t_hi is in
        lo, hi = self._mark(first), self._mark(last)
        if last <= first or lo is None or hi is None:
            return {}, 0, 0.0
        return ({name: (hi[2 * i] - lo[2 * i], hi[2 * i + 1] - lo[2 * i + 1])
                 for i, name in enumerate(STAGES)},
                last - first, (last - first) * width / 1e9)

    def export(self, registry) -> None:
        """Lazily sampled gauges in a node's ``Registry``, so
        ``METRICS_PULL`` / ``tools/psmon.py`` show the stages."""
        for name in STAGES:
            registry.gauge(f"engine.stage.{name}.ns",
                           fn=lambda name=name: self.totals()[name][0])
            registry.gauge(f"engine.stage.{name}.calls",
                           fn=lambda name=name: self.totals()[name][1])
        registry.gauge("engine.programs.misses",
                       fn=lambda: self.programs_built)
        registry.gauge(
            "engine.programs.hits",
            fn=lambda: self.totals()["select"][1] - self.programs_built)
        registry.gauge("engine.bound.misses", fn=lambda: self.ops_bound)
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
