"""The launch taken apart (``pslite_tpu/utils/profiling.py`` ``LAUNCH``):
what an op of each kind notes beside its stages, counted and not timed (the
hot path gained two ``stamp()``s and one ``note`` an op, no more); the
account's sums against the ``launch`` stage's, over totals and over a
window; the gauges; and ``ps.kv.op``'s ``op`` under a profiler session.
"""

import numpy as np
import pytest

from pslite_tpu.utils import profiling
from pslite_tpu.utils.profiling import (ENGINE_OP, LAUNCH, LAUNCH_OPS,
                                        LAUNCHED, StageClock, launched)

SLOT = 1 << StageClock.SLOT_SHIFT        # ns
SLOT_S = SLOT / 1e9

jax = pytest.importorskip("jax")

from pslite_tpu import KVWorker  # noqa: E402
from pslite_tpu.parallel import engine as dense_module  # noqa: E402
from pslite_tpu.parallel import sparse as sparse_module  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402


@pytest.fixture()
def worker(monkeypatch):
    """A worker whose engines note into a clock of their own."""
    monkeypatch.setattr(profiling, "_clock", StageClock())
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici")
    c.start()
    yield KVWorker(0, 0, postoffice=c.workers[0])
    c.finalize()


# -- (a) what an op notes, by kind ---------------------------------------------

# A batch of one lookup is too small to route by owner (the plain bodies);
# one of four is routed on the tests' eight shards, and its program takes
# and gives the overflow count besides.
DIM, ROWS, BATCH, ROUTED = 8, 64, 1, 4
TABLES = ("t0", "t1", "t2")


def _dense(worker):
    eng = worker.engine
    if "d" not in eng._buckets:
        eng.register_dense("d", np.arange(2, dtype=np.uint64), 8)
        eng.register_dense("d2", np.arange(2, dtype=np.uint64) + 10, 8)
    return eng, np.ones((eng.num_shards, 16), dtype=np.float32)


def _sparse(worker, batch=BATCH):
    eng = worker.po.van.sparse_engine
    if TABLES[0] not in eng._tables:
        for name in TABLES:
            eng.register_sparse(name, num_rows=ROWS, dim=DIM)
    W = eng.num_shards
    assert eng._routed(batch) == (batch == ROUTED)
    idx = np.tile(np.arange(batch, dtype=np.int32), (W, 1))
    return eng, idx, np.ones((W, batch, DIM), dtype=np.float32)


def _adam(worker):
    eng, g = _dense(worker)
    return eng, lambda: eng.push_pull("d", g, handle="adam:1e-3")


def _push(worker):
    eng, g = _dense(worker)
    return eng, lambda: eng.push("d2", g)


def _pull(worker):
    eng, g = _dense(worker)
    return eng, lambda: eng.pull("d2")


def _group(worker):
    eng, g = _dense(worker)
    return eng, lambda: eng.push_pull_group(["d", "d2"], [g, g])


def _replay(worker):
    eng, _ = _dense(worker)
    seq = np.ones((3, 16), dtype=np.float32)
    return eng, lambda: eng.replay("d2", seq, handle="adagrad:0.1")


def _sparse_pull(worker, batch=BATCH):
    eng, idx, _ = _sparse(worker, batch)
    return eng, lambda: eng.pull("t0", idx)


def _routed_pull(worker):
    return _sparse_pull(worker, ROUTED)


def _sparse_push(worker):
    eng, idx, g = _sparse(worker)
    return eng, lambda: eng.push("t0", idx, g)


def _row_adagrad(worker):
    eng, idx, g = _sparse(worker)
    return eng, lambda: eng.push("t1", idx, g, "row_adagrad:0.1,1e-8")


def _pull_group(worker):
    eng, idx, _ = _sparse(worker)
    return eng, lambda: eng.pull_group(TABLES, [idx] * 3)


def _push_group(worker, batch=BATCH):
    eng, idx, g = _sparse(worker, batch)
    return eng, lambda: eng.push_group(TABLES, [idx] * 3, [g] * 3)


def _routed_push_group(worker):
    return _push_group(worker, ROUTED)


# case: (the op, its kind, stamp() calls, arrays in, arrays out).  The
# stamps are the parent's 4 (3 for a dense pull, which prepares nothing)
# and the two around the jitted call.
CASES = {
    "dense.push_pull/adam": (_adam, "dense.push_pull", 6, 5, 5),
    "dense.push": (_push, "dense.push", 6, 2, 2),
    "dense.pull": (_pull, "dense.pull", 5, 1, 1),
    "dense.push_pull_group/2": (_group, "dense.push_pull", 6, 4, 4),
    "dense.replay/adagrad": (_replay, "dense.push_pull", 6, 3, 3),
    "sparse.pull": (_sparse_pull, "sparse.pull", 6, 2, 1),
    "sparse.push": (_sparse_push, "sparse.push", 6, 3, 2),
    "sparse.push/row_adagrad": (_row_adagrad, "sparse.push", 6, 6, 3),
    # (three tables of one width: one class, one result)
    "sparse.pull_group/3": (_pull_group, "sparse.pull", 6, 6, 1),
    "sparse.push_group/3": (_push_group, "sparse.push", 6, 9, 4),
    "sparse.pull/routed": (_routed_pull, "sparse.pull", 6, 3, 2),
    "sparse.push_group/3/routed": (_routed_push_group, "sparse.push", 6, 10,
                                   5),
}


def _spy(prog, calls):
    def called(*args):
        out = prog(*args)
        calls.append((len(args), len(jax.tree_util.tree_leaves(out))))
        return out

    return called


@pytest.mark.parametrize("case", list(CASES))
def test_an_op_notes_its_launch_once_and_pays_two_stamps(case, worker,
                                                         monkeypatch):
    make, kind, stamps, n_in, n_out = CASES[case]
    eng, op = make(worker)
    jax.block_until_ready(op())     # builds, binds, makes first-time state
    # Every program the engine may call from here on, behind a count of
    # what it is handed and what it returns.
    calls = []
    for key, prog in list(eng._programs.items()):
        eng._programs[key] = _spy(prog, calls)
    for bound in eng._bound.values():
        bound.prog = _spy(bound.prog, calls)
    module = dense_module if kind.startswith("dense") else sparse_module
    stamped, notes = [], []

    def stamp():
        stamped.append(1)
        return profiling.stamp()

    monkeypatch.setattr(module, "stamp", stamp)
    monkeypatch.setattr(eng, "_note", notes.append)
    jax.block_until_ready(op())
    assert len(stamped) == stamps
    assert calls == [(n_in, n_out)]
    launches = [note for note in notes if note[0] == LAUNCH]
    assert len(launches) == 1
    _, t_end, call_ns, launch_ns, code = launches[0]
    assert code == launched(kind, n_in + n_out)
    # Noted before the op's ENGINE_OP, with its t_end and its launch ns.
    assert notes[-1][0] == ENGINE_OP and notes[-2] is launches[0]
    assert notes[-1][1] == t_end and notes[-1][4] == launch_ns
    assert 0 < call_ns <= launch_ns


def test_a_bound_record_holds_its_arrays(worker):
    """Worked out once at ``_bind`` from what the program takes and gives:
    the state's arrays by the handle's kind, the token or the pulled array
    but where the store is the pulled value."""
    eng, g = _dense(worker)
    for handle, zero_copy, kind, arrays in (
            (None, False, "dense.push_pull", 2 + 2),
            (None, None, "dense.push", 2 + 2),
            ("sgd_momentum:0.1,0.9", False, "dense.push_pull", 3 + 3),
            ("adam:1e-3", False, "dense.push_pull", 5 + 5)):
        bound = eng._bind("d", handle, zero_copy)
        assert bound.launched == launched(kind, arrays), (handle, zero_copy)
    sp, idx, grads = _sparse(worker)
    with sp._table_mu["t0"]:
        assert sp._bind("push", "t0", None, BATCH).launched \
            == launched("sparse.push", 3 + 2)
        assert sp._bind("push", "t0", "row_adagrad:0.1,1e-8",
                        BATCH).launched == launched("sparse.push", 6 + 3)


# -- (b), (c) the account against the stage, totals and window ------------------


def _noted(clock, t_end, kind, launch, call, arrays):
    clock.note((LAUNCH, t_end, call, launch, launched(kind, arrays)))
    clock.note((ENGINE_OP, t_end, 10, 20, launch))


def _fill(clock):
    """Slot s (8..15) holds s ops of each kind; kind k's launch is
    ``1000 * (k + 1) + s`` ns, its call 900 of that, its arrays ``k + 2``."""
    for s in range(8, 16):
        for i in range(s):
            for k, kind in enumerate(LAUNCH_OPS):
                launch = 1000 * (k + 1) + s
                _noted(clock, s * SLOT + 1000 + 10 * i + k, kind, launch,
                       launch - 100 * (k + 1), k + 2)


def _check(kinds, stage):
    assert tuple(kinds) == LAUNCH_OPS
    assert sum(kind[0] for kind in kinds.values()) == stage[1]
    assert sum(kind[1] for kind in kinds.values()) == stage[0]    # exactly
    assert all(0 <= call <= launch for _, launch, call, _ in kinds.values())


def test_the_kinds_add_up_to_the_launch_stage_exactly():
    clock = StageClock()
    _fill(clock)
    kinds = clock.launches_totals()
    _check(kinds, clock.totals()["launch"])
    ops = sum(range(8, 16))
    for k, kind in enumerate(LAUNCH_OPS):
        ns = sum(s * (1000 * (k + 1) + s) for s in range(8, 16))
        assert dict(zip(LAUNCHED, kinds[kind])) == {
            "calls": ops, "ns": ns,
            "call.ns": ns - ops * 100 * (k + 1), "arrays": ops * (k + 2)}
    # The other accounts ride where they rode.
    assert clock.grouped_totals() == (0, 0)
    assert clock.routed_totals() == (0, 0)


@pytest.mark.parametrize("lo, hi, slots", [
    (10.0 * SLOT_S, 14.0 * SLOT_S, 4),          # on the borders
    (9.5 * SLOT_S, 14.9 * SLOT_S, 4),           # the ragged ends are cut
    (10.2 * SLOT_S, 10.9 * SLOT_S, 0),          # none
])
def test_the_window_answers_after_the_fact_as_the_stages_do(lo, hi, slots):
    clock = StageClock()
    _fill(clock)
    kinds, n, seconds = clock.launches(lo, hi)
    assert n == slots and seconds == pytest.approx(slots * SLOT_S)
    if not slots:
        assert kinds == {}
        return
    stages, n_stage, _ = clock.window(lo, hi)
    assert n_stage == n
    _check(kinds, stages["launch"])
    first = -(-int(lo * 1e9) // SLOT)
    assert kinds["sparse.pull"][0] == sum(range(first, first + slots))
    assert kinds["dense.pull"][3] == 4 * kinds["dense.pull"][0]


def test_the_noop_clock_keeps_no_launches(monkeypatch):
    monkeypatch.setattr(profiling, "_clock", None)
    monkeypatch.setenv("PS_TELEMETRY", "0")
    clock = profiling.stage_clock()
    assert not isinstance(clock, StageClock)
    _noted(clock, 5 * SLOT, "dense.push", 300, 200, 4)
    assert clock.launches_totals() == {}
    assert clock.launches(0.0, 100.0) == ({}, 0, 0.0)
    monkeypatch.setattr(profiling, "_clock", None)


def test_a_live_loop_of_every_kind_adds_up(worker):
    clock = profiling.stage_clock()
    ops = [make(worker)[1] for make, *_ in CASES.values()]
    for op in ops:
        op()                                    # builds
    before, stage0 = clock.launches_totals(), clock.totals()["launch"]
    for _ in range(3):
        for op in ops:
            op()
    worker.engine.block()
    after, stage1 = clock.launches_totals(), clock.totals()["launch"]
    grown = {kind: tuple(b - a for a, b in zip(before[kind], after[kind]))
             for kind in LAUNCH_OPS}
    _check(grown, (stage1[0] - stage0[0], stage1[1] - stage0[1]))
    want = {kind: 0 for kind in LAUNCH_OPS}
    arrays = dict(want)
    for _, kind, _, n_in, n_out in CASES.values():
        want[kind] += 3
        arrays[kind] += 3 * (n_in + n_out)
    assert {kind: grown[kind][0] for kind in LAUNCH_OPS} == want
    assert {kind: grown[kind][3] for kind in LAUNCH_OPS} == arrays
    assert all(grown[kind][2] > 0 for kind in LAUNCH_OPS)


# -- (d) the gauges --------------------------------------------------------------


def test_the_registry_snapshot_carries_the_launches(worker):
    for make, *_ in CASES.values():
        make(worker)[1]()
    worker.engine.block()
    gauges = worker.po.metrics.snapshot()["gauges"]
    totals = profiling.stage_clock().launches_totals()
    for kind in LAUNCH_OPS:
        n, launch, call, arrays = totals[kind]
        assert n > 0
        assert gauges[f"engine.launch.{kind}.calls"] == n
        assert gauges[f"engine.launch.{kind}.ns"] == launch
        assert gauges[f"engine.launch.{kind}.call.ns"] == call
        assert gauges[f"engine.launch.{kind}.arrays"] == arrays
    assert sum(gauges[f"engine.launch.{kind}.ns"] for kind in LAUNCH_OPS) \
        == gauges["engine.stage.launch.ns"]
    # Read by tests alone since PR 25: ``StageClock.ops_bound`` is theirs.
    assert "engine.bound.misses" not in gauges


# -- (e) the span ----------------------------------------------------------------


def test_ps_kv_op_says_which_op_it_is(worker, tmp_path):
    import glob
    import os

    keys = np.arange(4, dtype=np.uint64) + 40
    worker.register_dense("span", keys, 16)
    vals = np.ones(4 * 16, dtype=np.float32)
    out = np.zeros_like(vals)
    sp, idx, grads = _sparse(worker)

    def loop():
        stamps = {
            "dense.push_pull": worker.push_pull(keys, vals, out),
            "dense.push": worker.push(keys, vals),
            "dense.pull": worker.pull(keys, out),
            "sparse.pull": worker.pull_sparse("t0", idx),
            "sparse.push": worker.push_sparse("t0", idx, grads)}
        grouped = {
            "sparse.pull": worker.pull_sparse_group(TABLES, [idx] * 3),
            "sparse.push": worker.push_sparse_group(TABLES, [idx] * 3,
                                                    [grads] * 3)}
        for ts in (*stamps.values(), *grouped.values()):
            worker.wait(ts)
        return stamps, grouped

    loop()
    with profiling.device_trace(str(tmp_path)):
        stamps, grouped = loop()
    paths = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(paths[0])
    said = {}
    for plane in profile.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == profiling.OP_SPAN:
                    stats = dict(ev.stats)
                    said[int(stats["ts"])] = stats["op"]
    want = {ts: kind for kind, ts in stamps.items()}
    want.update({ts: kind for kind, ts in grouped.items()})
    assert said == want
    assert set(said.values()) == set(LAUNCH_OPS)
