"""``muon:lr,mu,wd,b1,b2,eps``: the first server handle that works on whole
matrices, on a dense bucket registered with its keys' lengths AND shapes
(``lens``, ``shapes``).

Through ``KVWorker.push_pull`` / ``push`` / ``pull`` on the engine path,
against ``benchmark/muon_reference.py`` (numpy, float64 outside the
products, operands rounded by ``reference.bf16``, one matrix at a time,
imports nothing of the program), on one shard (``test_muon_owners.py`` has
the handle over four).  The
tree has wide, tall and square matrices, a 64-row router, equal shapes that
share a batched product, a key on no lane border between two matrices and
AdamW keys beside the Muon keys.

Two bfloat16 computations of one recurrence differ by roundings that flip,
so a Muon key is held to the reference within a few bfloat16 steps of its
update (``TOL``, of the root mean square of one step of the key: 0.3 in any
element, 0.03 in the root mean square, where the program reads 0.01 and a
skipped Newton-Schulz step 0.8), which a missing Nesterov term or a wrong
scale miss by far; an AdamW key is held to f32 rounding.
"""

import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker  # noqa: E402
from pslite_tpu import checkpoint  # noqa: E402
from pslite_tpu.ops import muon  # noqa: E402
from pslite_tpu.parallel.engine import (CollectiveEngine,  # noqa: E402
                                        KEY_ELEMENTWISE)
from pslite_tpu.utils import logging as log  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
import muon_flops  # noqa: E402
from muon_reference import MuonReference, parse_muon_handle  # noqa: E402

HANDLE = "muon:1e-3,0.95,0.1,0.9,0.95,1e-8"
HYPER = parse_muon_handle(HANDLE)
# name, (rows, cols), AdamW.  Between the two matrices of 96 x 256 lies a
# key of 77 values: no matrix after it starts on a lane border.
TREE = [
    ("emb.w", (40, 256), True),
    ("wide.0", (96, 256), False),
    ("gain", (1, 77), True),
    ("wide.1", (96, 256), False),
    ("tall.0", (256, 96), False),        # the same group, transposed into it
    ("square", (128, 128), False),
    ("router", (64, 256), False),
    ("tall.1", (256, 96), False),
    ("kv_b", (192, 32), False),
    ("norm", (1, 300), True),
]
NAMES = [n for n, _, _ in TREE]
SHAPES = np.array([s for _, s, _ in TREE])
ADAMW = np.array([a for _, _, a in TREE])
LENS = SHAPES[:, 0] * SHAPES[:, 1]
FLAGS = np.where(ADAMW, KEY_ELEMENTWISE, 0)
KEYS = np.arange(100, 100 + len(TREE), dtype=np.uint64)
TOTAL = int(LENS.sum())
STARTS = np.concatenate([[0], np.cumsum(LENS)])
# Of the root mean square of one step: any element, the root mean square.
TOL = (0.3, 0.03)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


def _split(flat, starts=STARTS):
    return [np.asarray(flat)[..., starts[k]:starts[k + 1]]
            for k in range(len(starts) - 1)]


def _init(rng, total=TOTAL):
    return (0.02 * rng.normal(size=total)).astype(np.float32)


def _reference(init, shapes=SHAPES, adamw=ADAMW, starts=STARTS, **kw):
    return MuonReference(_split(init, starts), shapes, adamw, **HYPER, **kw)


def _engine(shards=1, handle=HANDLE):
    return CollectiveEngine(mesh=_mesh(shards), server_handle=handle)


def _register(eng, init, name="t", **kw):
    args = dict(lens=LENS, flags=FLAGS, shapes=SHAPES, init=init)
    args.update(kw)
    return eng.register_dense(name, KEYS, **args)


def _hold(got, ref, before, adamw=ADAMW, starts=STARTS, where=""):
    """``got`` (the flat pulled vector) against the reference's parameters
    after a step that began at ``before``."""
    for k, (g, want, was) in enumerate(zip(_split(got, starts), ref.p,
                                           before)):
        diff = np.abs(np.asarray(g, np.float64) - want)
        if adamw[k]:
            assert diff.max() < 2e-7, (where, k, diff.max())
            continue
        step = np.sqrt(np.mean((want - was) ** 2))
        assert diff.max() < TOL[0] * step, (where, k, diff.max(), step)
        assert np.sqrt(np.mean(diff ** 2)) < TOL[1] * step, (
            where, k, np.sqrt(np.mean(diff ** 2)), step)


def _step(ref, grads):
    before = [p.copy() for p in ref.p]
    ref.step(grads)
    return before


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici",
                        env_extra={"PS_ICI_SERVER_HANDLE": HANDLE})
    c.start()
    server = KVServer(0, postoffice=c.servers[0])   # the message path's
    server.set_request_handle(KVServerDefaultHandle())
    yield c
    c.finalize()


def _worker(cluster, shards=1):
    po = cluster.workers[0]
    assert po.van.engine._server_handle == HANDLE    # from the environment
    po.van.engine = _engine(shards)
    po.van.engine.export(po.metrics)
    return KVWorker(0, 0, postoffice=po)


# -- the handle against the reference -----------------------------------------


@pytest.mark.parametrize("origin", ["host", "device"])
def test_muon_through_kvworker_equals_the_reference(cluster, origin):
    """Three steps from a seeded store, every kind of key in one bucket."""
    kv = _worker(cluster)
    eng = kv.engine
    rng = np.random.default_rng(3)
    init = _init(rng)
    bucket = kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS,
                               shapes=SHAPES, init=init)
    assert bucket.total_len == TOTAL and bucket.shapes.shape == (10, 2)
    ref = _reference(init)
    for step in range(3):
        g = rng.normal(size=(1, TOTAL)).astype(np.float32)
        sent = (jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))
                if origin == "device" else g)
        ts = kv.push_pull(KEYS, sent, None)
        pulled = kv.get_pulled(ts)
        kv.wait(ts)
        before = _step(ref, _split(g))
        assert pulled.shape == (TOTAL,)
        _hold(pulled, ref, before, where=step)
    kind, (mom, m, v, slot) = eng.opt_state("tree")
    assert kind == "muon"
    np.testing.assert_array_equal(np.asarray(slot), 3.0)
    # The momentum is a sum of f32 gradients: f32 rounding, no bf16 in it.
    want = np.concatenate([ref.m[k] for k in range(len(TREE))
                           if not ADAMW[k]])
    np.testing.assert_allclose(np.asarray(mom), want, atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(m), np.concatenate([ref.m[k] for k in range(len(TREE))
                                       if ADAMW[k]]), atol=1e-7)
    assert eng.push_bytes == eng.pull_bytes == 3 * 4 * TOTAL
    gauges = kv.po.metrics.snapshot()["gauges"]
    assert gauges["engine.update.muon"] == 3
    assert gauges["engine.update.muon.matrices"] == 7
    assert gauges["engine.update.muon.ns_flops"] == muon_flops.published(
        [tuple(s) for s in SHAPES[~ADAMW]])
    assert gauges["engine.pull.from_kernel"] == 0
    assert gauges["engine.update.lamb"] == 0
    out = np.zeros(TOTAL, np.float32)
    kv.wait(kv.pull(KEYS, out))
    np.testing.assert_array_equal(out, np.asarray(pulled))


@pytest.mark.parametrize("name, shape", [
    ("wide", (48, 160)), ("tall", (160, 48)), ("square", (64, 64)),
    ("router", (64, 2048)), ("one_row_short", (127, 128))])
def test_one_matrix_of_each_orientation(name, shape):
    """A bucket of one key: what the batched group does to a single
    matrix, three steps."""
    eng = _engine()
    rng = np.random.default_rng(len(name))
    n = shape[0] * shape[1]
    init = _init(rng, n)
    eng.register_dense("t", KEYS[:1], lens=[n], shapes=[shape], init=init)
    starts = np.array([0, n])
    ref = _reference(init, [shape], [False], starts)
    for step in range(3):
        g = rng.normal(size=(1, n)).astype(np.float32)
        pulled = eng.push_pull("t", g)
        before = _step(ref, [g])
        _hold(pulled, ref, before, [False], starts, where=(name, step))


def test_a_batched_group_equals_its_keys_one_at_a_time():
    """Two wide and two tall keys of one side go through ONE batched
    product; each alone in a bucket of its own gives the same values bit
    for bit (a batch is a loop over its matrices to the MXU and to the CPU
    alike), and the plan says which went together."""
    eng = _engine()
    rng = np.random.default_rng(11)
    init = _init(rng)
    _register(eng, init)
    plan = eng._muon_plan(eng.bucket("t"))
    group = next(c for c in plan.chunks if (c.m, c.n) == (96, 256))
    assert [NAMES[k] for k in group.keys] == ["wide.0", "wide.1", "tall.0",
                                             "tall.1"]
    assert group.tall == (False, False, True, True)
    assert sorted((c.m, c.n, len(c.keys)) for c in plan.chunks) == [
        (32, 192, 1), (64, 256, 1), (96, 256, 4), (128, 128, 1)]
    grads = [rng.normal(size=(1, TOTAL)).astype(np.float32)
             for _ in range(2)]
    for g in grads:
        together = np.asarray(eng.push_pull("t", g))
    for k in group.keys:
        alone = _engine()
        sl = slice(STARTS[k], STARTS[k + 1])
        alone.register_dense("k", KEYS[:1], lens=LENS[k:k + 1],
                             shapes=SHAPES[k:k + 1], init=init[sl])
        for g in grads:
            pulled = np.asarray(alone.push_pull("k", g[:, sl]))
        np.testing.assert_array_equal(pulled, together[sl])


def test_a_group_larger_than_a_chunk_is_cut_in_key_order():
    shapes = [(8, 16)] * 5 + [(16, 8)] * 2
    plan = muon.muon_plan(shapes, [False] * 7, chunk_values=3 * 128)
    assert [(c.keys, c.tall) for c in plan.chunks] == [
        ((0, 1, 2), (False,) * 3), ((3, 4, 5), (False, False, True)),
        ((6,), (True,))]
    # One key at least, whatever its size.
    assert len(muon.muon_plan([(64, 64)], [False], 10).chunks) == 1


def test_two_workers_are_summed_in_f32():
    """W = 2 on a (dp, kv) = (2, 1) mesh: one shard holds the bucket and
    the workers' rows are summed before the handle sees them."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh, axis_name="kv", worker_axis="dp",
                           server_handle=HANDLE)
    assert eng.num_workers == 2 and eng.num_shards == 1
    rng = np.random.default_rng(5)
    init = _init(rng)
    _register(eng, init)
    ref = _reference(init)
    for step in range(2):
        g = rng.normal(size=(2, TOTAL)).astype(np.float32)
        pulled = eng.push_pull("t", g)
        before = _step(ref, _split(g))
        _hold(pulled, ref, before, where=step)


def test_push_then_pull_equals_push_pull(cluster):
    kv = _worker(cluster)
    rng = np.random.default_rng(9)
    init = _init(rng)
    for name in ("a", "b"):
        kv.register_dense(name, KEYS + (0 if name == "a" else 50),
                          lens=LENS, flags=FLAGS, shapes=SHAPES, init=init)
    g = rng.normal(size=(1, TOTAL)).astype(np.float32)
    ts = kv.push_pull(KEYS, g, None)
    both = np.asarray(kv.get_pulled(ts))
    kv.wait(ts)
    kv.wait(kv.push(KEYS + 50, g))
    out = np.zeros(TOTAL, np.float32)
    kv.wait(kv.pull(KEYS + 50, out))
    np.testing.assert_array_equal(out, both)
    assert kv.engine.muon_updates == 2


def test_a_zero_gradient_decays_and_stays_finite():
    eng = _engine()
    init = _init(np.random.default_rng(2))
    _register(eng, init)
    pulled = np.asarray(eng.push_pull("t", np.zeros((1, TOTAL), np.float32)))
    assert np.isfinite(pulled).all()
    keep = np.float32(1.0 - HYPER["lr"] * HYPER["wd"])
    np.testing.assert_allclose(pulled, init * keep, rtol=1e-6)


# -- the state at its own size --------------------------------------------------


def test_the_state_is_4_bytes_a_muon_value_and_8_an_adamw_value():
    eng = _engine()
    _register(eng, _init(np.random.default_rng(1)))
    assert eng.opt_state_nbytes("t") == 0
    eng.push_pull("t", np.ones((1, TOTAL), np.float32))
    muon_values = int(LENS[~ADAMW].sum())
    adamw_values = int(LENS[ADAMW].sum())
    assert eng.opt_state_nbytes("t") == (4 * muon_values + 8 * adamw_values
                                         + 4)
    # Three slots of the store's shape would be:
    assert eng.opt_state_nbytes("t") < 3 * 4 * eng.bucket("t").padded_len
    plan = eng._muon_plan(eng.bucket("t"))
    assert plan.state_bytes == 4 * muon_values + 8 * adamw_values
    kind, (mom, m, v, slot) = eng.opt_state("t")
    assert (mom.shape, m.shape, v.shape) == ((muon_values,),
                                             (adamw_values,) , (adamw_values,))


def test_the_momentum_vector_and_its_chunks_are_inverses():
    plan = muon.muon_plan(SHAPES, ADAMW)
    vector = np.arange(plan.muon_len, dtype=np.float32)
    chunks = muon.momentum_chunks(plan, vector, np)
    assert [c.shape for c in chunks] == [
        (len(c.keys), c.m, c.n) for c in plan.chunks]
    np.testing.assert_array_equal(
        muon.momentum_vector(plan, chunks, np), vector)
    # A tall key lies transposed in its chunk.
    group = next(c for c in plan.chunks if (c.m, c.n) == (96, 256))
    i = group.keys.index(NAMES.index("tall.0"))
    lo = int(plan.mom_starts[list(plan.muon_keys).index(
        NAMES.index("tall.0"))])
    np.testing.assert_array_equal(
        chunks[plan.chunks.index(group)][i],
        vector[lo:lo + 256 * 96].reshape(256, 96).T)


@pytest.mark.parametrize("backend", ["npz", "set_opt_state", "orbax"])
def test_save_and_restore_carry_momentum_moments_and_the_step(tmp_path,
                                                              backend):
    if backend == "orbax" and not checkpoint.have_orbax():
        pytest.skip("orbax is not installed")
    eng = _engine()
    rng = np.random.default_rng(4)
    init = _init(rng)
    _register(eng, init)
    ref = _reference(init)
    grads = [rng.normal(size=(1, TOTAL)).astype(np.float32)
             for _ in range(4)]
    for g in grads[:2]:
        eng.push_pull("t", g)
        ref.step(_split(g))
    other = _engine()
    _register(other, np.zeros(TOTAL, np.float32))
    if backend == "npz":
        path = str(tmp_path / "ckpt")
        checkpoint.save_engine(eng, path)
        checkpoint.restore_engine(other, path)
    elif backend == "orbax":
        path = str(tmp_path / "ckpt_orbax")
        checkpoint.save_engine_orbax(eng, path)
        checkpoint.restore_engine_orbax(other, path)
    else:
        kind, state = eng.opt_state("t")
        other.set_store_array("t", np.asarray(eng.store_array("t"))[:TOTAL])
        other.set_opt_state("t", kind, [np.asarray(s) for s in state])
    kind, restored = other.opt_state("t")
    assert kind == "muon"
    for got, want in zip(restored, eng.opt_state("t")[1]):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for g in grads[2:]:
        want = np.asarray(eng.push_pull("t", g))
        got = np.asarray(other.push_pull("t", g))
        np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(np.asarray(other.opt_state("t")[1][3]),
                                  4.0)


def test_reshard_from_one_shard_to_four_owners_and_back():
    """1 -> 4 -> 1 shards: store, momentum, m, v and the step move intact
    between the key order of one shard and the four owners' layout."""
    eng = _engine()
    rng = np.random.default_rng(6)
    init = _init(rng)
    _register(eng, init)
    twin = _engine()
    _register(twin, init)
    g = rng.normal(size=(1, TOTAL)).astype(np.float32)
    jax.block_until_ready((eng.push_pull("t", g), twin.push_pull("t", g)))

    def same_state():
        *states, steps = zip(eng.opt_state("t")[1], twin.opt_state("t")[1])
        for got, want in states:            # momentum, m, v: key order
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # The step: a slot a shard, of whichever mesh.
        assert np.asarray(steps[0])[0] == np.asarray(steps[1])[0]
        np.testing.assert_array_equal(
            np.asarray(eng.store_array("t"))[:TOTAL],
            np.asarray(twin.store_array("t"))[:TOTAL])

    eng.reshard(Mesh(np.array(jax.devices()[1:2]), ("kv",)))
    np.testing.assert_array_equal(np.asarray(eng.push_pull("t", g)),
                                  np.asarray(twin.push_pull("t", g)))
    eng.reshard(_mesh(4))
    assert eng.num_shards == 4 and eng.bucket("t").owned is not None
    assert eng.bucket("t").padded_len == eng.bucket("t").owned.padded_len
    same_state()
    # Four workers that each push a quarter of g: the sum is g's, up to its
    # last place.
    np.testing.assert_allclose(
        np.asarray(eng.push_pull("t", np.repeat(g / 4, 4, axis=0))),
        np.asarray(twin.push_pull("t", g)), atol=2e-5)
    jax.block_until_ready(eng._stores["t"])
    eng.reshard(_mesh(1))
    assert eng.num_shards == 1 and eng.bucket("t").owned is None
    np.testing.assert_array_equal(np.asarray(eng.opt_state("t")[1][3]), 3.0)
    np.testing.assert_allclose(np.asarray(eng.push_pull("t", g)),
                               np.asarray(twin.push_pull("t", g)), atol=4e-5)
    assert np.asarray(eng.opt_state("t")[1][0]).shape == (
        int(LENS[~ADAMW].sum()),)


# -- where it cannot run it says so by name -------------------------------------


def _refused(eng, match, **kw):
    with pytest.raises(log.CheckError, match=match):
        _register(eng, None, **kw)
        eng.push_pull("t", np.ones((eng.num_workers, TOTAL), np.float32))
    assert eng.muon_updates == 0 and not eng._opt_states


@pytest.mark.parametrize("case", ["no_shapes", "a_wrong_product",
                                  "a_mixed_bucket",
                                  "a_bf16_store", "no_lens", "no_bucket"])
def test_muon_refuses_by_name_what_it_cannot_run(case):
    import jax.numpy as jnp

    if case == "no_shapes":
        _refused(_engine(), "needs each key's \\(rows, cols\\).*shapes=",
                 shapes=None)
    elif case == "a_wrong_product":
        bad = SHAPES.copy()
        bad[3] = (96, 255)
        _refused(_engine(), "rows \\* cols must be the key's len; key \\[3\\]",
                 shapes=bad)
    elif case == "a_mixed_bucket":
        _refused(_engine(), "pushed and pulled in bfloat16",
                 dtype=jnp.float32, job_dtype=jnp.bfloat16)
    elif case == "a_bf16_store":
        _refused(_engine(), "is kept in bfloat16", dtype=jnp.bfloat16)
    elif case == "no_lens":
        eng = _engine()
        eng.register_dense("flat", KEYS[:2], 64)
        with pytest.raises(log.CheckError,
                           match="needs the keys' own lengths"):
            eng.push_pull("flat", np.ones((1, 128), np.float32))
        with pytest.raises(log.CheckError, match="shapes need per-key lens"):
            eng.register_dense("u", KEYS[:2], 64, shapes=[(8, 8), (8, 8)])
    else:
        eng = _engine()
        _register(eng, None)
        with pytest.raises(log.CheckError, match="carries no bucket"):
            eng.replay("t", np.ones((2, 1, TOTAL), np.float32))
    # Nothing fell back to an element-wise update.


def test_another_handle_on_the_same_bucket_ignores_the_shapes():
    eng = _engine(handle="adam:1e-2,0.9,0.999,1e-8")
    _register(eng, None)
    pulled = np.asarray(eng.push_pull("t", np.ones((1, TOTAL), np.float32)))
    np.testing.assert_allclose(pulled, -1e-2, rtol=1e-4)
    with pytest.raises(log.CheckError, match="already has 'adam' state"):
        eng.push_pull("t", np.ones((1, TOTAL), np.float32), HANDLE)


def test_the_span_of_a_dense_op_names_muon(cluster, monkeypatch):
    from pslite_tpu.kv import kv_app

    seen = []

    class Span:
        def __init__(self, *args, **kw):
            pass

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            pass

        def set_metadata(self, **kw):
            seen.append(kw)

    kv = _worker(cluster)
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS, shapes=SHAPES)
    monkeypatch.setattr(kv_app, "tracing", lambda: True)   # a session runs
    monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
    ts = kv.push_pull(KEYS, np.ones((1, TOTAL), np.float32), None)
    kv.wait(ts)
    assert {"ts": ts, "name": "tree", "op": "dense.push_pull",
            "handle": "muon"} in seen


def test_the_program_names_its_four_parts():
    plan = muon.muon_plan(SHAPES, ADAMW)
    state = tuple(np.zeros(s, np.float32) for s in muon.state_shapes(plan))
    text = jax.jit(
        lambda store, state, agg: muon.muon_update(
            store, state, agg, STARTS, SHAPES, plan, interpret=True,
            **HYPER)
    ).lower(np.zeros(TOTAL + -TOTAL % muon.LANES, np.float32),  # a shard
            (*state, np.zeros(1, np.float32)),
            np.zeros((1, TOTAL), np.float32)).as_text(debug_info=True)
    for scope in ("ps.update.muon.momentum", "ps.update.muon.ns",
                  "ps.update.muon.apply", "ps.update.muon.adamw"):
        assert scope in text, scope


# -- a key's gradient taken from the row where it lies (PR 44) ---------------

# Every way a key can lie in the row: AdamW vectors and matrices on lane
# borders, two of them 512 values off a tile of 1,024 (behind a gain of 512
# values, as every second layer of moonlight-16b-muon lies), a tall key, two
# that share a chunk with a tall one, and behind a key of 60 values nothing
# on a lane border any more: those keep XLA's cut.
ROW_TREE = [
    ("emb.w", (8, 128), True),
    ("wide", (16, 256), False),
    ("gain", (1, 512), True),
    ("off_tile", (32, 128), False),
    ("tall", (256, 128), False),
    ("mate.0", (128, 256), False),
    ("mate.1", (256, 128), False),
    ("odd", (6, 10), False),
    ("odd_gain", (1, 77), True),
    ("late", (16, 256), False),        # the shape of "wide", off a lane
]
ROW_NAMES = [n for n, _, _ in ROW_TREE]
ROW_SHAPES = np.array([s for _, s, _ in ROW_TREE])
ROW_ADAMW = np.array([a for _, _, a in ROW_TREE])
ROW_LENS = ROW_SHAPES[:, 0] * ROW_SHAPES[:, 1]
ROW_STARTS = np.concatenate([[0], np.cumsum(ROW_LENS)])
ROW_TOTAL = int(ROW_LENS.sum())
ROW_KEYS = np.arange(300, 300 + len(ROW_TREE), dtype=np.uint64)
ROW_TAKEN = ["emb.w", "gain", "off_tile", "tall", "mate.0", "mate.1"]


def _todays_cut(row, name):
    """The parent's ``key_grad``: what XLA squeezes."""
    from jax import lax

    k = ROW_NAMES.index(name)
    return np.asarray(lax.slice(
        row, (0, int(ROW_STARTS[k])), (1, int(ROW_STARTS[k + 1]))
    ).reshape(tuple(ROW_SHAPES[k])))


def _without_the_row(plan):
    """PR 43's plan: every chunk and every AdamW key by XLA's cut on the
    way in and its ``dynamic_update_slice`` on the way out."""
    return _without_the_apply(plan)._replace(
        chunks=tuple(c._replace(row=False) for c in plan.chunks),
        row_keys=np.array([], np.int64))


def _without_the_apply(plan):
    """PR 44's plan: no key's new values are a kernel's."""
    return plan._replace(apply_keys=np.array([], np.int64))


def _shard(values):
    """A store as a shard holds it: whole lanes long, and what lies
    behind the last key is nobody's."""
    return np.pad(np.asarray(values, np.float32),
                  (0, -len(values) % 1024 + 1024), constant_values=3.0)


def _row_state(plan, rng):
    """A state that has seen steps: momenta of any sign, v positive."""
    *moms, m, v = (rng.normal(size=s).astype(np.float32)
                   for s in muon.state_shapes(plan))
    return (*moms, m, np.abs(v), np.ones(1, np.float32))


def test_the_plan_says_which_keys_leave_the_row_through_a_kernel():
    plan = muon.muon_plan(ROW_SHAPES, ROW_ADAMW)
    assert [ROW_NAMES[k] for k in plan.row_keys] == ROW_TAKEN
    assert {(c.m, c.n): c.row for c in plan.chunks} == {
        (16, 256): False,      # "late" is on no lane border: "wide" with it
        (32, 128): True, (128, 256): True, (6, 10): False}
    assert ROW_STARTS[ROW_NAMES.index("off_tile")] % 1024 == 512
    assert ROW_STARTS[ROW_NAMES.index("tall")] % 1024 == 512
    # What the chip's tiles allow of a side.
    assert muon.takes_row(512, 576, 2048, False)        # rows in sixteens
    assert not muon.takes_row(512, 8, 2048, False)
    assert muon.takes_row(0, 2048, 1408, False)         # tall: whole lanes
    assert not muon.takes_row(0, 2048, 576, False)
    assert not muon.takes_row(64, 16, 128, False)
    assert muon.takes_row(128, 1, 41943040, True)
    assert not muon.takes_row(0, 1, 128 * 2053, True)   # a prime of lanes
    # moonlight-16b-muon: every one of the 153 keys.
    cfg = _config()
    tensors = muon_flops.expand_shapes(cfg["tensors"])
    full = muon.muon_plan(
        [s for _, s in tensors],
        [muon_flops.is_adamw(n, cfg["adamw_keys"]) for n, _ in tensors])
    assert len(full.row_keys) == 153 and all(c.row for c in full.chunks)


@pytest.mark.parametrize("case", ["wide", "tall", "off_tile", "adamw",
                                  "fall_back", "two_workers"])
def test_a_key_leaves_the_row_as_todays_cut_bit_for_bit(case):
    """The kernels' read of a key against ``lax.slice(row, (0, lo), (1,
    hi)).reshape(shape)`` on one tree: a matrix as it lands in its chunk
    (the pass handed ``M, X = G, bf16(G)``), an AdamW key as a vector."""
    import jax.numpy as jnp

    plan = muon.muon_plan(ROW_SHAPES, ROW_ADAMW)
    rng = np.random.default_rng(len(case))
    row = jnp.asarray(rng.normal(size=(1, ROW_TOTAL)).astype(np.float32))

    def landed(name):
        k = ROW_NAMES.index(name)
        chunk = next(c for c in plan.chunks if k in c.keys)
        i = chunk.keys.index(k)
        mom = jnp.full((len(chunk.keys), chunk.m, chunk.n), 7.0, jnp.float32)
        got, x = muon.row_momentum(
            lambda mom, g: (g, g.astype(jnp.bfloat16)), row, mom, chunk,
            ROW_STARTS, chunk.tall[i], interpret=True)
        want = _todays_cut(row, name)
        want = want.T if chunk.tall[i] else want
        np.testing.assert_array_equal(np.asarray(got[i]), want)
        np.testing.assert_array_equal(
            np.asarray(x[i]), np.asarray(jnp.asarray(want, jnp.bfloat16)))
        # Every slot of the other orientation is as it was.
        for j, tall in enumerate(chunk.tall):
            if tall != chunk.tall[i]:
                np.testing.assert_array_equal(np.asarray(got[j]), 7.0)

    if case in ("wide", "tall", "off_tile"):
        for name in {"wide": ["mate.0"], "tall": ["tall", "mate.1"],
                     "off_tile": ["off_tile"]}[case]:
            landed(name)
    elif case == "adamw":
        for name in ("emb.w", "gain"):
            k = ROW_NAMES.index(name)
            got = muon.row_vector(row, int(ROW_STARTS[k]), int(ROW_LENS[k]),
                                  interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got), _todays_cut(row, name).reshape(-1))
    elif case == "fall_back":
        # A key of 60 values, and what lies behind it: the program with
        # the kernels and the parent's give one store and one state.
        for name in ("wide", "odd", "odd_gain", "late"):
            assert ROW_NAMES.index(name) not in plan.row_keys
        store = _shard(_init(rng, ROW_TOTAL))
        state = _row_state(plan, rng)
        got, want = (jax.jit(lambda *a, p=p: muon.muon_update(
            *a, ROW_STARTS, ROW_SHAPES, p, interpret=True, **HYPER))(
                store, state, row) for p in (plan, _without_the_row(plan)))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    else:
        # W = 2: the rows are summed first, and the sum is what is cut.
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "kv"))
        eng = CollectiveEngine(mesh=mesh, axis_name="kv", worker_axis="dp",
                               server_handle=HANDLE)
        one = _engine()
        init = _init(rng, ROW_TOTAL)
        for e in (eng, one):
            e.register_dense("t", ROW_KEYS, lens=ROW_LENS, shapes=ROW_SHAPES,
                             flags=np.where(ROW_ADAMW, KEY_ELEMENTWISE, 0),
                             init=init)
        g = rng.integers(-8, 8, size=(2, ROW_TOTAL)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(eng.push_pull("t", g)),
            np.asarray(one.push_pull("t", g.sum(0, keepdims=True))))
        assert eng.muon_row_keys == one.muon_row_keys == len(ROW_TAKEN)


@pytest.mark.parametrize("group", ["(32, 128)", "(128, 256)"])
def test_momentum_and_x_of_a_chunk_are_the_parents_bit_for_bit(group):
    """``M = mu*M + G`` and ``X = bf16(G + mu*M)`` of a chunk whose
    gradients the kernel takes from the row, against the parent's
    expression on the parent's cut of the same row."""
    import jax.numpy as jnp

    mu = HYPER["mu"]

    def momentum(mom, g):       # ``muon_update``'s, letter for letter
        mom = mu * mom + g
        x = (g + mu * mom).astype(jnp.bfloat16)
        return mom, x

    plan = muon.muon_plan(ROW_SHAPES, ROW_ADAMW)
    chunk = next(c for c in plan.chunks if str((c.m, c.n)) == group)
    rng = np.random.default_rng(12)
    row = jnp.asarray(rng.normal(size=(1, ROW_TOTAL)).astype(np.float32))
    mom0 = jnp.asarray(rng.normal(
        size=(len(chunk.keys), chunk.m, chunk.n)).astype(np.float32))
    mom, x = mom0, None
    for tall in sorted(set(chunk.tall)):
        mom, x = muon.row_momentum(momentum, row, mom, chunk, ROW_STARTS,
                                   tall, x, interpret=True)
    grads = [_todays_cut(row, ROW_NAMES[k]) for k in chunk.keys]
    want_mom, want_x = jax.jit(momentum)(mom0, jnp.stack(
        [g.T if t else g for g, t in zip(grads, chunk.tall)]))
    np.testing.assert_array_equal(np.asarray(mom), np.asarray(want_mom))
    assert x.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(x), np.asarray(want_x))


def test_the_gauge_counts_the_keys_that_left_the_row_through_a_kernel(
        cluster):
    kv = _worker(cluster)
    kv.register_dense("rows", ROW_KEYS, lens=ROW_LENS, shapes=ROW_SHAPES,
                      flags=np.where(ROW_ADAMW, KEY_ELEMENTWISE, 0))
    gauges = lambda: kv.po.metrics.snapshot()["gauges"]
    assert gauges()["engine.update.muon.row_keys"] == 0
    kv.wait(kv.push_pull(ROW_KEYS, np.ones((1, ROW_TOTAL), np.float32),
                         None))
    fell_back = {"wide", "odd", "odd_gain", "late"}
    assert gauges()["engine.update.muon.row_keys"] == len(ROW_TAKEN) \
        == len(ROW_KEYS) - len(fell_back)
    # The existing tree: "emb.w" and the first matrix of 96 x 256 lie on
    # lane borders, and that matrix shares its chunk with three that do
    # not (behind the gain of 77 values): AdamW's key alone.
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS, shapes=SHAPES)
    kv.wait(kv.push_pull(KEYS, np.ones((1, TOTAL), np.float32), None))
    assert gauges()["engine.update.muon.row_keys"] == 1
    assert gauges()["engine.update.muon.matrices"] == 7


# -- a key's new values written where they lie, once (PR 45) --------------------

# A bucket whose every key a kernel writes back: wide and tall matrices and
# AdamW vectors on a tile border of 1,024 and 512 values off it (behind a
# gain of 512 values), two chunks, one of both orientations, and a tree that
# ends 128 values into a tile.  With ``ROW_BLOCK_VALUES`` at 4,096
# (``small_blocks``) every key but the gains takes two grid steps or more.
APPLY_TREE = [
    ("emb.w", (64, 128), True),
    ("wide", (32, 256), False),          # on a tile border
    ("gain.0", (1, 512), True),
    ("off_tile", (32, 256), False),      # 512 off, the chunk of "wide"
    ("tall", (256, 128), False),         # 512 off
    ("mate", (128, 256), False),         # 512 off, the chunk of the talls
    ("gain.1", (1, 512), True),          # 512 off, in the store and in m, v
    ("tall.on", (256, 128), False),      # on a tile border
    ("norm", (1, 128), True),
]
APPLY_NAMES = [n for n, _, _ in APPLY_TREE]
APPLY_SHAPES = np.array([s for _, s, _ in APPLY_TREE])
APPLY_ADAMW = np.array([a for _, _, a in APPLY_TREE])
APPLY_LENS = APPLY_SHAPES[:, 0] * APPLY_SHAPES[:, 1]
APPLY_STARTS = np.concatenate([[0], np.cumsum(APPLY_LENS)])
APPLY_TOTAL = int(APPLY_LENS.sum())
APPLY_KEYS = np.arange(500, 500 + len(APPLY_TREE), dtype=np.uint64)
APPLY_FLAGS = np.where(APPLY_ADAMW, KEY_ELEMENTWISE, 0)


@pytest.fixture()
def small_blocks(monkeypatch):
    monkeypatch.setattr(muon, "ROW_BLOCK_VALUES", 4096)


def _apply_lies(name):
    return int(APPLY_STARTS[APPLY_NAMES.index(name)]) % 1024


def test_the_apply_tree_lies_as_the_cell_does():
    assert [_apply_lies(n) for n in ("wide", "off_tile", "tall", "mate",
                                     "gain.1", "tall.on")] == [
        0, 512, 512, 512, 512, 0]
    assert APPLY_TOTAL % 1024 == 128
    plan = muon.muon_plan(APPLY_SHAPES, APPLY_ADAMW)
    assert plan.pulls and len(plan.apply_keys) == len(APPLY_TREE)
    assert [(c.m, c.n, c.tall) for c in plan.chunks] == [
        (32, 256, (False, False)), (128, 256, (True, False, True))]


@pytest.mark.parametrize("group", ["(32, 256)", "(128, 256)"])
def test_row_apply_writes_store_and_pulled_as_todays_put_bit_for_bit(
        group, small_blocks):
    """``row_apply`` against ``put(store, k, key_values(store, k) * keep -
    scale * o_k)``, the parent's chain, on one chunk: the store bit for
    bit, every value outside the chunk's keys as it was, and the pulled
    vector the store's values where a key lies and unset elsewhere."""
    import jax.numpy as jnp
    from jax import lax

    plan = muon.muon_plan(APPLY_SHAPES, APPLY_ADAMW)
    chunk = next(c for c in plan.chunks if str((c.m, c.n)) == group)
    rows, cols = chunk.m, chunk.n
    assert rows // muon._block_rows(rows, cols, 16) >= 2        # wide
    assert cols // muon._block_rows(cols, rows, 128) >= 2       # tall
    rng = np.random.default_rng(len(group))
    store = _shard(_init(rng, APPLY_TOTAL))
    o = jnp.asarray(rng.normal(size=(len(chunk.keys), rows, cols)),
                    jnp.bfloat16)
    keep, scale = 0.9999, float(1e-3 * 0.2 * np.sqrt(cols))

    def new_values(p, o, scale):        # ``muon_update``'s
        return p * keep - scale * o

    def todays(store, o):
        for i, (k, tall) in enumerate(zip(chunk.keys, chunk.tall)):
            o_k = (o[i].T if tall else o[i]).reshape(-1).astype(jnp.float32)
            lo, hi = int(APPLY_STARTS[k]), int(APPLY_STARTS[k + 1])
            store = lax.dynamic_update_slice(
                store,
                new_values(lax.slice(store, (lo,), (hi,)), o_k, scale),
                (lo,))
        return store

    def kernels(store, o):
        pulled = None
        for tall in sorted(set(chunk.tall)):
            store, pulled = muon.row_apply(
                new_values, scale, o, store, pulled, chunk, APPLY_STARTS,
                tall,
                [i for i, t in enumerate(chunk.tall) if t == tall],
                pulled_len=APPLY_TOTAL, interpret=True)
        return store, pulled

    want = np.asarray(jax.jit(todays)(store, o))
    got, pulled = (np.asarray(x) for x in jax.jit(kernels)(store, o))
    np.testing.assert_array_equal(got, want)
    assert pulled.shape == (APPLY_TOTAL,)
    written = np.zeros(APPLY_TOTAL, bool)
    for k in chunk.keys:
        written[APPLY_STARTS[k]:APPLY_STARTS[k + 1]] = True
    np.testing.assert_array_equal(pulled[written], want[:APPLY_TOTAL][written])
    np.testing.assert_array_equal(got[:APPLY_TOTAL][~written],
                                  store[:APPLY_TOTAL][~written])
    np.testing.assert_array_equal(got[APPLY_TOTAL:], 3.0)
    assert (got[:APPLY_TOTAL][written] != store[:APPLY_TOTAL][written]).all()


@pytest.mark.parametrize("name", ["emb.w", "gain.0", "gain.1", "norm"])
def test_row_adamw_is_adamw_of_the_key_alone_bit_for_bit(name, small_blocks):
    """``row_adamw`` against the same expression jitted on the key's own
    slices: p, m and v in place at their offsets, the pulled values the
    new p, every other value of all four as it was."""
    import jax.numpy as jnp

    b1, b2, eps, keep = 0.9, 0.95, 1e-8, 0.9999

    def adamw(p, m, v, g, alpha):       # ``muon_update``'s
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p * keep - alpha * m / (jnp.sqrt(v) + eps), m, v

    plan = muon.muon_plan(APPLY_SHAPES, APPLY_ADAMW)
    k = APPLY_NAMES.index(name)
    j = list(plan.adamw_keys).index(k)
    start, n = int(APPLY_STARTS[k]), int(APPLY_LENS[k])
    lo = int(plan.adamw_starts[j])
    assert (name, n // muon._vector_tile(n)) in [
        ("emb.w", 2), ("gain.0", 1), ("gain.1", 1), ("norm", 1)]
    assert (lo % 1024 == 512) == (name == "gain.1")
    rng = np.random.default_rng(len(name))
    store = _shard(_init(rng, APPLY_TOTAL))
    m, v = (np.abs(rng.normal(size=plan.adamw_len)).astype(np.float32)
            for _ in range(2))
    g = rng.normal(size=n).astype(np.float32)
    alpha = jnp.float32(1.7e-3)
    p1, m1, v1, pulled = (np.asarray(x) for x in jax.jit(
        lambda *a: muon.row_adamw(adamw, *a, None, start, lo,
                                  pulled_len=APPLY_TOTAL, interpret=True)
    )(alpha, g, m, v, store))
    want = jax.jit(adamw)(store[start:start + n], m[lo:lo + n],
                          v[lo:lo + n], g, alpha)
    for got, was, at, new in ((p1, store, start, want[0]),
                              (m1, m, lo, want[1]), (v1, v, lo, want[2])):
        np.testing.assert_array_equal(got[at:at + n], np.asarray(new))
        np.testing.assert_array_equal(got[:at], was[:at])
        np.testing.assert_array_equal(got[at + n:], was[at + n:])
    np.testing.assert_array_equal(pulled[start:start + n], p1[start:start + n])


@pytest.mark.parametrize("case, start, shape, elementwise, lies, takes", [
    ("a_wide_key_off_a_tile", 512, (1408, 2048), False, (), True),
    ("a_tall_key", 0, (2048, 1408), False, (), True),
    ("start_off_a_lane", 64, (32, 256), False, (), False),
    ("rows_no_block_divides", 0, (8, 256), False, (), False),
    ("a_tall_side_of_no_whole_lanes", 0, (256, 96), False, (), False),
    ("an_adamw_key", 512, (1, 2048), True, (512, 4096), True),
    ("m_and_v_off_a_lane", 512, (1, 2048), True, (77, 4096), False),
    ("m_and_v_of_no_whole_lanes", 512, (1, 2048), True, (512, 4173), False),
])
def test_the_predicate_of_a_key_a_kernel_writes_back(case, start, shape,
                                                     elementwise, lies,
                                                     takes):
    assert muon.takes_apply(start, *shape, elementwise, *lies) == takes, case


def test_the_plan_says_which_keys_a_kernel_writes_back():
    """Per key, whatever its chunk: a matrix on a lane border is written
    by the kernel though a mate off one keeps the chunk's gradients on
    XLA's cut; AdamW keys behind one of 77 values have m and v off a
    lane."""
    plan = muon.muon_plan(ROW_SHAPES, ROW_ADAMW)
    assert [ROW_NAMES[k] for k in plan.apply_keys] == [
        "wide", "off_tile", "tall", "mate.0", "mate.1"]
    assert not plan.pulls
    assert plan.adamw_len % muon.LANES == 77
    assert len(muon.muon_plan(SHAPES, ADAMW).apply_keys) == 1   # "wide.0"
    cfg = _config()
    tensors = muon_flops.expand_shapes(cfg["tensors"])
    full = muon.muon_plan(
        [s for _, s in tensors],
        [muon_flops.is_adamw(n, cfg["adamw_keys"]) for n, _ in tensors])
    assert len(full.apply_keys) == 153 and full.pulls


@pytest.mark.parametrize("odd", ["a_matrix_off_a_lane", "an_adamw_key_of_77"])
def test_a_bucket_with_a_key_no_kernel_writes_keeps_the_cut(odd,
                                                            small_blocks):
    """One key the kernels cannot take, behind the others: they still
    write theirs, the pulled tree is the program's cut of the store, and
    it is PR 44's program's bit for bit and the reference's."""
    extra = {"a_matrix_off_a_lane": [("odd", (6, 10), False)],
             "an_adamw_key_of_77": [("odd", (1, 77), True)]}[odd]
    tree = APPLY_TREE + extra
    shapes = np.array([s for _, s, _ in tree])
    adamw = np.array([a for _, _, a in tree])
    lens = shapes[:, 0] * shapes[:, 1]
    starts = np.concatenate([[0], np.cumsum(lens)])
    keys = np.arange(len(tree), dtype=np.uint64)
    rng = np.random.default_rng(21)
    init = _init(rng, int(lens.sum()))
    eng = _engine()
    bucket = eng.register_dense(
        "t", keys, lens=lens, shapes=shapes, init=init,
        flags=np.where(adamw, KEY_ELEMENTWISE, 0))
    plan = eng._muon_plan(bucket)
    taken = {"a_matrix_off_a_lane": 9, "an_adamw_key_of_77": 5}[odd]
    assert len(plan.apply_keys) == taken and not plan.pulls
    assert not eng._kernel_pulls("push_pull_st", HANDLE, bucket)
    ref = _reference(init, shapes, adamw, starts)
    g = rng.normal(size=(1, int(lens.sum()))).astype(np.float32)
    pulled = np.asarray(eng.push_pull("t", g))
    before = _step(ref, _split(g, starts))
    _hold(pulled, ref, before, adamw, starts, where=odd)
    assert eng.kernel_pulls == 0 and eng.muon_apply_keys == taken
    state = (*(np.zeros(s, np.float32) for s in muon.state_shapes(plan)),
             np.zeros(1, np.float32))
    want, _ = jax.jit(lambda *a: muon.muon_update(
        *a, starts, shapes, _without_the_apply(plan), interpret=True,
        **HYPER))(np.asarray(_shard(init)), state, g)
    matrices = np.concatenate([np.arange(starts[k], starts[k + 1])
                               for k in plan.muon_keys])
    np.testing.assert_array_equal(pulled[matrices],
                                  np.asarray(want)[matrices])
    np.testing.assert_allclose(pulled, np.asarray(want)[:len(pulled)],
                               rtol=2e-7, atol=1e-9)   # AdamW: one rounding


@pytest.mark.parametrize("origin", ["host", "device"])
def test_the_kernels_pulled_tree_equals_the_reference(cluster, origin,
                                                      small_blocks):
    """Three steps of the bucket whose every key a kernel writes back:
    the pulled tree is the kernels' own vector (``engine.pull.from_kernel``
    rises) and the store's first ``total`` values bit for bit."""
    kv = _worker(cluster)
    eng = kv.engine
    rng = np.random.default_rng(17)
    init = _init(rng, APPLY_TOTAL)
    kv.register_dense("apply", APPLY_KEYS, lens=APPLY_LENS,
                      flags=APPLY_FLAGS, shapes=APPLY_SHAPES, init=init)
    ref = _reference(init, APPLY_SHAPES, APPLY_ADAMW, APPLY_STARTS)
    for step in range(3):
        g = rng.normal(size=(1, APPLY_TOTAL)).astype(np.float32)
        sent = (jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))
                if origin == "device" else g)
        ts = kv.push_pull(APPLY_KEYS, sent, None)
        pulled = np.asarray(kv.get_pulled(ts))
        kv.wait(ts)
        before = _step(ref, _split(g, APPLY_STARTS))
        assert pulled.shape == (APPLY_TOTAL,) and np.isfinite(pulled).all()
        _hold(pulled, ref, before, APPLY_ADAMW, APPLY_STARTS, where=step)
        np.testing.assert_array_equal(
            pulled, np.asarray(eng.pull("apply"))[:APPLY_TOTAL])
    assert eng.kernel_pulls == 3
    kind, (mom, m, v, slot) = eng.opt_state("apply")
    np.testing.assert_array_equal(np.asarray(slot), 3.0)
    np.testing.assert_allclose(
        np.asarray(m), np.concatenate(
            [ref.m[k] for k in range(len(APPLY_TREE)) if APPLY_ADAMW[k]]),
        atol=1e-7)


@pytest.mark.parametrize("way", ["push_pull", "push_then_pull", "pull_alone"])
def test_every_way_to_the_tree_gives_one_tree(cluster, way, small_blocks):
    """``push_pull`` hands back the kernels' vector, ``push`` makes none
    and ``pull`` reads the store: one tree, bit for bit, and PR 44's
    program's (no key's new values a kernel's) where the matrices lie."""
    kv = _worker(cluster)
    eng = kv.engine
    rng = np.random.default_rng(23)
    init = _init(rng, APPLY_TOTAL)
    g = rng.normal(size=(1, APPLY_TOTAL)).astype(np.float32)
    kv.register_dense("apply", APPLY_KEYS, lens=APPLY_LENS,
                      flags=APPLY_FLAGS, shapes=APPLY_SHAPES, init=init)
    out = np.zeros(APPLY_TOTAL, np.float32)
    if way == "push_pull":
        ts = kv.push_pull(APPLY_KEYS, g, None)
        out = np.asarray(kv.get_pulled(ts))
        kv.wait(ts)
    elif way == "push_then_pull":
        kv.wait(kv.push(APPLY_KEYS, g))
        kv.wait(kv.pull(APPLY_KEYS, out))
    else:
        kv.wait(kv.push_pull(APPLY_KEYS, g, None))
        kv.wait(kv.pull(APPLY_KEYS, out))
    assert eng.kernel_pulls == (way != "push_then_pull")
    plan = muon.muon_plan(APPLY_SHAPES, APPLY_ADAMW)
    state = (*(np.zeros(s, np.float32) for s in muon.state_shapes(plan)),
             np.zeros(1, np.float32))
    want, _ = jax.jit(lambda *a: muon.muon_update(
        *a, APPLY_STARTS, APPLY_SHAPES, _without_the_apply(plan),
        interpret=True, **HYPER))(_shard(init), state, g)
    want = np.asarray(want)[:APPLY_TOTAL]
    matrices = np.concatenate([np.arange(APPLY_STARTS[k], APPLY_STARTS[k + 1])
                               for k in plan.muon_keys])
    np.testing.assert_array_equal(out[matrices], want[matrices])
    np.testing.assert_allclose(out, want, rtol=2e-7, atol=1e-9)
    ref = _reference(init, APPLY_SHAPES, APPLY_ADAMW, APPLY_STARTS)
    before = _step(ref, _split(g, APPLY_STARTS))
    _hold(out, ref, before, APPLY_ADAMW, APPLY_STARTS, where=way)


def test_the_gauge_counts_the_keys_a_kernel_writes_back(cluster):
    kv = _worker(cluster)
    gauges = lambda: kv.po.metrics.snapshot()["gauges"]
    assert gauges()["engine.update.muon.apply_keys"] == 0
    kv.register_dense("apply", APPLY_KEYS, lens=APPLY_LENS,
                      flags=APPLY_FLAGS, shapes=APPLY_SHAPES)
    kv.wait(kv.push_pull(APPLY_KEYS, np.ones((1, APPLY_TOTAL), np.float32),
                         None))
    # Every key of the bucket: the pulled result is the kernels'.
    assert gauges()["engine.update.muon.apply_keys"] == len(APPLY_KEYS) \
        == gauges()["engine.update.muon.row_keys"]
    assert gauges()["engine.pull.from_kernel"] == 1
    kv.register_dense("rows", ROW_KEYS, lens=ROW_LENS, shapes=ROW_SHAPES,
                      flags=np.where(ROW_ADAMW, KEY_ELEMENTWISE, 0))
    kv.wait(kv.push_pull(ROW_KEYS, np.ones((1, ROW_TOTAL), np.float32),
                         None))
    # Five matrices on lane borders; the AdamW keys' m and v lie off one.
    assert gauges()["engine.update.muon.apply_keys"] == 5
    assert gauges()["engine.update.muon.row_keys"] == len(ROW_TAKEN)
    assert gauges()["engine.pull.from_kernel"] == 1


# -- the plan, the published count and the cut ----------------------------------


def _config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "moonlight-16b-muon.json")) as fh:
        return json.load(fh)


def test_the_plans_flops_are_the_benchmarks_published_count():
    cfg = _config()
    tensors = muon_flops.expand_shapes(cfg["tensors"])
    adamw = [muon_flops.is_adamw(n, cfg["adamw_keys"]) for n, _ in tensors]
    plan = muon.muon_plan([s for _, s in tensors], adamw)
    shapes = muon_flops.matrices(cfg)
    assert plan.matrices == len(shapes) == 135 and len(tensors) == 153
    assert plan.ns_flops == muon_flops.published(shapes) == 20631616225280.0
    assert muon_flops.least(shapes) == 14262857891840.0
    assert plan.muon_len == 484573184 and plan.adamw_len == 83911168
    assert plan.state_bytes == 2609582080      # 2.61 GB, not 3 x 2.27
    assert sum(len(c.keys) for c in plan.chunks) == 135
    # No batched product takes more than a layer's 24 expert matrices.
    assert max(len(c.keys) * c.m * c.n for c in plan.chunks) == 69206016
    assert muon_flops.by_group(shapes)[(1408, 2048)] == 96


def _grown(cfg, layers, experts, vocab):
    """The configuration's tensor list with its three ``reduced`` keys set
    to other values: MoE layers, routed experts held, vocabulary rows."""
    out = json.loads(json.dumps(cfg["tensors"]))
    for entry in out:
        if isinstance(entry, dict):
            if entry["name"] != "moe":
                continue
            entry["repeat"] = layers
            for inner in entry["tensors"]:
                if isinstance(inner, dict):
                    assert inner["name"] == "expert"
                    inner["repeat"] = experts
        elif entry[0] in ("emb.w", "head.w"):
            entry[1][0] = vocab
    return muon_flops.expand_shapes(out)


def test_the_cut_adds_up_to_the_published_tree():
    """64 experts held, 163,840 rows and 1 + 26 layers sum to the published
    15.96 B parameters; and the eight servers' shares of one MoE layer
    (experts 8k..8k+7 each, what all hold alike counted once) add up to
    that layer."""
    cfg = _config()
    pub = cfg["published"]
    full = _grown(cfg, pub["num_hidden_layers"] - 1,
                  pub["n_routed_experts"], pub["vocab_size"])
    assert sum(r * c for _, (r, c) in full) == pub["parameters"] \
        == 15960108544
    here = muon_flops.expand_shapes(cfg["tensors"])
    assert sum(r * c for _, (r, c) in here) == cfg["parameters"] == 568484352
    size = lambda tensors, prefix: {
        n: r * c for n, (r, c) in tensors if n.startswith(prefix)}
    layer = size(full, "moe.0.")
    share = size(here, "moe.0.")
    servers = pub["servers_sharing_a_layer"]
    held = cfg["sizes"]["n_routed_experts"]
    assert servers * held == pub["n_routed_experts"]
    experts = {n: v for n, v in share.items() if ".expert." in n}
    alike = {n: v for n, v in share.items() if ".expert." not in n}
    assert len(experts) == 3 * held
    rebuilt = dict(alike)
    for k in range(servers):
        for name, v in experts.items():
            j = int(name.split(".")[3])
            rebuilt[name.replace(f".expert.{j}.",
                                 f".expert.{held * k + j}.")] = v
    assert rebuilt == layer
    assert sum(layer.values()) == 31199744 + 64 * 8650752
