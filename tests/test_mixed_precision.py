"""A dense bucket whose job's dtype is narrower than its store's:
``register_dense(..., dtype=float32, job_dtype=bfloat16)``, mixed-precision
training with the 32-bit master copy where the optimizer state lives.

The contract (``CollectiveEngine.register_dense``): the store, the moments,
the sum over W and every norm are f32; a gradient is widened exactly; what
``push_pull`` and ``pull`` hand back is the f32 parameter the store now
holds rounded to nearest-even, ``store[:total].astype(bfloat16)`` bit for
bit.  Through ``KVWorker`` on the engine path, against float64 recurrences
(``benchmark/lamb_reference.py``, ``benchmark/reference.py``: numpy, nothing
of the program's) fed the bf16 gradients widened, on one shard and on the
4-shard CPU mesh, kernels interpreted.  What is not served is refused by
name.  The programs compiled for a described v5e are in
``tests/test_compile_for_v5e.py``.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker  # noqa: E402
from pslite_tpu.ops.fused_update import LAMB_TILE  # noqa: E402
from pslite_tpu.parallel.engine import (CollectiveEngine,  # noqa: E402
                                        KEY_NO_ADAPT, KEY_NO_DECAY)
from pslite_tpu.utils import logging as log  # noqa: E402

from helpers import LoopbackCluster  # noqa: E402

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark"))
from lamb_reference import LambReference, parse_lamb_handle  # noqa: E402
from reference import AdamReference, bf16 as round_bf16  # noqa: E402
from reference import parse_adam_handle  # noqa: E402

LAMB = "lamb:1e-2,0.9,0.999,1e-6,0.01"
ADAM = "adam:1e-2,0.9,0.999,1e-8"
BF16 = np.dtype(jnp.bfloat16)
EXCLUDED = KEY_NO_DECAY | KEY_NO_ADAPT
# As tests/test_lamb_handle.py: borders on no tile's and no shard's; over
# four shards of one tile each the 100,000 lie on shards 0, 1 and 2.
LENS = np.array([2, 3, 127, 128, 1025, 30522, 100000, 1000])
FLAGS = np.array([0, EXCLUDED, 0, EXCLUDED, 0, 0, 0, EXCLUDED])
KEYS = np.arange(100, 100 + len(LENS), dtype=np.uint64)
TOTAL = int(LENS.sum())
STARTS = np.concatenate([[0], np.cumsum(LENS)])
TOL = 2e-6      # tests/test_lamb_handle.py's


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


def _split(flat):
    return [np.asarray(flat)[..., STARTS[k]:STARTS[k + 1]]
            for k in range(len(LENS))]


def _init(rng):
    return (0.02 * rng.normal(size=TOTAL)).astype(np.float32)


def _grads(rng, workers):
    """``[W, TOTAL]`` bfloat16, and the same values widened."""
    g = rng.normal(size=(workers, TOTAL)).astype(BF16)
    return g, g.astype(np.float32)


class _Reference:
    """The float64 recurrence of either handle over the whole bucket."""

    def __init__(self, handle, init, **kw):
        if handle.startswith("lamb"):
            self.ref = LambReference(_split(init), FLAGS,
                                     **parse_lamb_handle(handle), **kw)
        else:
            self.ref = AdamReference(TOTAL, **parse_adam_handle(handle),
                                     **kw)
            self.ref.p = np.asarray(init, np.float64).copy()

    def step(self, wide):
        if isinstance(self.ref, LambReference):
            return np.concatenate(self.ref.step(_split(wide)))
        return np.asarray(self.ref.step(wide)).reshape(-1)


def _bits(x):
    return np.ascontiguousarray(np.asarray(x)).view(np.uint16)


def _store(eng, name="tree"):
    store = np.asarray(eng.store_array(name))
    assert store.dtype == np.float32
    return store[:TOTAL]


def _held(pulled, eng, name="tree"):
    """``pulled`` is bfloat16, of the keys' length, and the store rounded
    to nearest-even bit for bit (by ``ml_dtypes`` and by the benchmark's own
    integer rounding); the store is returned."""
    assert pulled.dtype == BF16 and pulled.shape == (TOTAL,)
    store = _store(eng, name)
    np.testing.assert_array_equal(_bits(pulled), _bits(store.astype(BF16)))
    np.testing.assert_array_equal(
        np.asarray(pulled).astype(np.float64), round_bf16(store))
    return store


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici",
                        env_extra={"PS_ICI_SERVER_HANDLE": LAMB})
    c.start()
    server = KVServer(0, postoffice=c.servers[0])   # the message path's
    server.set_request_handle(KVServerDefaultHandle())
    yield c
    c.finalize()


def _worker(cluster, shards, handle=LAMB):
    po = cluster.workers[0]
    po.van.engine = CollectiveEngine(mesh=_mesh(shards),
                                     server_handle=handle)
    po.van.engine.export(po.metrics)
    return KVWorker(0, 0, postoffice=po)


def _register(target, init=None, **kw):
    return target.register_dense("tree", KEYS, lens=LENS, flags=FLAGS,
                                 init=init, dtype=jnp.float32,
                                 job_dtype=jnp.bfloat16, **kw)


# -- the contract through KVWorker ---------------------------------------------


@pytest.mark.parametrize("origin", ["host", "device"])
@pytest.mark.parametrize("shards", [1, 4])
@pytest.mark.parametrize("handle", [LAMB, ADAM])
def test_the_store_is_f32_and_the_pulled_values_are_its_rounding(
        cluster, handle, shards, origin):
    kv = _worker(cluster, shards, handle)
    eng = kv.engine
    rng = np.random.default_rng(shards + len(handle))
    init = _init(rng)
    bucket = _register(kv, init)
    assert bucket.mixed and bucket.job_dtype == BF16
    assert np.dtype(bucket.dtype) == np.float32
    assert bucket.nbytes == 2 * TOTAL
    ref = _Reference(handle, init)
    for step in range(4):
        g, wide = _grads(rng, shards)
        if origin == "device":
            # Rows, as the record's sharding has them or placed anew.
            sent = (jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis)))
                    if step % 2 == 0 else jnp.asarray(g))
        else:
            sent = g
        ts = kv.push_pull(KEYS, sent, None, lens=LENS if step % 2 else None)
        pulled = kv.get_pulled(ts)
        kv.wait(ts)
        want = ref.step(wide)
        store = _held(pulled, eng)
        assert np.max(np.abs(store - want)) < TOL, step
    kind, (m, v, slot) = eng.opt_state("tree")
    assert kind == handle.split(":")[0]
    assert m.dtype == v.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(slot), 4.0)
    # The counters count the job's bytes: 2 B pushed, 2 B pulled a value.
    assert eng.push_bytes == eng.pull_bytes == 4 * 2 * TOTAL
    gauges = kv.po.metrics.snapshot()["gauges"]
    assert gauges["engine.dense.narrow"] == 4
    assert gauges["engine.update.lamb"] == (4 if handle == LAMB else 0)
    assert gauges["engine.pull.from_kernel"] == (
        4 if handle == LAMB and shards == 1 else 0)
    # ``pull`` alone, into a host buffer of the job's dtype.
    out = np.zeros(TOTAL, BF16)
    kv.wait(kv.pull(KEYS, out))
    np.testing.assert_array_equal(_bits(out), _bits(store.astype(BF16)))
    assert eng.pull_bytes == 5 * 2 * TOTAL
    assert kv.po.metrics.snapshot()["gauges"]["engine.dense.narrow"] == 5


@pytest.mark.parametrize("handle", ["adagrad:0.01,1e-8",
                                    "sgd_momentum:0.01,0.9"])
def test_the_other_stateful_handles_widen_in_their_kernel(handle):
    """``adagrad`` and ``sgd_momentum`` on a mixed bucket: the element-wise
    kernel's own ``astype``, equal bit for bit to the same gradients
    widened by the caller into an f32-job bucket."""
    rng = np.random.default_rng(9)
    init = _init(rng)
    mixed = CollectiveEngine(mesh=_mesh(4), server_handle=handle)
    plain = CollectiveEngine(mesh=_mesh(4), server_handle=handle)
    _register(mixed, init)
    plain.register_dense("tree", KEYS, lens=LENS, flags=FLAGS, init=init)
    for _ in range(2):
        g, wide = _grads(rng, 4)
        pulled = mixed.push_pull("tree", g)
        want = np.asarray(plain.push_pull("tree", wide))
        store = _held(np.asarray(pulled), mixed)
        np.testing.assert_array_equal(store, want)


@pytest.mark.parametrize("shards", [1, 4])
def test_the_same_gradients_widened_by_the_caller_leave_the_same_store(
        shards):
    """The tie to the f32 path: on one shard the kernel widens the job's
    row in VMEM, on four XLA widens it before the sum; either way the
    store is what an f32-job bucket holds after the caller's own exact
    widening, bit for bit."""
    rng = np.random.default_rng(shards)
    init = _init(rng)
    mixed = CollectiveEngine(mesh=_mesh(shards), server_handle=LAMB)
    plain = CollectiveEngine(mesh=_mesh(shards), server_handle=LAMB)
    _register(mixed, init)
    plain.register_dense("tree", KEYS, lens=LENS, flags=FLAGS, init=init)
    for _ in range(3):
        g, wide = _grads(rng, shards)
        pulled = mixed.push_pull("tree", g)
        want = np.asarray(plain.push_pull("tree", wide))
        np.testing.assert_array_equal(_store(mixed), want)
        np.testing.assert_array_equal(_bits(pulled),
                                      _bits(want.astype(BF16)))
    for a, b in zip(mixed.opt_state("tree")[1], plain.opt_state("tree")[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_reference_with_a_bf16_master_misses_the_tolerance():
    """The control: the same recurrence with every stored value rounded
    to bfloat16 (a 16-bit master and moments) is hundreds of tolerances
    away after three steps, so the comparison above would not pass one."""
    rng = np.random.default_rng(2)
    init = _init(rng)
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=LAMB)
    _register(eng, init)
    ref, ctl = _Reference(LAMB, init), _Reference(LAMB, init,
                                                  rounding=round_bf16)
    for _ in range(3):
        g, wide = _grads(rng, 1)
        eng.push_pull("tree", g)
        want, narrow = ref.step(wide), ctl.step(wide)
    store = _store(eng)
    assert np.max(np.abs(store - want)) < TOL
    assert np.max(np.abs(narrow - want)) > 100 * TOL


def test_the_sum_over_the_workers_is_taken_in_f32():
    """Four workers whose bf16 sum rounds otherwise than their f32 sum:
    1 + 2^-8 three times over is 1 + 3 * 2^-8 in f32 and, added pairwise
    in bfloat16 (8 bits of significand), 1 + 2^-7 or 1 + 2^-6.  Under
    ``sgd_momentum:1,0`` the store is minus the sum."""
    eng = CollectiveEngine(mesh=_mesh(4), server_handle="sgd_momentum:1,0")
    _register(eng)
    rows = np.array([1.0, 2.0 ** -8, 2.0 ** -8, 2.0 ** -8])
    g = np.broadcast_to(rows[:, None], (4, TOTAL)).astype(BF16)
    assert (g.astype(np.float32)[:, 0] == rows).all()      # all exact
    pulled = eng.push_pull("tree", g)
    store = _held(np.asarray(pulled), eng)
    exact = np.float32(1.0 + 3 * 2.0 ** -8)
    assert (store == -exact).all()
    in_bf16 = g[0] + g[1] + g[2] + g[3]                    # rounds each add
    assert in_bf16.dtype == BF16
    assert (in_bf16.astype(np.float32) != exact).all()


def test_a_push_alone_then_a_pull(cluster):
    kv = _worker(cluster, 1)
    eng = kv.engine
    rng = np.random.default_rng(6)
    init = _init(rng)
    _register(kv, init)
    ref = _Reference(LAMB, init)
    g, wide = _grads(rng, 1)
    kv.wait(kv.push(KEYS, g))
    want = ref.step(wide)
    assert np.max(np.abs(_store(eng) - want)) < TOL
    # A push returns nothing to round: the one-result kernel.
    assert (eng.lamb_updates, eng.kernel_pulls, eng.narrow_ops) == (1, 0, 1)
    assert (eng.push_bytes, eng.pull_bytes) == (2 * TOTAL, 0)
    _held(eng.pull("tree"), eng)


def test_a_bucket_too_short_for_the_kernels_vector_blocks():
    """Up to 512 values the chip lays a vector in one tile of its own
    length (``fused_update.lamb_apply_pulls``): the pulled values are cut
    from the store and rounded after the kernel, the contract the same."""
    lens, keys = np.array([300, 212]), np.array([7, 8], dtype=np.uint64)
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=LAMB)
    rng = np.random.default_rng(8)
    init = (0.02 * rng.normal(size=512)).astype(np.float32)
    eng.register_dense("t", keys, lens=lens, init=init,
                       job_dtype=jnp.bfloat16)
    g = rng.normal(size=(1, 512)).astype(BF16)
    pulled = np.asarray(eng.push_pull("t", g))
    ref = LambReference([init[:300], init[300:]], [0, 0],
                        **parse_lamb_handle(LAMB))
    want = np.concatenate(ref.step([g.astype(np.float32)[:, :300],
                                    g.astype(np.float32)[:, 300:]]))
    store = np.asarray(eng.store_array("t"))[:512]
    assert np.max(np.abs(store - want)) < TOL
    np.testing.assert_array_equal(_bits(pulled), _bits(store.astype(BF16)))
    assert (eng.narrow_ops, eng.kernel_pulls) == (1, 0)


# -- what is not served is refused by name --------------------------------------


def _refused(call, *words):
    with pytest.raises(log.CheckError) as exc:
        call()
    for word in words:
        assert word in str(exc.value), (word, str(exc.value))


def test_a_gradient_of_another_dtype_is_refused_on_every_bucket(cluster):
    kv = _worker(cluster, 1, ADAM)
    eng = kv.engine
    _register(kv)
    wide = np.ones((1, TOTAL), np.float32)
    for sent in (wide, jnp.asarray(wide), jnp.ones(TOTAL, jnp.float32),
                 np.ones(TOTAL, np.float16)):
        _refused(lambda: kv.push_pull(KEYS, sent, None), "'tree'",
                 "bfloat16", "float32 store", str(sent.dtype))
        _refused(lambda: kv.push(KEYS, sent), "'tree'")
    # A device gradient is rows [W, total]: no other form is laid out anew.
    for sent in (jnp.ones(TOTAL, jnp.bfloat16),
                 jnp.ones((1, TOTAL + 1), jnp.bfloat16)):
        _refused(lambda: kv.push_pull(KEYS, sent, None), "'tree'",
                 f"rows [1, {TOTAL}]", str(tuple(sent.shape)))
    assert eng.narrow_ops == 0 and eng.push_bytes == 0
    # The device branch looks at the dtype on an f32 bucket too, where it
    # used to trace the bucket's program anew for whatever it was handed.
    plain = np.array([50], dtype=np.uint64)
    kv.register_dense("plain", plain, 256)
    _refused(lambda: kv.push_pull(
        plain, jnp.ones((1, 256), jnp.bfloat16), None),
        "'plain'", "float32", "bfloat16")
    kv.register_dense("own", np.array([60, 61], dtype=np.uint64),
                      lens=[100, 600])
    _refused(lambda: kv.push_pull(
        np.array([60, 61], dtype=np.uint64),
        jnp.ones((1, 700), jnp.bfloat16), None), "'own'", "bfloat16")
    # A host array is still staged in the bucket's dtype, as it always was.
    kv.wait(kv.push_pull(plain, np.ones((1, 256), np.float64), None))


def test_registration_refuses_what_no_program_serves():
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=LAMB)
    _refused(lambda: eng.register_dense("u", KEYS, 64,
                                        job_dtype=jnp.bfloat16),
             "'u'", "bfloat16", "float32 store", "one val_len", "lens=")
    _refused(lambda: eng.register_dense("w", KEYS, lens=LENS,
                                        dtype=jnp.bfloat16,
                                        job_dtype=jnp.float32),
             "'w'", "narrower")
    _refused(lambda: eng.register_dense("i", KEYS, lens=LENS,
                                        job_dtype=jnp.int8),
             "'i'", "narrower float")
    # The store's own dtype named twice is no mixed bucket.
    same = eng.register_dense("s", KEYS, lens=LENS, job_dtype=jnp.float32)
    assert not same.mixed and same.nbytes == 4 * TOTAL


def test_the_paths_that_do_not_serve_a_job_dtype_refuse_by_name(cluster):
    kv = _worker(cluster, 4)
    eng = kv.engine
    _register(kv)
    g = np.ones((4, TOTAL), BF16)
    said = ("'tree'", "bfloat16", "float32 store")
    # A stateless handle: the programs shared by length.
    for handle in ("sum", "assign", "sgd:0.1"):
        _refused(lambda: eng.push_pull("tree", g, handle), *said,
                 "stateless", "stateful handle")
        _refused(lambda: eng.push("tree", g, handle), *said, "stateless")
    _refused(lambda: eng.replay("tree", np.ones((2, 4, TOTAL), BF16)),
             *said, "replay")
    _refused(lambda: list(eng.push_pull_stream("tree", [g])), *said,
             "stream")
    _refused(lambda: eng.push_pull_group(["tree"], [g], "sum"), *said,
             "group")
    _refused(lambda: eng.register_pull_buffer("tree"), *said, "pinned")
    # The message path: a call the engine cannot take would go to servers
    # that know nothing of the bucket.
    _refused(lambda: kv.push_pull(KEYS, g, None, cmd=7), *said,
             "message path", "cmd")
    _refused(lambda: kv.pull(KEYS, np.zeros(TOTAL, BF16), cmd=7), *said,
             "message path")
    # In-place pull delivery is never the store of another dtype.
    pulled = eng.push_pull("tree", g, None, True)
    assert pulled.dtype == BF16
    assert eng.narrow_ops == 1


# -- state that moves keeps the job's dtype -------------------------------------


def test_save_restore_and_four_to_two_shards_keep_the_job_dtype(tmp_path):
    from pslite_tpu import checkpoint

    rng = np.random.default_rng(12)
    init = _init(rng)
    eng = CollectiveEngine(mesh=_mesh(4), server_handle=LAMB)
    _register(eng, init)
    ref = _Reference(LAMB, init)
    g, wide = _grads(rng, 4)
    eng.push_pull("tree", g)
    ref.step(wide)
    path = str(tmp_path / "ckpt")
    checkpoint.save_engine(eng, path)
    # Restored into a fresh registration: the files hold the f32 store.
    again = CollectiveEngine(mesh=_mesh(4), server_handle=LAMB)
    _register(again)
    checkpoint.restore_engine(again, path)
    np.testing.assert_array_equal(_store(again), _store(eng))
    for e in (eng, again):
        e.reshard(_mesh(2))
        bucket = e.bucket("tree")
        assert bucket.mixed and bucket.job_dtype == BF16
        assert bucket.padded_len == 4 * LAMB_TILE
    g2, wide2 = _grads(rng, 2)
    want = ref.step(wide2)
    for e in (eng, again):
        store = _held(np.asarray(e.push_pull("tree", g2)), e)
        assert np.max(np.abs(store - want)) < TOL
        kind, (m, v, slot) = e.opt_state("tree")
        assert m.dtype == jnp.float32 and float(np.asarray(slot)[0]) == 2.0


# -- what KVWorker keeps and says -----------------------------------------------


def test_pulled_trees_are_kept_by_their_bytes_at_two_a_value(
        cluster, monkeypatch):
    """``get_pulled``'s bound is in bytes: of a bf16 job's pulled trees
    three are kept where one f32 tree and a half would fit."""
    kv = _worker(cluster, 1)
    monkeypatch.setattr(KVWorker, "_DEVICE_RESULTS_BYTES", 6 * TOTAL)
    _register(kv)
    assert kv._results_heavy
    g = np.ones((1, TOTAL), BF16)
    stamps = [kv.push_pull(KEYS, g, None) for _ in range(4)]
    for ts in stamps:
        kv.wait(ts)
    assert [kv.get_pulled(ts) is not None for ts in stamps] \
        == [False, True, True, True]
    assert kv.get_pulled(stamps[-1]).nbytes == 2 * TOTAL


def test_the_span_of_a_dense_op_names_the_job_dtype(cluster, monkeypatch):
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

    kv = _worker(cluster, 1, ADAM)
    _register(kv)
    kv.register_dense("plain", np.array([50], dtype=np.uint64), 256)
    monkeypatch.setattr(kv_app, "tracing", lambda: True)   # a session runs
    monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
    ts = kv.push_pull(KEYS, np.ones((1, TOTAL), BF16), None)
    kv.wait(ts)
    assert {"ts": ts, "name": "tree", "op": "dense.push_pull",
            "handle": "adam", "job": "bfloat16"} in seen
    ts = kv.push_pull(np.array([50], dtype=np.uint64),
                      np.ones((1, 256), np.float32), None)
    kv.wait(ts)
    assert {"ts": ts, "name": "plain", "op": "dense.push_pull",
            "handle": "adam"} in seen


def test_widening_and_narrowing_outside_a_kernel_lie_under_their_scopes():
    """On four shards XLA widens the gradient before the f32 sum and
    rounds the shards before the gather: ``ps.push.widen`` and
    ``ps.pull.narrow`` in the lowered program; on one shard the kernels do
    both and neither scope exists."""
    texts = {}
    for shards in (1, 4):
        eng = CollectiveEngine(mesh=_mesh(shards), server_handle=LAMB)
        bucket = _register(eng)
        prog = eng._program("push_pull_st", bucket.padded_len, jnp.float32,
                            LAMB, bucket)
        eng._ensure_opt_state("tree", "lamb", bucket)
        g = jax.device_put(np.ones((shards, TOTAL), BF16),
                           NamedSharding(eng.mesh, P(eng.axis)))
        texts[shards] = prog.lower(
            eng._stores["tree"], *eng._opt_states["tree"], g).as_text(
                debug_info=True)
    assert "ps.push.widen" in texts[4] and "ps.pull.narrow" in texts[4]
    assert "ps.push.widen" not in texts[1]
    assert "ps.pull.narrow" not in texts[1]
