"""``lamb:lr,b1,b2,eps,wd``: a server handle that is not element-wise, on a
dense bucket registered with its keys' own lengths (``lens``).

Through ``KVWorker.push_pull`` on the engine path, against
``benchmark/lamb_reference.py`` (numpy, float64, imports nothing of the
program), on one shard and on the 4-shard CPU mesh, kernels interpreted.
The keys' borders lie on no tile's and no shard's: lengths 2, 3, 127, 128,
1,025, 30,522, and one key that spans three of four shards.
"""

import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

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

HANDLE = "lamb:1e-2,0.9,0.999,1e-6,0.01"
EXCLUDED = KEY_NO_DECAY | KEY_NO_ADAPT
# With four shards of one tile each, the 100,000 after 31,807 values lie on
# shards 0, 1 and 2; the last key is excluded from decay and adaptation.
LENS = np.array([2, 3, 127, 128, 1025, 30522, 100000, 1000])
FLAGS = np.array([0, EXCLUDED, 0, EXCLUDED, 0, 0, 0, EXCLUDED])
KEYS = np.arange(100, 100 + len(LENS), dtype=np.uint64)
TOTAL = int(LENS.sum())
STARTS = np.concatenate([[0], np.cumsum(LENS)])


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


def _split(flat):
    return [np.asarray(flat)[..., STARTS[k]:STARTS[k + 1]]
            for k in range(len(LENS))]


def _init(rng):
    return (0.02 * rng.normal(size=TOTAL)).astype(np.float32)


def _reference(init, handle=HANDLE, flags=FLAGS, **kw):
    return LambReference(_split(init), flags, **parse_lamb_handle(handle),
                         **kw)


def _err(got, ref):
    return float(np.max(np.abs(np.asarray(got, np.float64)
                               - np.concatenate(ref.p))))


@pytest.fixture()
def cluster():
    c = LoopbackCluster(num_workers=1, num_servers=1, van_type="ici",
                        env_extra={"PS_ICI_SERVER_HANDLE": HANDLE})
    c.start()
    server = KVServer(0, postoffice=c.servers[0])   # the message path's
    server.set_request_handle(KVServerDefaultHandle())
    yield c
    c.finalize()


def _worker(cluster, shards):
    """A ``KVWorker`` whose van's engine lies over ``shards`` devices (the
    van builds its own over all eight)."""
    po = cluster.workers[0]
    assert po.van.engine._server_handle == HANDLE    # from the environment
    po.van.engine = CollectiveEngine(mesh=_mesh(shards),
                                     server_handle=HANDLE)
    po.van.engine.export(po.metrics)
    return KVWorker(0, 0, postoffice=po)


# -- the handle against the reference -----------------------------------------


@pytest.mark.parametrize("origin", ["host", "device"])
@pytest.mark.parametrize("shards", [1, 4])
def test_lamb_through_kvworker_equals_the_reference(cluster, shards, origin):
    """Five steps, W = ``shards`` workers whose gradients all differ."""
    kv = _worker(cluster, shards)
    eng = kv.engine
    rng = np.random.default_rng(shards)
    init = _init(rng)
    bucket = kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS,
                               init=init)
    assert bucket.total_len == TOTAL
    assert bucket.padded_len == (3 if shards == 1 else 4) * LAMB_TILE
    assert bucket.padded_len % (shards * LAMB_TILE) == 0
    ref = _reference(init)
    for step in range(5):
        g = rng.normal(size=(shards, TOTAL)).astype(np.float32)
        if origin == "device":
            # As a job has it: the keys' own length, which is no tile's.
            sent = jax.device_put(
                g, NamedSharding(eng.mesh, P(eng.axis, None)))
        else:
            sent = g
        ts = kv.push_pull(KEYS, sent, None,
                          lens=LENS if step % 2 else None)
        pulled = kv.get_pulled(ts)
        kv.wait(ts)
        ref.step(_split(g))
        assert pulled.shape == (TOTAL,)
        assert _err(pulled, ref) < 2e-6, step
    # The adapted keys' ratios are far from 1: the test would not pass a
    # program that skipped them.
    assert all(abs(r - 1.0) > 0.3 for r, f in zip(ref.ratios, FLAGS)
               if not f)
    kind, (m, v, slot) = eng.opt_state("tree")
    assert kind == "lamb"
    np.testing.assert_array_equal(np.asarray(slot), 5.0)
    np.testing.assert_allclose(np.asarray(m)[:TOTAL],
                               np.concatenate(ref.m), atol=1e-6)
    # Every op ran on the engine path, each under LAMB.
    assert eng.push_bytes == eng.pull_bytes == 5 * 4 * TOTAL
    gauges = kv.po.metrics.snapshot()["gauges"]
    assert gauges["engine.update.lamb"] == 5
    assert gauges["engine.dense.segments"] == len(LENS)
    out = np.zeros(TOTAL, np.float32)
    kv.wait(kv.pull(KEYS, out))
    assert _err(out, ref) < 2e-6


def test_an_excluded_key_gets_no_decay_and_ratio_one():
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    rng = np.random.default_rng(3)
    init = _init(rng)
    eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    g = rng.normal(size=(1, TOTAL)).astype(np.float32)
    got = _split(np.asarray(eng.push_pull("t", g)))
    lr, eps = 1e-2, 1e-6
    for k, (p0, gk) in enumerate(zip(_split(init), _split(g[0]))):
        # Step 1: mh = g and vh = g*g whatever the betas.
        plain = p0 - lr * gk / (np.abs(gk) + eps)
        if FLAGS[k] == EXCLUDED:
            np.testing.assert_allclose(got[k], plain, atol=1e-6)
        else:
            assert np.max(np.abs(got[k] - plain)) > 1e-3


@pytest.mark.parametrize("zero_gradient", [True, False])
def test_a_zero_norm_key_gets_ratio_one_and_stays_finite(zero_gradient):
    eng = CollectiveEngine(mesh=_mesh(4), server_handle=HANDLE)
    rng = np.random.default_rng(4)
    init = _init(rng)
    zeroed = (2, 5)                      # adapted keys with a zero store
    for k in zeroed:
        init[STARTS[k]:STARTS[k + 1]] = 0.0
    eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    ref = _reference(init)
    for _ in range(2):
        g = rng.normal(size=(4, TOTAL)).astype(np.float32)
        if zero_gradient:
            for k in zeroed:
                g[:, STARTS[k]:STARTS[k + 1]] = 0.0
        pulled = np.asarray(eng.push_pull("t", g))
        ref.step(_split(g))
        assert np.isfinite(pulled).all()
        assert _err(pulled, ref) < 2e-6
    for k in zeroed:
        if zero_gradient:   # |p| = |u| = 0: nothing moves
            assert not _split(pulled)[k].any()
        else:               # |p| = 0 at the first step: a plain Adam step
            assert np.abs(_split(pulled)[k]).min() > 1e-3


@pytest.mark.parametrize("shards", [1, 4])
def test_all_keys_excluded_is_adam(shards):
    """No decay and ratio 1 leave ``p -= lr*mh/(sqrt(vh)+eps)``: Adam, but
    for where eps stands (``adam_update`` folds the corrections into one
    ``alpha_t``, which scales eps by ``1/sqrt(1-b2^t)``) and for how
    ``1 - b**t`` is computed.  With eps far below the gradients' size the
    two agree to f32 rounding of a step."""
    lr = 1e-2
    lamb = CollectiveEngine(mesh=_mesh(shards),
                            server_handle=f"lamb:{lr},0.9,0.999,1e-12,0.01")
    adam = CollectiveEngine(mesh=_mesh(shards),
                            server_handle=f"adam:{lr},0.9,0.999,1e-12")
    rng = np.random.default_rng(5)
    init = _init(rng)
    lamb.register_dense("t", KEYS, lens=LENS,
                        flags=np.full(len(LENS), EXCLUDED), init=init)
    # (Adam on a bucket with lens: any handle takes such a bucket.)
    adam.register_dense("t", KEYS, lens=LENS, init=init)
    for step in range(5):
        g = (0.5 + rng.random(size=(shards, TOTAL))).astype(np.float32)
        g *= rng.choice([-1.0, 1.0], size=TOTAL).astype(np.float32)
        a = np.asarray(adam.push_pull("t", g))
        b = np.asarray(lamb.push_pull("t", g))
        assert np.max(np.abs(a - b)) < 5e-5 * lr, step


@pytest.mark.parametrize("shards", [1, 4])
def test_the_padding_never_enters_a_norm_and_stays_zero(shards):
    """The gradient ends where the last key does, inside the last tile.  On
    one shard the kernel reads the row as it is, and behind its end what
    lies there (the interpreter hands it NaN); on four the program fills
    the row with zeros before it is cut.  Neither reaches a norm, a moment
    or the store."""
    eng = CollectiveEngine(mesh=_mesh(shards), server_handle=HANDLE)
    rng = np.random.default_rng(6)
    init = _init(rng)
    bucket = eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    assert bucket.padded_len - bucket.total_len > 1000
    ref = _reference(init)
    for _ in range(3):
        g = rng.normal(size=(shards, TOTAL)).astype(np.float32)
        sent = jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))
        pulled = eng.push_pull("t", sent)
        ref.step(_split(g))
        assert pulled.shape == (TOTAL,)
        assert _err(pulled, ref) < 2e-6
    _, (m, v, _) = eng.opt_state("t")
    for vec in (eng._stores["t"], m, v):
        assert not np.asarray(vec)[TOTAL:].any()


def test_a_gradient_at_the_padded_length_is_refused():
    """``padded_len`` is the engine's own: a program of a bucket with
    ``lens`` takes the keys' values and nothing behind them."""
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    bucket = eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS)
    with pytest.raises(log.CheckError, match="bad grad len"):
        eng.push_pull("t", np.zeros((1, bucket.padded_len), np.float32))


@pytest.mark.parametrize("handle", ["adam:1e-2,0.9,0.999,1e-8",
                                    "sgd_momentum:0.1,0.9", "adagrad:0.1"])
@pytest.mark.parametrize("shards", [1, 4])
def test_an_elementwise_handle_on_a_bucket_with_lens(shards, handle):
    """Any stateful handle's program of such a bucket is the bucket's own:
    it takes ``[W, total]`` as it is, cuts its own output (no copy after
    the program), and equals the same handle on a uniform bucket."""
    own = CollectiveEngine(mesh=_mesh(shards), server_handle=handle)
    flat = CollectiveEngine(mesh=_mesh(shards), server_handle=handle)
    rng = np.random.default_rng(9)
    init = _init(rng)
    own.register_dense("t", KEYS, lens=LENS, init=init)
    flat.register_dense("t", KEYS[:1], TOTAL, init=init)
    for _ in range(3):
        g = rng.normal(size=(shards, TOTAL)).astype(np.float32)
        sent = jax.device_put(g, NamedSharding(own.mesh, P(own.axis, None)))
        a, b = own.push_pull("t", sent), flat.push_pull("t", g)
        assert a.shape == (TOTAL,)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    bound = own._bound[("t", None, False)]
    assert not bound.cut and bound.prep == own._prep_grads_whole
    own.push("t", g)                      # the push alone, the same way
    assert not np.asarray(own._stores["t"])[TOTAL:].any()
    # These handles' kernels leave no pulled values: the cut, as before.
    assert (own.lamb_updates, own.kernel_pulls) == (0, 0)


def test_worker_axis_and_kv_axis_apart():
    """2 workers over ``dp``, 4 shards over ``kv``: the gradients are
    summed over ``dp``, the norms over ``kv``."""
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 4), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh, server_handle=HANDLE, worker_axis="dp")
    rng = np.random.default_rng(7)
    init = _init(rng)
    eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    ref = _reference(init)
    for _ in range(3):
        g = rng.normal(size=(2, TOTAL)).astype(np.float32)
        pulled = np.asarray(eng.push_pull("t", g))
        ref.step(_split(g))
        assert _err(pulled, ref) < 2e-6


def test_state_moves_to_another_number_of_shards():
    """``reshard`` pads a bucket with ``lens`` to whole tiles a shard of
    the new mesh, and the step slot of ``lamb`` travels as Adam's does."""
    eng = CollectiveEngine(mesh=_mesh(4), server_handle=HANDLE)
    rng = np.random.default_rng(8)
    init = _init(rng)
    eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    ref = _reference(init)
    for shards in (4, 2, 1):
        if shards != 4:
            eng.reshard(_mesh(shards))
            assert eng.bucket("t").padded_len % (shards * LAMB_TILE) == 0
        g = rng.normal(size=(shards, TOTAL)).astype(np.float32)
        pulled = np.asarray(eng.push_pull("t", g))
        ref.step(_split(g))
        assert _err(pulled, ref) < 2e-6
    kind, state = eng.opt_state("t")
    other = CollectiveEngine(mesh=_mesh(4), server_handle=HANDLE)
    other.register_dense("t", KEYS, lens=LENS, flags=FLAGS,
                         init=np.asarray(eng.store_array("t"))[:TOTAL])
    m, v, slot = (np.asarray(s) for s in state)
    other.set_opt_state("t", kind, [m[:TOTAL], v[:TOTAL], slot])
    g = rng.normal(size=(4, TOTAL)).astype(np.float32)
    pulled = np.asarray(other.push_pull("t", g))
    ref.step(_split(g))
    assert _err(pulled, ref) < 2e-6


# -- the pulled values as the second kernel leaves them ------------------------


@pytest.mark.parametrize("total", [
    2 * LAMB_TILE + 26428,      # ends in the middle of the last tile
    2 * LAMB_TILE,              # ends on a tile's border
    1000,                       # a bucket shorter than one tile
])
def test_lamb_apply_leaves_the_new_parameters_twice(total):
    """With ``pulled_len`` the kernel's second result is its first cut at
    that length, bit for bit, in a buffer of its own; the first is what
    the kernel without it stores, and the padding keeps its value."""
    import jax.numpy as jnp

    from pslite_tpu.ops import fused_update

    padded = -(-total // LAMB_TILE) * LAMB_TILE
    lens = np.array([total - 300, 300])
    starts = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    rng = np.random.default_rng(total)
    store = rng.normal(size=padded).astype(np.float32)   # padding not zero
    m = rng.normal(size=padded).astype(np.float32)
    v = rng.random(size=padded).astype(np.float32)
    args = (jnp.asarray(store), jnp.asarray(m), jnp.asarray(v),
            jnp.float32(3.0), jnp.asarray(starts),
            jnp.full(2, 0.01, jnp.float32), jnp.full(2, 0.1, jnp.float32),
            jnp.asarray(fused_update.lamb_blocks(starts, padded, 1)[0]),
            jnp.zeros(1, jnp.int32))
    new_store, pulled = fused_update.lamb_apply(*args, interpret=True,
                                                pulled_len=total)
    alone, none = fused_update.lamb_apply(*args, interpret=True)
    assert none is None and pulled.shape == (total,)
    assert pulled.unsafe_buffer_pointer() != new_store.unsafe_buffer_pointer()
    new_store = np.asarray(new_store)
    np.testing.assert_array_equal(new_store, np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(pulled), new_store[:total])
    np.testing.assert_array_equal(new_store[total:], store[total:])
    assert np.abs(new_store[:total] - store[:total]).min() > 0


@pytest.mark.parametrize("mesh_shape, from_kernel", [
    ((1,), True), ((4,), False), ((2, 1), True)])
def test_where_one_shard_holds_the_bucket_the_kernel_writes_the_pulled_values(
        mesh_shape, from_kernel):
    """``push_pull`` returns the store's values up to ``total_len`` bit for
    bit on either path; on one shard (under a worker axis too) they are
    the kernel's own second result, counted by ``engine.pull.from_kernel``;
    over four they are the gathered shards, and the count stays."""
    from pslite_tpu.telemetry.metrics import Registry

    devices = np.array(jax.devices()[:int(np.prod(mesh_shape))])
    if len(mesh_shape) == 1:
        eng = CollectiveEngine(mesh=Mesh(devices, ("kv",)),
                               server_handle=HANDLE)
    else:
        eng = CollectiveEngine(
            mesh=Mesh(devices.reshape(mesh_shape), ("dp", "kv")),
            server_handle=HANDLE, worker_axis="dp")
    registry = Registry()
    eng.export(registry)
    rng = np.random.default_rng(len(devices))
    init = _init(rng)
    eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    ref = _reference(init)
    for step in range(3):
        g = rng.normal(size=(eng.num_workers, TOTAL)).astype(np.float32)
        pulled = eng.push_pull("t", g)
        ref.step(_split(g))
        assert pulled.shape == (TOTAL,)
        np.testing.assert_array_equal(
            np.asarray(pulled), np.asarray(eng.store_array("t"))[:TOTAL])
        assert _err(pulled, ref) < 2e-6, step
    eng.push("t", g)            # a push alone pulls nothing
    eng.pull("t")               # nor does a pull run the kernel
    gauges = registry.snapshot()["gauges"]
    assert gauges["engine.update.lamb"] == 4
    assert gauges["engine.pull.from_kernel"] == (3 if from_kernel else 0)
    assert not np.asarray(eng.store_array("t"))[TOTAL:].any()


def test_one_shard_and_four_pull_the_same_values():
    """The kernel's vector on one shard against the gathered shards on
    four, the same store and gradients: within the file's tolerance (the
    norms are summed in another order over four shards)."""
    rng = np.random.default_rng(12)
    init = _init(rng)
    one = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    four = CollectiveEngine(mesh=_mesh(4), server_handle=HANDLE)
    for eng in (one, four):
        eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS, init=init)
    for step in range(3):
        g = rng.normal(size=(1, TOTAL)).astype(np.float32)
        spread = np.concatenate([g, np.zeros((3, TOTAL), np.float32)])
        a = np.asarray(one.push_pull("t", g))
        b = np.asarray(four.push_pull("t", spread))
        assert np.max(np.abs(a - b)) < 2e-6, step
    assert (one.kernel_pulls, four.kernel_pulls) == (3, 0)


def test_a_push_alone_lowers_the_one_result_kernel():
    """Whether the vector is made is a static argument of the one kernel:
    the program of ``push`` has ``lamb_apply`` with the store for its one
    result, that of ``push_pull`` on one shard with two."""
    import re

    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    bucket = eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS)
    g = np.zeros((1, TOTAL), np.float32)
    eng.push_pull("t", g)
    _, state = eng.opt_state("t")
    args = (eng.store_array("t"), *state,
            jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None))))

    def results(op):
        text = eng._program(op, bucket.padded_len, bucket.dtype, HANDLE,
                            bucket).lower(*args).as_text()
        (sig,) = re.findall(
            r"func\.func private @lamb_apply\(.*?\) -> \(?(.*?)\)? \{", text)
        return re.findall(r"tensor<[^>]*>", sig)

    assert results("push_st") == [f"tensor<{bucket.padded_len}xf32>"]
    assert results("push_pull_st") == [f"tensor<{bucket.padded_len}xf32>",
                                       f"tensor<{TOTAL}xf32>"]


def test_a_bucket_the_kernel_cannot_leave_a_vector_of_keeps_the_cut():
    """Up to 512 values the chip lays a vector out in one tile of its own
    length, which the kernel's blocks of whole tiles are not
    (``fused_update.lamb_apply_pulls``): the program cuts the store, as it
    does over several shards, and the count stays."""
    lens, keys = np.array([300, 212]), KEYS[:2]
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    rng = np.random.default_rng(13)
    init = (0.02 * rng.normal(size=512)).astype(np.float32)
    eng.register_dense("t", keys, lens=lens, init=init)
    ref = LambReference([init[:300], init[300:]], np.zeros(2, np.int32),
                        **parse_lamb_handle(HANDLE))
    g = rng.normal(size=(1, 512)).astype(np.float32)
    pulled = np.asarray(eng.push_pull("t", g))
    ref.step([g[:, :300], g[:, 300:]])
    assert pulled.shape == (512,) and _err(pulled, ref) < 2e-6
    assert (eng.lamb_updates, eng.kernel_pulls) == (1, 0)


# -- what is refused, each with a sentence -------------------------------------


def test_values_that_the_lens_do_not_sum_to_are_refused(cluster):
    kv = _worker(cluster, 1)
    with pytest.raises(log.CheckError, match="init must hold one value"):
        kv.register_dense("tree", KEYS, lens=LENS,
                          init=np.zeros(TOTAL - 1, np.float32))
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS)
    with pytest.raises(log.CheckError, match="bad grad len"):
        kv.push_pull(KEYS, np.zeros((1, TOTAL - 1), np.float32), None,
                     lens=LENS)
    with pytest.raises(log.CheckError, match="one length >= 0 for each"):
        kv.register_dense("short", KEYS, lens=LENS[:-1])
    with pytest.raises(log.CheckError, match="val_len .* or lens"):
        kv.register_dense("both", KEYS, 4, lens=LENS)
    with pytest.raises(log.CheckError, match="flags need per-key lens"):
        kv.register_dense("flags", KEYS, 4, flags=FLAGS)


def test_lamb_on_a_bucket_without_segments_is_refused_at_bind(cluster):
    kv = _worker(cluster, 1)
    keys = np.array([7, 8], dtype=np.uint64)
    kv.register_dense("flat", keys, 64)
    with pytest.raises(log.CheckError, match="needs the keys' own lengths"):
        kv.push_pull(keys, np.ones((1, 128), np.float32), None)
    assert kv.engine.lamb_updates == 0 and not kv.engine._programs
    # ... and where no bucket is carried at all.
    kv.register_dense("tree", KEYS, lens=LENS)
    with pytest.raises(log.CheckError, match="carries no bucket"):
        kv.engine.replay("tree", np.ones((2, 1, TOTAL), np.float32))
    # Another handle on the same bucket needs no segments.
    kv.engine.push_pull("flat", np.ones((1, 128), np.float32), "sum")


def test_a_call_with_other_lens_than_the_registered_is_refused(cluster):
    kv = _worker(cluster, 1)
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS)
    other = LENS.copy()
    other[0], other[1] = other[1], other[0]
    with pytest.raises(log.CheckError, match="registered with other lens"):
        kv.push_pull(KEYS, np.zeros((1, TOTAL), np.float32), None,
                     lens=other)
    assert kv.engine.push_bytes == 0


def test_a_uniform_bucket_routes_as_before(cluster):
    kv = _worker(cluster, 1)
    keys = np.array([10, 12, 14], dtype=np.uint64)
    kv.register_dense("three", keys, 4)
    assert kv.engine.bucket("three").lens is None
    assert kv.engine.bucket("three").padded_len == 12
    assert kv._engine_route(keys) == "three"
    # ``lens`` on a bucket of one ``val_len``: the message path's, as ever.
    assert kv._engine_route(keys, 0, np.array([4, 4, 4])) is None
    assert kv._engine_route(keys, 1) is None
    # ... and on a bucket that has them: the engine's, in the same lookup.
    kv.register_dense("tree", KEYS, lens=LENS)
    assert kv._engine_route(KEYS) == "tree"
    assert kv._engine_route(KEYS, 0, LENS) == "tree"
    assert kv._engine_route(KEYS, 0, LENS.astype(np.int32)) == "tree"
    assert kv.po.metrics.snapshot()["gauges"]["engine.dense.segments"] == 8


# -- what a whole tree in one bucket asks of KVWorker ---------------------------


def test_kept_device_results_are_bounded_by_bytes_beside_a_large_bucket(
        cluster, monkeypatch):
    """``get_pulled`` serves the last 8 results; eight pulled copies of a
    whole tree would not fit beside the tree.  Small results keep their
    window of 8 beside it, and the bound goes with the bucket."""
    kv = _worker(cluster, 1)
    small = np.array([7], dtype=np.uint64)
    kv.register_dense("small", small, lens=[64])
    assert not kv._results_heavy
    monkeypatch.setattr(KVWorker, "_DEVICE_RESULTS_BYTES", 3 * 4 * TOTAL)
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS)
    assert kv._results_heavy
    g = np.ones((1, TOTAL), np.float32)
    stamps = [kv.push_pull(KEYS, g, None) for _ in range(5)]
    for ts in stamps:
        kv.wait(ts)
    assert [kv.get_pulled(ts) is not None for ts in stamps] \
        == [False, False, True, True, True]
    # Seven small results in flight beside the newest tree: all are kept.
    one = np.ones((1, 64), np.float32)
    more = [kv.push_pull(small, one, None) for _ in range(7)]
    for ts in more:
        kv.wait(ts)
    assert all(kv.get_pulled(ts) is not None for ts in more + stamps[-1:])
    assert kv.get_pulled(stamps[-2]) is None
    # A small bucket registered in the tree's place lifts the bound.
    kv.register_dense("tree", KEYS, 4)
    assert not kv._results_heavy


def test_the_span_of_a_dense_op_names_the_handles_kind(cluster, monkeypatch):
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

    kv = _worker(cluster, 1)
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS)
    monkeypatch.setattr(kv_app, "tracing", lambda: True)   # a session runs
    monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
    ts = kv.push_pull(KEYS, np.ones((1, TOTAL), np.float32), None)
    kv.wait(ts)
    assert {"ts": ts, "name": "tree", "handle": "lamb"} in seen
