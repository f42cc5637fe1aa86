"""``lamb:lr,b1,b2,eps,wd``: a server handle that is not element-wise, on a
dense bucket registered with its keys' own lengths (``lens``).

Through ``KVWorker.push_pull`` on the engine path, against
``benchmark/lamb_reference.py`` (numpy, float64, imports nothing of the
program), on one shard and on the 4-shard CPU mesh, kernels interpreted.
The keys' borders lie on no tile's and no shard's: lengths 2, 3, 127, 128,
1,025, 30,522, and one key that spans three of four shards.  On one shard
every key that VMEM holds takes one pass (``fused_update.lamb_plan``): a
second tree of fourteen tiles has keys on either side of that cap, which
the tests bring down to a few tiles.
"""

import functools
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from pslite_tpu import KVServer, KVServerDefaultHandle, KVWorker  # noqa: E402
from pslite_tpu.ops import fused_update  # noqa: E402
from pslite_tpu.ops.fused_update import LAMB_TILE  # noqa: E402
from pslite_tpu.parallel.engine import (CollectiveEngine,  # noqa: E402
                                        KEY_NO_ADAPT, KEY_NO_DECAY)
from pslite_tpu.telemetry.metrics import Registry  # noqa: E402
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


@pytest.mark.parametrize("held_tiles", [128, 0])
@pytest.mark.parametrize("total", [
    2 * LAMB_TILE + 26428,      # ends in the middle of the last tile
    2 * LAMB_TILE,              # ends on a tile's border
    1000,                       # a bucket shorter than one tile
])
def test_lamb_apply_leaves_the_new_parameters_twice(monkeypatch, total,
                                                    held_tiles):
    """With ``pulled_len`` the kernel's last result is its first cut at
    that length, bit for bit, in a buffer of its own; the first is what
    the kernel without it stores, and the padding keeps its value.  In
    one pass (both keys held: the gradient in, m and v out besides) and
    as the second of two (no key held: m and v as ``lamb_moments`` left
    them), which agree."""
    import jax.numpy as jnp

    monkeypatch.setattr(fused_update, "LAMB_HELD_TILES", held_tiles)
    padded = -(-total // LAMB_TILE) * LAMB_TILE
    lens = np.array([total - 300, 300])
    starts = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    plan = fused_update.lamb_plan(starts, padded, 1)
    assert plan.held.all() == bool(held_tiles) == (not plan.tiles.size)
    rng = np.random.default_rng(total)
    store = rng.normal(size=padded).astype(np.float32)   # padding not zero
    m = rng.normal(size=padded).astype(np.float32)
    v = rng.random(size=padded).astype(np.float32)
    g = rng.normal(size=(1, total)).astype(np.float32)
    lr, step = 0.1, jnp.float32(3.0)
    decay, base = jnp.full(2, 0.01, jnp.float32), jnp.zeros(1, jnp.int32)
    keys = (jnp.asarray(starts), decay)
    mine = jnp.asarray(plan.blocks[0])
    # The two passes, as ``engine.py`` ``_lamb_fn`` chains them.
    every = jnp.arange(padded // LAMB_TILE, dtype=jnp.int32)
    m2, v2, sums = fused_update.lamb_moments(
        jnp.asarray(store), jnp.asarray(m), jnp.asarray(v), jnp.asarray(g),
        step, *keys, mine, base, every, interpret=True)
    sq = np.sqrt(np.asarray(sums).reshape(2, 2))
    scale = jnp.asarray(lr * sq[:, 0] / sq[:, 1], jnp.float32)
    second = (jnp.asarray(store), m2, v2, step, *keys, scale, mine, base)
    if held_tiles:
        kernel = functools.partial(
            fused_update.lamb_one_pass, jnp.asarray(store), jnp.asarray(m),
            jnp.asarray(v), jnp.asarray(g), step, *keys,
            jnp.zeros(2, jnp.float32), mine, base, jnp.ones(2, jnp.int32),
            jnp.ones(2, jnp.int32), jnp.asarray(plan.walked),
            jnp.asarray(plan.stepped), interpret=True, lr=lr, lag=plan.lag)
    else:
        kernel = functools.partial(fused_update.lamb_apply, *second,
                                   interpret=True)
    new_store, *moments, pulled = kernel(pulled_len=total)
    alone, *_, none = kernel()
    assert none is None and pulled.shape == (total,)
    assert pulled.unsafe_buffer_pointer() != new_store.unsafe_buffer_pointer()
    new_store = np.asarray(new_store)
    np.testing.assert_array_equal(new_store, np.asarray(alone))
    np.testing.assert_array_equal(np.asarray(pulled), new_store[:total])
    np.testing.assert_array_equal(new_store[total:], store[total:])
    assert np.abs(new_store[:total] - store[:total]).min() > 0
    # Either form leaves what the two passes leave: the same moments bit
    # for bit, the same store to the rounding of a ratio.
    assert len(moments) == (2 if held_tiles else 0)
    for mine_, theirs in zip(moments, (m2, v2)):
        np.testing.assert_array_equal(np.asarray(mine_)[:total],
                                      np.asarray(theirs)[:total])
    two, _ = fused_update.lamb_apply(*second, interpret=True)
    np.testing.assert_allclose(new_store, np.asarray(two), atol=2e-6, rtol=0)


@pytest.mark.parametrize("mesh_shape, from_kernel", [
    ((1,), True), ((4,), False), ((2, 1), True)])
def test_where_one_shard_holds_the_bucket_the_kernel_writes_the_pulled_values(
        mesh_shape, from_kernel):
    """``push_pull`` returns the store's values up to ``total_len`` bit for
    bit on either path; on one shard (under a worker axis too) they are
    the kernel's own second result, counted by ``engine.pull.from_kernel``;
    over four they are the gathered shards, and the count stays."""
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


@pytest.mark.parametrize("held_tiles, shards", [(128, 1), (0, 1), (128, 2)])
def test_a_push_alone_lowers_the_one_result_kernel(monkeypatch, held_tiles,
                                                   shards):
    """Whether the vector is made is a static argument of the one kernel:
    the program of ``push`` has ``lamb_apply`` without it, that of
    ``push_pull`` on one shard with it, its last result.  Where every key
    is held ``lamb_one_pass`` is the program's one kernel and has the
    store, m and v for results; where none is (a cap of no tile; two shards,
    whatever the cap) ``lamb_moments`` runs before it and it has the
    store alone, as it had."""
    import re

    monkeypatch.setattr(fused_update, "LAMB_HELD_TILES", held_tiles)
    eng = CollectiveEngine(mesh=_mesh(shards), server_handle=HANDLE)
    registry = Registry()
    eng.export(registry)
    bucket = eng.register_dense("t", KEYS, lens=LENS, flags=FLAGS)
    g = np.zeros((shards, TOTAL), np.float32)
    eng.push_pull("t", g)
    _, state = eng.opt_state("t")
    args = (eng.store_array("t"), *state,
            jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None))))
    one_pass = held_tiles > 0 and shards == 1
    assert registry.snapshot()["gauges"]["engine.update.lamb.one_pass"] == (
        TOTAL if one_pass else 0)

    def results(op):
        text = eng._program(op, bucket.padded_len, bucket.dtype, HANDLE,
                            bucket).lower(*args).as_text()
        assert ("@lamb_moments(" in text) == (not one_pass)
        assert one_pass or "all_reduce" in text     # the norms' psum
        # (The jitted wrapper's name; the custom call is ``lamb_apply``
        # either way: tests/test_compile_for_v5e.py.)
        wrapper = "lamb_one_pass" if one_pass else "lamb_apply"
        (sig,) = re.findall(
            r"func\.func private @%s\(.*?\) -> \(?(.*?)\)? \{" % wrapper,
            text)
        return re.findall(r"tensor<[^>]*>", sig)

    shard = [f"tensor<{bucket.padded_len // shards}xf32>"]
    assert results("push_st") == shard * (3 if one_pass else 1)
    assert results("push_pull_st") == shard * (3 if one_pass else 1) + (
        [f"tensor<{TOTAL}xf32>"] if shards == 1 else [])


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


# -- one pass over every key that VMEM holds ------------------------------------

# Fourteen tiles.  Runs of small keys (0; 5; 8; 10) between keys that reach
# into 2 (1, 9), 3 (4, 6), 4 (3) and 5 tiles (7).  Key 5 lies inside one
# tile and key 6 spans three.  Key 2 is excluded, key 10 adapted with a store
# of zeros.  With a cap of three tiles keys 3 and 7 are left to two passes,
# each between held keys, and tiles 0, 5-7 and 13 are walked by no first
# pass; keys 2 and 8 lie in walked tiles alone and go with them.  With a cap
# of one tile every tile is walked, so no key is held.  The gradient ends
# 60,479 values into the last tile.
LENS2 = np.array([300, 70000, 5, 200000, 140000, 40, 131072, 300000, 1000,
                  70000, 30])
FLAGS2 = np.array([0, 0, EXCLUDED, 0, 0, EXCLUDED, 0, 0, 0, 0, 0])
KEYS2 = np.arange(200, 200 + len(LENS2), dtype=np.uint64)
TOTAL2 = int(LENS2.sum())
STARTS2 = np.concatenate([[0], np.cumsum(LENS2)])
REACH2 = (STARTS2[1:] - 1) // LAMB_TILE - STARTS2[:-1] // LAMB_TILE + 1
# The values that take one pass, by the cap in tiles.
ONE_PASS2 = {128: TOTAL2, 3: TOTAL2 - 500000 - 1005, 1: 0, 0: 0}


def _split2(flat):
    return [np.asarray(flat)[..., STARTS2[k]:STARTS2[k + 1]]
            for k in range(len(LENS2))]


def _tree2(held_tiles, monkeypatch, rng, **kw):
    """An engine over one shard that holds ``LENS2`` under a cap of
    ``held_tiles``, its gauges, and the float64 recurrence beside it."""
    monkeypatch.setattr(fused_update, "LAMB_HELD_TILES", held_tiles)
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    registry = Registry()
    eng.export(registry)
    init = (0.02 * rng.normal(size=TOTAL2)).astype(np.float32)
    init[STARTS2[10]:] = 0.0
    bucket = eng.register_dense("t", KEYS2, lens=LENS2, flags=FLAGS2,
                                init=init, **kw)
    assert bucket.padded_len == 14 * LAMB_TILE
    # The programs of push_pull and of push, built under this cap.
    eng._bind("t", None, False)
    eng._bind("t", None, None)
    ref = LambReference(_split2(init), FLAGS2, **parse_lamb_handle(HANDLE))
    return eng, registry, ref


def test_the_second_tree_is_what_its_comment_says():
    assert list(REACH2) == [1, 2, 1, 4, 3, 1, 3, 5, 1, 2, 1]
    assert STARTS2[5] // LAMB_TILE == (STARTS2[6] - 1) // LAMB_TILE
    assert TOTAL2 - 13 * LAMB_TILE == 60479


@pytest.mark.parametrize("shards, held_tiles, held, tiles, stepped, lag", [
    # every key held: no first pass, the store four steps behind
    (1, 128, range(11), [], range(14), 4),
    # keys 3 and 7 over the cap: their tiles walked, with their neighbours'
    # (keys 2 and 8 lie in those tiles alone: the first pass has their sums)
    (1, 3, [0, 1, 4, 5, 6, 9, 10], [1, 2, 3, 4, 8, 9, 10, 11, 12],
     [0, 0, 0, 0, 0, 5, 6, 7, 7, 7, 7, 7, 7, 13], 2),
    # a cap of one tile: the longer keys' tiles are all there are
    (1, 1, [], range(14), [0] * 14, 0),
    # no key held: every tile walked, the second of two passes
    (1, 0, [], range(14), [0] * 14, 0),
    # two shards: a norm is a sum over them, whatever the cap
    (2, 128, [], range(7), [0] * 7, 0),
])
def test_lamb_plan_holds_the_keys_that_fit(monkeypatch, shards, held_tiles,
                                           held, tiles, stepped, lag):
    monkeypatch.setattr(fused_update, "LAMB_HELD_TILES", held_tiles)
    plan = fused_update.lamb_plan(STARTS2, 14 * LAMB_TILE, shards)
    assert list(np.flatnonzero(plan.held)) == list(held)
    assert list(plan.tiles) == list(np.flatnonzero(plan.walked)) \
        == list(tiles)
    assert list(plan.stepped) == list(stepped)
    assert plan.lag == lag
    assert plan.one_pass_len == int(LENS2[list(held)].sum()) \
        == (ONE_PASS2[held_tiles] if shards == 1 else 0)
    assert plan.blocks.shape == (shards, 2 * 14 // shards)
    # An empty key is no one's: never held, and walks nothing.
    empty = fused_update.lamb_plan(np.array([0, 0, 5, 5]), LAMB_TILE, 1)
    assert list(empty.held) == [False, held_tiles > 0, False]


OVER = (fused_update.LAMB_HELD_TILES + 1) * LAMB_TILE + 5


@pytest.mark.parametrize("lens, held", [
    # a small key before an embedding table: both in tile 0, all walked
    ([300, 31254528], []),
    ([300, OVER, 1000], []),            # ... and one behind, in its last
    ([70000, OVER, 70000], [0, 2]),     # each reaches a tile of its own
    ([OVER, 300, LAMB_TILE], [2]),      # 300 in walked tiles alone
    ([LAMB_TILE, OVER], [0]),           # borders on the tiles'
])
def test_a_held_key_has_a_tile_the_first_pass_does_not_walk(lens, held):
    """The one-pass kernel stores m and v of the tiles that are not walked
    and names such a tile for every walked one (``stepped``); the chip
    writes a block's window back whether or not the kernel stored to it.
    So a key in walked tiles alone is not held, and where every tile is
    walked none is: the program is the two passes."""
    starts = np.concatenate([[0], np.cumsum(lens)])
    padded = -(-starts[-1] // LAMB_TILE) * LAMB_TILE
    plan = fused_update.lamb_plan(starts, padded, 1)
    assert list(np.flatnonzero(plan.held)) == held
    assert plan.one_pass_len == sum(lens[k] for k in held)
    if held:
        assert not plan.walked[plan.stepped].any()
        assert (plan.stepped[plan.walked == 0]
                == np.flatnonzero(plan.walked == 0)).all()
    else:
        assert plan.walked.all() and plan.lag == 0


@pytest.mark.parametrize("lens", [[300, LAMB_TILE + 5], [300, LAMB_TILE, 9]])
def test_where_every_tile_is_walked_the_program_is_the_two_passes(
        monkeypatch, lens):
    """A small key and one over the cap that share tile 0 (the cap a tile):
    no key held, the gauge reads 0, both kernels in the program, and m and
    v after two steps are the recurrence's."""
    monkeypatch.setattr(fused_update, "LAMB_HELD_TILES", 1)
    lens = np.array(lens)
    total = int(lens.sum())
    cuts = np.cumsum(lens)[:-1]
    eng = CollectiveEngine(mesh=_mesh(1), server_handle=HANDLE)
    rng = np.random.default_rng(31)
    init = (0.02 * rng.normal(size=total)).astype(np.float32)
    bucket = eng.register_dense("t", KEYS[:len(lens)], lens=lens, init=init)
    ref = LambReference(np.split(init, cuts), np.zeros(len(lens), np.int32),
                        **parse_lamb_handle(HANDLE))
    for step in range(2):
        g = rng.normal(size=(1, total)).astype(np.float32)
        pulled = np.asarray(eng.push_pull("t", g))
        ref.step(np.split(g, cuts, axis=1))
        assert np.max(np.abs(pulled - np.concatenate(ref.p))) < 2e-6, step
    assert (eng.lamb_one_pass, eng.kernel_pulls) == (0, 2)
    _, (m, v, _) = eng.opt_state("t")
    np.testing.assert_allclose(np.asarray(m)[:total],
                               np.concatenate(ref.m), atol=1e-6)
    np.testing.assert_allclose(np.asarray(v)[:total],
                               np.concatenate(ref.v), atol=1e-6)
    assert not np.asarray(m)[total:].any()
    text = eng._program("push_pull_st", bucket.padded_len, bucket.dtype,
                        HANDLE, bucket).lower(
        eng.store_array("t"), *eng.opt_state("t")[1],
        jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))
    ).as_text()
    assert "@lamb_moments(" in text and "@lamb_apply(" in text


@pytest.mark.parametrize("held_tiles", [128, 3, 1, 0])
def test_the_keys_that_vmem_holds_take_one_pass(monkeypatch, held_tiles):
    """Three steps against the float64 recurrence and against two passes
    over every key (a cap of no tile), the file's tolerance for both: all
    keys held, keys over the cap between held ones (two short keys in
    their tiles going with them), a cap under which every tile is walked,
    none.  The pulled values are the store's bit for bit, the
    padding stays zero and enters no norm (behind the row's end the
    interpreter hands the kernel NaN), a zero-norm key takes a plain Adam
    step, and the gauge says how much of the tree went in one pass."""
    eng, registry, ref = _tree2(held_tiles, monkeypatch,
                                np.random.default_rng(21))
    two, _, _ = _tree2(0, monkeypatch, np.random.default_rng(21))
    rng = np.random.default_rng(22)
    for step in range(3):
        g = rng.normal(size=(1, TOTAL2)).astype(np.float32)
        sent = jax.device_put(g, NamedSharding(eng.mesh, P(eng.axis, None)))
        pulled = np.asarray(eng.push_pull("t", sent))
        ref.step(_split2(g))
        assert pulled.shape == (TOTAL2,)
        assert np.max(np.abs(pulled - np.concatenate(ref.p))) < 2e-6, step
        assert np.max(np.abs(pulled - np.asarray(two.push_pull("t", g)))) \
            < 2e-6, step
        np.testing.assert_array_equal(
            pulled, np.asarray(eng.store_array("t"))[:TOTAL2])
        if step == 0:
            assert np.abs(_split2(pulled)[10]).min() > 1e-3
    eng.push("t", g)            # the push alone takes the same pass
    ref.step(_split2(g))
    two.push("t", g)
    for got in (eng, two):
        store = np.asarray(got.store_array("t"))
        assert np.max(np.abs(store[:TOTAL2] - np.concatenate(ref.p))) < 2e-6
    _, (m, v, slot) = eng.opt_state("t")
    _, (m2, v2, _) = two.opt_state("t")
    np.testing.assert_array_equal(np.asarray(slot), 4.0)
    np.testing.assert_array_equal(np.asarray(m), np.asarray(m2))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v2))
    for vec in (eng.store_array("t"), m, v):
        assert not np.asarray(vec)[TOTAL2:].any()
    assert all(abs(r - 1.0) > 0.3 for r, f in zip(ref.ratios, FLAGS2)
               if not f)
    gauges = registry.snapshot()["gauges"]
    assert gauges["engine.update.lamb"] == 4
    assert gauges["engine.pull.from_kernel"] == 3
    assert gauges["engine.update.lamb.one_pass"] == ONE_PASS2[held_tiles]


@pytest.mark.parametrize("held_tiles", [128, 3, 0])
def test_a_mixed_bucket_takes_the_same_pass(monkeypatch, held_tiles):
    """bf16 rows in, a bf16 pulled vector out: the f32 store, m and v are
    bit for bit those of the f32 bucket handed the same gradients
    widened, and the pulled values the store's rounded to nearest-even."""
    import jax.numpy as jnp

    mixed, registry, _ = _tree2(held_tiles, monkeypatch,
                                np.random.default_rng(23),
                                dtype=np.float32, job_dtype=jnp.bfloat16)
    plain, _, _ = _tree2(held_tiles, monkeypatch, np.random.default_rng(23))
    rng = np.random.default_rng(24)
    for step in range(2):
        g = rng.normal(size=(1, TOTAL2)).astype(jnp.bfloat16)
        sent = jax.device_put(g, NamedSharding(mixed.mesh,
                                               P(mixed.axis, None)))
        pulled = mixed.push_pull("t", sent)
        plain.push_pull("t", g.astype(np.float32))
        assert pulled.dtype == jnp.bfloat16 and pulled.shape == (TOTAL2,)
        store = np.asarray(mixed.store_array("t"))
        np.testing.assert_array_equal(store,
                                      np.asarray(plain.store_array("t")))
        np.testing.assert_array_equal(
            np.asarray(pulled), store[:TOTAL2].astype(jnp.bfloat16))
    for a, b in zip(mixed.opt_state("t")[1], plain.opt_state("t")[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    gauges = registry.snapshot()["gauges"]
    assert gauges["engine.dense.narrow"] == 2
    assert gauges["engine.update.lamb.one_pass"] == ONE_PASS2[held_tiles]


def test_two_shards_keep_two_passes_and_agree_with_one(monkeypatch):
    """Over two shards a key's norm is a ``psum``: both kernels and the
    reduction between them, no key held (the gauge reads 0), and the same
    values as one shard's one pass within the file's tolerance."""
    one, _, ref = _tree2(128, monkeypatch, np.random.default_rng(25))
    two = CollectiveEngine(mesh=_mesh(2), server_handle=HANDLE)
    registry = Registry()
    two.export(registry)
    two.register_dense("t", KEYS2, lens=LENS2, flags=FLAGS2,
                       init=np.asarray(one.store_array("t"))[:TOTAL2])
    rng = np.random.default_rng(26)
    for step in range(2):
        g = rng.normal(size=(1, TOTAL2)).astype(np.float32)
        a = np.asarray(one.push_pull("t", g))
        b = np.asarray(two.push_pull(
            "t", np.concatenate([g, np.zeros_like(g)])))
        ref.step(_split2(g))
        assert np.max(np.abs(a - b)) < 2e-6, step
        assert np.max(np.abs(b - np.concatenate(ref.p))) < 2e-6, step
    assert registry.snapshot()["gauges"]["engine.update.lamb.one_pass"] == 0
    assert (one.lamb_one_pass, two.lamb_one_pass) == (TOTAL2, 0)
    assert (one.kernel_pulls, two.kernel_pulls) == (2, 0)


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
    assert {"ts": ts, "name": "tree", "op": "dense.push_pull",
            "handle": "lamb"} in seen
