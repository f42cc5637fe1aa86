"""``muon`` over four colocated servers: a bucket sharded on its keys'
borders, every matrix whole on one owner (``ops/muon.py`` ``owner_plan``,
``CollectiveEngine._lay_by_owners``), beside ``test_muon_handle.py``, whose
trees, reference and tolerances these tests take.

On four virtual CPU devices (as ``test_sparse_four_servers.py`` builds its
mesh) with W = 4 workers: the four rows are laid into the owners' order,
summed in f32 and scattered, each owner updates the keys it owns with state
of its own size, the new parameters are gathered and handed back in key
order at ``total_len``.  Two trees: ``TREE`` has a key of 77 values, so
its neighbours keep XLA's slices; ``APPLY_TREE`` lies on lane borders, so
every key takes the kernels.  Whatever leaves the bucket (``store_array``,
``opt_state``, ``pull``, a checkpoint) is in key order.

(A test waits for a step before it reads the engine: the Pallas TPU
interpreter's callbacks run jax operations of their own on the first
device, and those never start while the main thread dispatches beside
them.  The chip has no interpreter.)
"""

import json
import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from jax.sharding import Mesh  # noqa: E402

from pslite_tpu import checkpoint  # noqa: E402
from pslite_tpu.ops import muon  # noqa: E402
from pslite_tpu.parallel.engine import CollectiveEngine  # noqa: E402
from pslite_tpu.utils import logging as log  # noqa: E402

import test_muon_handle as one  # noqa: E402
from test_muon_handle import (ADAMW, APPLY_ADAMW, APPLY_FLAGS,  # noqa: E402
                              APPLY_KEYS, APPLY_LENS, APPLY_SHAPES,
                              APPLY_STARTS, APPLY_TOTAL, FLAGS, HANDLE, KEYS,
                              LENS, SHAPES, STARTS, TOTAL, cluster)

W = 4                       # servers = workers = shards

TREES = {
    "a_key_of_77": (KEYS, LENS, FLAGS, SHAPES, ADAMW, STARTS, TOTAL),
    "on_lane_borders": (APPLY_KEYS, APPLY_LENS, APPLY_FLAGS, APPLY_SHAPES,
                        APPLY_ADAMW, APPLY_STARTS, APPLY_TOTAL),
}


def _engine(shards=W, handle=HANDLE):
    return CollectiveEngine(mesh=one._mesh(shards), server_handle=handle)


def _register(eng, tree, init, name="t"):
    keys, lens, flags, shapes = TREES[tree][:4]
    return eng.register_dense(name, keys, lens=lens, flags=flags,
                              shapes=shapes, init=init)


def _full_size():
    with open(os.path.join(one.ROOT, "benchmark", "configs",
                           "moonlight-16b-muon-4chip.json")) as fh:
        cfg = json.load(fh)
    tensors = one.muon_flops.expand_shapes(cfg["tensors"])
    shapes = np.array([s for _, s in tensors])
    adamw = np.array([one.muon_flops.is_adamw(n, cfg["adamw_keys"])
                      for n, _ in tensors])
    return shapes, adamw


def _shapes(tree):
    if tree == "the_full_size_list":
        return _full_size()
    return TREES[tree][3], TREES[tree][4]


# -- four shards against the reference and against one shard ---------------------


@pytest.mark.parametrize("tree", list(TREES))
def test_four_workers_on_four_owners_equal_the_reference_and_one_shard(tree):
    keys, lens, flags, shapes, adamw, starts, total = TREES[tree]
    rng = np.random.default_rng(31)
    init = one._init(rng, total)
    eng, single = _engine(), _engine(1)
    _register(eng, tree, init)
    _register(single, tree, init)
    ref = one._reference(init, shapes, adamw, starts)
    for step in range(2):
        g = rng.normal(size=(W, total)).astype(np.float32)
        pulled = jax.block_until_ready(eng.push_pull("t", g))
        assert pulled.shape == (total,)             # key order, total_len
        before = one._step(ref, one._split(g, starts))
        one._hold(pulled, ref, before, adamw, starts, where=step)
        # The one-shard run of the same four rows, summed in another order:
        # an f32 sum's last place through one step (and 2e-3 of a Muon
        # step's size at most, where a rounding of X flips).
        alone = np.asarray(single.push_pull(
            "t", g.sum(axis=0, dtype=np.float32)[None]))
        for k, (got, want) in enumerate(zip(one._split(pulled, starts),
                                            one._split(alone, starts))):
            tol = 2e-7 if adamw[k] else 2e-5
            assert np.abs(np.asarray(got) - want).max() < tol, (step, k)
        # Identical on the four workers.
        copies = [np.asarray(s.data) for s in pulled.addressable_shards]
        assert len(copies) == W
        for copy in copies[1:]:
            np.testing.assert_array_equal(copy, copies[0])
    assert eng.bucket("t").owned is not None
    assert single.bucket("t").owned is None         # the identity: no plan


# -- the plan ------------------------------------------------------------------


@pytest.mark.parametrize("tree", [*TREES, "the_full_size_list"])
def test_every_matrix_is_whole_on_one_owner_and_the_owners_add_up(tree):
    shapes, adamw = _shapes(tree)
    lens = shapes[:, 0] * shapes[:, 1]
    starts = np.concatenate([[0], np.cumsum(lens)])
    owners = muon.owner_plan(shapes, adamw, W)
    segs = owners.segments
    # The runs of the key order cover the tree once, in order...
    assert segs[0, 0] == 0 and segs[-1, 0] + segs[-1, 2] == starts[-1]
    np.testing.assert_array_equal(segs[1:, 0], segs[:-1, 0] + segs[:-1, 2])
    # ... and land apart in the owners' order, inside the padded bucket.
    by_dst = segs[np.argsort(segs[:, 1])]
    assert (by_dst[1:, 1] >= by_dst[:-1, 1] + by_dst[:-1, 2]).all()
    assert by_dst[-1, 1] + by_dst[-1, 2] <= owners.padded_len
    for k in np.flatnonzero(~adamw):
        # A matrix: one run's worth, on one shard, where its slot says.
        s, _, _, slot = owners.where[k]
        lo = s * owners.shard_len + owners.starts[slot]
        assert lo + lens[k] <= (s + 1) * owners.shard_len
        run = segs[(segs[:, 0] <= starts[k])
                   & (starts[k] < segs[:, 0] + segs[:, 2])][0]
        assert run[1] + (starts[k] - run[0]) == lo
        assert run[0] + run[2] >= starts[k + 1]
        assert tuple(owners.shapes[slot]) == tuple(shapes[k])
    # The owners' slots hold each matrix once.
    held = owners.where[~adamw]
    assert len({(s, a, j) for s, a, j, _ in held.tolist()}) == len(held)
    if starts[-1] < 2 ** 24:            # every value, where that is small
        values = np.arange(starts[-1], dtype=np.float32)
        np.testing.assert_array_equal(
            muon.unplace(owners, muon.place(owners, values, np), np), values)


def test_the_owners_of_the_full_size_list_are_level():
    shapes, adamw = _full_size()
    owners = muon.owner_plan(shapes, adamw, W)
    assert owners.total_len == 568_484_352 and owners.matrices == 135
    assert owners.padded_len <= 1.10 * owners.total_len
    assert owners.flops.max() <= 1.2 * owners.flops.mean()
    assert owners.flops.sum() == muon.muon_plan(shapes, adamw).ns_flops
    lens = shapes[:, 0] * shapes[:, 1]
    least = 4 * int(lens[~adamw].sum()) + 8 * int(lens[adamw].sum())
    assert least <= owners.state_bytes <= 1.10 * least
    # Owners with the same keys left over share a branch of the program.
    assert len(owners.branches) < W


# -- the state an owner ------------------------------------------------------------


def test_the_state_is_an_owners_own_and_leaves_in_key_order():
    eng, single = _engine(), _engine(1)
    rng = np.random.default_rng(8)
    init = one._init(rng)
    _register(eng, "a_key_of_77", init)
    _register(single, "a_key_of_77", init)
    g = rng.normal(size=(W, TOTAL)).astype(np.float32)
    jax.block_until_ready(eng.push_pull("t", g))
    single.push_pull("t", g.sum(axis=0, dtype=np.float32)[None])
    owners = eng.bucket("t").owned
    muon_values, adamw_values = int(LENS[~ADAMW].sum()), int(LENS[ADAMW].sum())
    # 4 B a Muon value + 8 B an AdamW value + the plan's padding + a step
    # slot a shard, a quarter on each.
    assert eng.opt_state_nbytes("t") == owners.state_bytes + 4 * W
    assert owners.state_bytes >= 4 * muon_values + 8 * adamw_values
    for arr in eng._opt_states["t"]:
        shards = arr.addressable_shards
        assert len(shards) == W
        assert all(s.data.nbytes == arr.nbytes // W for s in shards)
    # The logical form is one shard's.
    kind, (mom, m, v, slot) = eng.opt_state("t")
    _, (mom1, m1, v1, _) = single.opt_state("t")
    assert (mom.shape, m.shape, v.shape) == ((muon_values,),
                                             (adamw_values,),
                                             (adamw_values,))
    np.testing.assert_allclose(np.asarray(mom), np.asarray(mom1), atol=1e-5)
    np.testing.assert_allclose(np.asarray(m), np.asarray(m1), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(slot), np.ones(W, np.float32))
    # So is the store, and a pull.
    store = np.asarray(eng.store_array("t"))
    assert store.shape == (TOTAL,)
    np.testing.assert_allclose(store, np.asarray(
        single.store_array("t"))[:TOTAL], atol=2e-5)
    np.testing.assert_array_equal(np.asarray(eng.pull("t")), store)


@pytest.mark.parametrize("backend", ["npz", "set_opt_state", "orbax"])
def test_save_and_restore_across_the_two_layouts(tmp_path, backend):
    """Saved from four owners, restored onto one shard, and back."""
    if backend == "orbax" and not checkpoint.have_orbax():
        pytest.skip("orbax is not installed")
    rng = np.random.default_rng(12)
    init = one._init(rng)
    grads = [rng.normal(size=(W, TOTAL)).astype(np.float32)
             for _ in range(3)]

    def carry(src, dst, tag):
        if backend == "npz":
            path = str(tmp_path / f"ckpt_{tag}")
            checkpoint.save_engine(src, path)
            checkpoint.restore_engine(dst, path)
        elif backend == "orbax":
            path = str(tmp_path / f"orbax_{tag}")
            checkpoint.save_engine_orbax(src, path)
            checkpoint.restore_engine_orbax(dst, path)
        else:
            kind, state = src.opt_state("t")
            dst.set_store_array("t", np.asarray(src.store_array("t"))[:TOTAL])
            dst.set_opt_state("t", kind, [np.asarray(s) for s in state])
        *states, steps = zip(dst.opt_state("t")[1], src.opt_state("t")[1])
        for got, want in states:            # momentum, m, v: key order
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        # The step: a slot a shard, of whichever mesh.
        assert np.asarray(steps[0])[0] == np.asarray(steps[1])[0]
        np.testing.assert_array_equal(
            np.asarray(dst.store_array("t"))[:TOTAL],
            np.asarray(src.store_array("t"))[:TOTAL])

    four = _engine()
    _register(four, "a_key_of_77", init)
    jax.block_until_ready(four.push_pull("t", grads[0]))
    alone = _engine(1)
    _register(alone, "a_key_of_77", np.zeros(TOTAL, np.float32))
    carry(four, alone, "four_to_one")
    assert alone.bucket("t").owned is None
    summed = grads[1].sum(axis=0, dtype=np.float32)[None]
    want = np.asarray(four.push_pull("t", grads[1]))
    got = np.asarray(alone.push_pull("t", summed))
    np.testing.assert_allclose(got, want, atol=2e-5)
    back = _engine()
    _register(back, "a_key_of_77", np.zeros(TOTAL, np.float32))
    carry(alone, back, "one_to_four")
    assert back.bucket("t").owned is not None
    np.testing.assert_allclose(np.asarray(back.push_pull("t", grads[2])),
                               np.asarray(four.push_pull("t", grads[2])),
                               atol=4e-5)
    np.testing.assert_array_equal(np.asarray(back.opt_state("t")[1][3]), 3.0)


# -- other handles, and what stays refused -----------------------------------------


@pytest.mark.parametrize("handle", ["adam:1e-2,0.9,0.999,1e-8",
                                    "lamb:1e-2,0.9,0.999,1e-6,0.01"])
def test_another_handle_over_four_shards_ignores_the_shapes(handle):
    rng = np.random.default_rng(3)
    init = one._init(rng)
    g = rng.normal(size=(W, TOTAL)).astype(np.float32)
    with_shapes, without = _engine(handle=handle), _engine(handle=handle)
    _register(with_shapes, "a_key_of_77", init)
    without.register_dense("t", KEYS, lens=LENS, flags=FLAGS * 0, init=init)
    with_shapes.register_dense("u", KEYS + 50, lens=LENS, flags=FLAGS * 0,
                               shapes=SHAPES, init=init)
    for _ in range(2):
        want = np.asarray(without.push_pull("t", g))
        np.testing.assert_array_equal(
            np.asarray(with_shapes.push_pull("u", g)), want)
    # A plan is made at registration; the store is laid by it only when a
    # handle that needs its keys whole takes the bucket.
    bucket = with_shapes.bucket("u")
    assert bucket.owner_plan is not None and bucket.owned is None
    assert bucket.padded_len == without.bucket("t").padded_len
    with pytest.raises(log.CheckError, match="already has .* state"):
        with_shapes.push_pull("u", g, HANDLE)
    assert bucket.owned is None


@pytest.mark.parametrize("case", ["no_shapes", "a_mixed_bucket",
                                  "a_bf16_store"])
def test_what_muon_still_refuses_over_four_shards(case):
    import jax.numpy as jnp

    kw, match = {
        "no_shapes": (dict(shapes=None), "needs each key's \\(rows, cols\\)"),
        "a_mixed_bucket": (dict(dtype=jnp.float32, job_dtype=jnp.bfloat16),
                           "pushed and pulled in bfloat16"),
        "a_bf16_store": (dict(dtype=jnp.bfloat16), "is kept in bfloat16"),
    }[case]
    eng = _engine()
    with pytest.raises(log.CheckError, match=match):
        one._register(eng, None, **kw)
        eng.push_pull("t", np.ones((W, TOTAL), kw.get(
            "job_dtype", kw.get("dtype", np.float32))))
    assert eng.muon_updates == 0 and not eng._opt_states
    assert eng.bucket("t").owned is None


def test_a_bucket_laid_by_its_owners_is_served_by_its_own_programs_alone():
    eng = _engine()
    _register(eng, "a_key_of_77", one._init(np.random.default_rng(1)))
    g = np.ones((W, TOTAL), np.float32)
    jax.block_until_ready(eng.push_pull("t", g))
    for handle in ("sum", "adam:1e-2,0.9,0.999,1e-8"):
        with pytest.raises(log.CheckError,
                           match="sharded on its keys' borders"):
            eng.push_pull("t", g, handle)
    with pytest.raises(log.CheckError, match="sharded on its keys' borders"):
        eng.register_pull_buffer("t")
    token = eng.push("t", g)                    # push alone, then pull
    token.block_until_ready()
    assert eng.muon_updates == 2
    assert np.asarray(eng.pull("t")).shape == (TOTAL,)


# -- through KVWorker, and what it says of itself --------------------------------------


def test_through_kvworker_one_launch_a_step_and_the_gauges(cluster,
                                                           monkeypatch):
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

    kv = one._worker(cluster, shards=W)
    rng = np.random.default_rng(17)
    init = one._init(rng)
    kv.register_dense("tree", KEYS, lens=LENS, flags=FLAGS, shapes=SHAPES,
                      init=init)
    ref = one._reference(init)
    monkeypatch.setattr(kv_app, "tracing", lambda: True)   # a session runs
    monkeypatch.setattr(kv_app, "TraceAnnotation", Span)
    for step in range(2):
        g = rng.normal(size=(W, TOTAL)).astype(np.float32)
        ts = kv.push_pull(KEYS, g, None)
        pulled = np.asarray(kv.get_pulled(ts))
        kv.wait(ts)
        before = one._step(ref, one._split(g))
        one._hold(pulled, ref, before, where=step)
    eng = kv.engine
    assert eng.muon_updates == 2                # one program a step
    owners = eng.bucket("tree").owned
    snap = kv.po.metrics.snapshot()["gauges"]
    assert snap["engine.update.muon"] == 2
    assert snap["engine.update.muon.matrices"] == int((~ADAMW).sum())
    assert snap["engine.update.muon.owners"] == W
    assert snap["engine.update.muon.owner_flops"] == round(
        1000 * owners.flops.max() / owners.flops.mean())
    assert snap["engine.dense.owned.pad_bytes"] == 4 * (
        owners.padded_len - TOTAL) > 0
    assert snap["engine.pull.from_kernel"] == 0     # the gather, placed
    assert {"ts": ts, "name": "tree", "op": "dense.push_pull",
            "handle": "muon", "owners": W} in seen


def test_the_program_names_its_two_placing_passes():
    eng = _engine()
    _register(eng, "on_lane_borders", None)
    eng._bind("t", None, False)                 # lays the bucket
    bucket = eng.bucket("t")
    eng._ensure_opt_state("t", "muon", bucket)
    prog = eng._program("push_pull_st", bucket.padded_len, bucket.dtype,
                        HANDLE, bucket)
    text = prog.lower(
        eng._stores["t"], *eng._opt_states["t"],
        np.zeros((W, APPLY_TOTAL), np.float32)).as_text(debug_info=True)
    for scope in ("ps.push.place", "ps.push.reduce", "ps.update",
                  "ps.pull.gather", "ps.pull.place"):
        assert scope in text, scope
    # One shard: the identity, and no placing pass.
    single = _engine(1)
    _register(single, "on_lane_borders", None)
    single._ensure_opt_state("t", "muon", single.bucket("t"))
    prog = single._program("push_pull_st", single.bucket("t").padded_len,
                           single.bucket("t").dtype, HANDLE,
                           single.bucket("t"))
    alone = prog.lower(
        single._stores["t"], *single._opt_states["t"],
        np.zeros((1, APPLY_TOTAL), np.float32)).as_text(debug_info=True)
    assert "ps.push.place" not in alone and "ps.pull.place" not in alone


def test_two_workers_over_two_owners_on_a_dp_kv_mesh():
    """W = 2 workers and S = 2 owners on separate axes: the placed row is
    cut at the owner's shard and summed over the worker axis."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh, axis_name="kv", worker_axis="dp",
                           server_handle=HANDLE)
    assert (eng.num_workers, eng.num_shards) == (2, 2)
    rng = np.random.default_rng(23)
    init = one._init(rng)
    _register(eng, "a_key_of_77", init)
    ref = one._reference(init)
    for step in range(2):
        g = rng.normal(size=(2, TOTAL)).astype(np.float32)
        pulled = jax.block_until_ready(eng.push_pull("t", g))
        before = one._step(ref, one._split(g))
        one._hold(pulled, ref, before, where=step)
    assert eng.bucket("t").owned.shards == 2
