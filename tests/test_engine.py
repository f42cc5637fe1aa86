"""CollectiveEngine / SparseEngine numerics on an 8-device virtual CPU mesh.

Validates that the ICI data plane reproduces the reference's server
aggregation semantics (push => sum across workers, pull => broadcast;
kv_app.h:430-452) as jitted reduce-scatter/all-gather collectives.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from pslite_tpu.parallel import CollectiveEngine, default_mesh
from pslite_tpu.parallel.sparse import SparseEngine


@pytest.fixture(scope="module")
def mesh():
    m = default_mesh()
    assert m.shape["kv"] == 8, "conftest must provide 8 virtual devices"
    return m


def test_dense_push_pull_aggregates(mesh):
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(4, dtype=np.uint64)
    val_len = 100  # total 400, not divisible by 8 -> exercises padding
    eng.register_dense("b0", keys, val_len)
    W = eng.num_shards
    base = np.arange(4 * val_len, dtype=np.float32)
    grads = np.stack([(w + 1) * base for w in range(W)])  # [W, total]
    pulled = np.asarray(eng.push_pull("b0", grads))
    expected = base * sum(range(1, W + 1))
    np.testing.assert_allclose(pulled, expected, rtol=1e-5)


def test_dense_push_accumulates_then_pull(mesh):
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(3, dtype=np.uint64)
    eng.register_dense("b1", keys, 64)
    ones = np.ones(3 * 64, dtype=np.float32)
    eng.push("b1", ones)  # broadcast to all 8 workers -> sum = 8
    eng.push("b1", ones)
    out = np.asarray(eng.pull("b1"))
    np.testing.assert_allclose(out, 16 * ones)


def test_dense_sgd_handle(mesh):
    eng = CollectiveEngine(mesh=mesh, server_handle="sgd:0.5")
    keys = np.arange(2, dtype=np.uint64)
    init = np.full(2 * 8, 10.0, dtype=np.float32)
    eng.register_dense("b2", keys, 8, init=init)
    grads = np.ones((8, 16), dtype=np.float32)  # sum = 8
    pulled = np.asarray(eng.push_pull("b2", grads))
    np.testing.assert_allclose(pulled, 10.0 - 0.5 * 8.0 * np.ones(16))


def test_fused_sgd_momentum_handle_parity(mesh):
    """The Pallas sgd+momentum kernel fused into the push program must
    match the host momentum recurrence over several steps."""
    lr, mu = 0.1, 0.9
    eng = CollectiveEngine(
        mesh=mesh, server_handle=f"sgd_momentum:{lr},{mu}"
    )
    keys = np.arange(3, dtype=np.uint64)
    val_len = 100  # padding exercised (300 % 8 != 0)
    init = np.linspace(1, 2, 3 * val_len).astype(np.float32)
    eng.register_dense("sgdm", keys, val_len, init=init)
    W = eng.num_shards
    rng = np.random.default_rng(7)

    ref_store = init.copy()
    ref_mom = np.zeros_like(ref_store)
    for step in range(4):
        grads = rng.normal(size=(W, 3 * val_len)).astype(np.float32)
        pulled = np.asarray(eng.push_pull("sgdm", grads))
        agg = grads.sum(axis=0)
        ref_mom = mu * ref_mom + agg
        ref_store = ref_store - lr * ref_mom
        np.testing.assert_allclose(pulled, ref_store, rtol=2e-5, atol=2e-5)


def test_fused_adam_handle_parity(mesh):
    """The Pallas Adam kernel (with bias correction via the step counter)
    must match the host Adam recurrence."""
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(2, dtype=np.uint64)
    val_len = 64
    init = np.full(2 * val_len, 5.0, np.float32)
    eng.register_dense("adam", keys, val_len, init=init)
    W = eng.num_shards
    rng = np.random.default_rng(11)

    ref_store = init.copy().astype(np.float64)
    ref_m = np.zeros_like(ref_store)
    ref_v = np.zeros_like(ref_store)
    for step in range(1, 4):
        grads = rng.normal(size=(W, 2 * val_len)).astype(np.float32)
        pulled = np.asarray(
            eng.push_pull("adam", grads, handle=f"adam:{lr}")
        )
        g = grads.sum(axis=0).astype(np.float64)
        ref_m = b1 * ref_m + (1 - b1) * g
        ref_v = b2 * ref_v + (1 - b2) * g * g
        alpha = lr * np.sqrt(1 - b2 ** step) / (1 - b1 ** step)
        ref_store = ref_store - alpha * ref_m / (np.sqrt(ref_v) + eps)
        np.testing.assert_allclose(pulled, ref_store, rtol=1e-4, atol=1e-4)


def test_fused_handle_push_then_pull(mesh):
    """Stateful handles work on the separate push/pull ops too, and the
    returned token is blockable."""
    eng = CollectiveEngine(mesh=mesh, server_handle="sgd_momentum:0.5,0.0")
    keys = np.arange(1, dtype=np.uint64)
    init = np.zeros(32, np.float32)
    eng.register_dense("tok", keys, 32, init=init)
    token = eng.push("tok", np.ones(32, np.float32))  # agg = 8
    token.block_until_ready()
    out = np.asarray(eng.pull("tok"))
    np.testing.assert_allclose(out, -0.5 * 8.0 * np.ones(32))


def test_fused_handle_kind_switch_rejected(mesh):
    eng = CollectiveEngine(mesh=mesh, server_handle="sgd_momentum")
    keys = np.arange(1, dtype=np.uint64)
    eng.register_dense("sw", keys, 16)
    eng.push("sw", np.ones(16, np.float32))
    with pytest.raises(Exception, match="cannot"):
        eng.push("sw", np.ones(16, np.float32), handle="adam")


def test_fused_handle_checkpoint_resume(mesh, tmp_path):
    """Optimizer state (momentum) survives save/restore: resuming after 2
    steps matches 4 uninterrupted steps."""
    from pslite_tpu import checkpoint

    handle = "sgd_momentum:0.1,0.9"
    keys = np.arange(2, dtype=np.uint64)
    val_len = 32
    init = np.ones(2 * val_len, np.float32)
    rng = np.random.default_rng(3)
    grads = [
        rng.normal(size=(8, 2 * val_len)).astype(np.float32)
        for _ in range(4)
    ]

    ref = CollectiveEngine(mesh=mesh, server_handle=handle)
    ref.register_dense("ck", keys, val_len, init=init)
    for g in grads:
        expected = np.asarray(ref.push_pull("ck", g))

    eng1 = CollectiveEngine(mesh=mesh, server_handle=handle)
    eng1.register_dense("ck", keys, val_len, init=init)
    for g in grads[:2]:
        eng1.push_pull("ck", g)
    path = str(tmp_path / "state")
    checkpoint.save_engine(eng1, path)

    eng2 = CollectiveEngine(mesh=mesh, server_handle=handle)
    eng2.register_dense("ck", keys, val_len, init=init)
    checkpoint.restore_engine(eng2, path)
    for g in grads[2:]:
        resumed = np.asarray(eng2.push_pull("ck", g))
    np.testing.assert_allclose(resumed, expected, rtol=1e-5, atol=1e-5)


def test_two_axis_mesh_decouples_workers_from_shards():
    """2-D (dp, kv) mesh: 2 worker rows x 4 server shards — the W != S
    asymmetry of the reference, on the collective path."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh2, worker_axis="dp")
    assert eng.num_workers == 2 and eng.num_shards == 4
    keys = np.arange(3, dtype=np.uint64)
    val_len = 40
    eng.register_dense("b2d", keys, val_len)
    rng = np.random.default_rng(21)
    grads = rng.normal(size=(2, 3 * val_len)).astype(np.float32)
    pulled = np.asarray(eng.push_pull("b2d", grads))
    np.testing.assert_allclose(pulled, grads.sum(axis=0), rtol=1e-5)

    # push-only + pull round trip accumulates.
    token = eng.push("b2d", grads)
    token.block_until_ready()
    out = np.asarray(eng.pull("b2d"))
    np.testing.assert_allclose(out, 2 * grads.sum(axis=0), rtol=1e-5)

    # Wrong worker-row count must fail loud, not silently drop rows —
    # including the pre-sharded device-array fast path.
    import jax

    bad_host = np.ones((4, eng.bucket("b2d").padded_len), np.float32)
    with pytest.raises(Exception, match="bad worker dim"):
        eng.push_pull("b2d", bad_host)
    bad_dev = jax.device_put(bad_host)
    with pytest.raises(Exception, match="bad worker dim"):
        eng.push_pull("b2d", bad_dev)


def test_push_pull_group_matches_singles(mesh):
    """One grouped program over several buckets == per-bucket push_pulls
    (same aggregation, one dispatch)."""
    eng_a = CollectiveEngine(mesh=mesh)
    eng_b = CollectiveEngine(mesh=mesh)
    rng = np.random.default_rng(9)
    names, glist = [], []
    for i, val_len in enumerate((40, 100, 16)):
        name = f"grp{i}"
        keys = np.arange(2, dtype=np.uint64) + 10 * i
        eng_a.register_dense(name, keys, val_len)
        eng_b.register_dense(name, keys, val_len)
        g = rng.normal(size=(8, 2 * val_len)).astype(np.float32)
        names.append(name)
        glist.append(g)
    grouped = eng_a.push_pull_group(names, glist)
    singles = [eng_b.push_pull(n, g) for n, g in zip(names, glist)]
    for got, want in zip(grouped, singles):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5
        )
    # Second grouped round accumulates in the stores like singles do.
    grouped2 = eng_a.push_pull_group(names, glist)
    singles2 = [eng_b.push_pull(n, g) for n, g in zip(names, glist)]
    for got, want in zip(grouped2, singles2):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-5
        )


def test_dense_bfloat16_bucket(mesh):
    """bfloat16 buckets (the MXU-native dtype) work through the fused
    push_pull path with tolerable precision."""
    import jax.numpy as jnp

    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(2, dtype=np.uint64)
    val_len = 64
    eng.register_dense("bf16", keys, val_len, dtype=jnp.bfloat16)
    W = eng.num_shards
    grads = np.ones((W, 2 * val_len), dtype=np.float32)
    pulled = np.asarray(eng.push_pull("bf16", grads), dtype=np.float32)
    np.testing.assert_allclose(pulled, float(W), rtol=1e-2)


def test_dense_init_roundtrip(mesh):
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(5, dtype=np.uint64)
    init = np.random.default_rng(1).normal(size=5 * 32).astype(np.float32)
    eng.register_dense("b3", keys, 32, init=init)
    np.testing.assert_allclose(np.asarray(eng.pull("b3")), init, rtol=1e-6)


def test_sparse_push_pull(mesh):
    eng = SparseEngine(mesh)
    rng = np.random.default_rng(7)
    num_rows, dim, n = 37, 4, 6
    eng.register_sparse("emb", num_rows, dim)
    W = eng.num_shards
    # Skewed indices with duplicates within and across workers.
    idx = rng.integers(0, num_rows, size=(W, n)).astype(np.int32)
    idx[:, 0] = 3  # hot row pushed by every worker
    grads = rng.normal(size=(W, n, dim)).astype(np.float32)

    eng.push("emb", idx, grads)

    # Host reference: scatter-add.
    ref = np.zeros((num_rows, dim), dtype=np.float32)
    for w in range(W):
        for i in range(n):
            ref[idx[w, i]] += grads[w, i]

    pulled = np.asarray(eng.pull("emb", idx))  # [W, n, dim]
    for w in range(W):
        np.testing.assert_allclose(pulled[w], ref[idx[w]], rtol=1e-4,
                                   atol=1e-5)


def test_sparse_pull_zero_init(mesh):
    eng = SparseEngine(mesh)
    eng.register_sparse("z", 16, 2)
    idx = np.zeros((8, 3), dtype=np.int32)
    out = np.asarray(eng.pull("z", idx))
    assert out.shape == (8, 3, 2)
    np.testing.assert_array_equal(out, 0)


def test_sparse_row_adagrad(mesh):
    """Fused row-wise Adagrad (DLRM embedding optimizer): per-row
    aggregate gradient -> accumulator += mean(G^2) -> row -= lr*G/
    (sqrt(acc)+eps); untouched rows unchanged; state persists across
    pushes."""
    eng = SparseEngine(mesh)
    rng = np.random.default_rng(11)
    num_rows, dim, n = 23, 4, 5
    init = rng.normal(size=(num_rows, dim)).astype(np.float32)
    eng.register_sparse("emb", num_rows, dim, init=init)
    W = eng.num_shards
    lr, eps = 0.1, 1e-8

    ref = init.copy().astype(np.float64)
    acc = np.zeros(num_rows, np.float64)
    for step in range(3):
        idx = rng.integers(0, num_rows, size=(W, n)).astype(np.int32)
        idx[:, 0] = 7  # hot row from every worker
        grads = rng.normal(size=(W, n, dim)).astype(np.float32)
        eng.push("emb", idx, grads, handle=f"row_adagrad:{lr},{eps}")

        G = np.zeros((num_rows, dim), np.float64)
        for w in range(W):
            for i in range(n):
                G[idx[w, i]] += grads[w, i]
        acc += np.mean(G ** 2, axis=1)
        denom = np.sqrt(acc)[:, None] + eps
        step_arr = np.where(denom > eps, lr * G / denom, 0.0)
        ref -= step_arr

    all_idx = np.tile(np.arange(num_rows, dtype=np.int32), (W, 1))
    pulled = np.asarray(eng.pull("emb", all_idx))[0]
    np.testing.assert_allclose(pulled, ref, rtol=1e-4, atol=1e-4)

    # Accumulator snapshot / restore roundtrip.
    snap = np.asarray(eng.acc_array("emb"))
    eng.set_acc_array("emb", snap)
    assert snap.shape == (eng.table("emb").rows_per_shard * W,)


def test_fused_adagrad_handle_parity(mesh):
    """The fused Adagrad kernel as a dense server handle must match the
    host recurrence (dense twin of the sparse row_adagrad)."""
    lr, eps = 0.05, 1e-8
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(3, dtype=np.uint64)
    val_len = 100
    init = np.linspace(-1, 1, 3 * val_len).astype(np.float32)
    eng.register_dense("ag", keys, val_len, init=init)
    W = eng.num_shards
    rng = np.random.default_rng(13)

    ref_store = init.copy().astype(np.float64)
    ref_acc = np.zeros_like(ref_store)
    for _ in range(4):
        grads = rng.normal(size=(W, 3 * val_len)).astype(np.float32)
        pulled = np.asarray(
            eng.push_pull("ag", grads, handle=f"adagrad:{lr},{eps}")
        )
        g = grads.sum(axis=0).astype(np.float64)
        ref_acc = ref_acc + g * g
        ref_store = ref_store - lr * g / (np.sqrt(ref_acc) + eps)
        np.testing.assert_allclose(pulled, ref_store, rtol=1e-4, atol=1e-4)


def test_sparse_group_ops_match_single(mesh):
    """push_group/pull_group over heterogeneous tables (different rows,
    dims, batch sizes) match per-table push/pull — one dispatch for the
    many-embedding-tables recommender pattern."""
    specs = {"a": (17, 4, 3), "b": (33, 8, 5), "c": (9, 2, 2)}
    rng = np.random.default_rng(21)

    grp = SparseEngine(mesh)
    one = SparseEngine(mesh)
    W = grp.num_shards
    data = {}
    for n, (rows, dim, nb) in specs.items():
        init = rng.normal(size=(rows, dim)).astype(np.float32)
        grp.register_sparse(n, rows, dim, init=init)
        one.register_sparse(n, rows, dim, init=init)
        idx = rng.integers(0, rows, size=(W, nb)).astype(np.int32)
        g = rng.normal(size=(W, nb, dim)).astype(np.float32)
        data[n] = (idx, g)

    names = list(specs)
    # Plain scatter-add group push.
    grp.push_group(names, [data[n][0] for n in names],
                   [data[n][1] for n in names])
    for n in names:
        one.push(n, *data[n])
    outs = grp.pull_group(names, [data[n][0] for n in names])
    for n, out in zip(names, outs):
        want = np.asarray(one.pull(n, data[n][0]))
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-5)

    # Row-adagrad group push (accumulators advance per table).
    grp.push_group(names, [data[n][0] for n in names],
                   [data[n][1] for n in names], handle="row_adagrad:0.1")
    for n in names:
        one.push(n, *data[n], handle="row_adagrad:0.1")
    for n in names:
        rows = specs[n][0]
        all_idx = np.broadcast_to(
            np.arange(rows, dtype=np.int32), (W, rows)
        )
        np.testing.assert_allclose(
            np.asarray(grp.pull(n, all_idx))[0],
            np.asarray(one.pull(n, all_idx))[0],
            rtol=1e-4, atol=1e-5, err_msg=n,
        )
        np.testing.assert_allclose(
            np.asarray(grp.acc_array(n)), np.asarray(one.acc_array(n)),
            rtol=1e-5, atol=1e-6, err_msg=n,
        )


def test_pinned_pull_buffer_address_identity(mesh):
    """PinMemory / w_pool_ analog (ucx_van.h:603-623): once a pull buffer
    is registered, every pull lands the gathered store at the SAME device
    addresses — the collective version of the reference's registered
    recv-buffer identity check (test_benchmark.cc:169-181)."""
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(4, dtype=np.uint64)
    eng.register_dense("pin0", keys, 64)  # total 256, divisible by 8
    eng.register_pull_buffer("pin0")

    def addrs(arr):
        return sorted(
            s.data.unsafe_buffer_pointer() for s in arr.addressable_shards
        )

    ones = np.ones(4 * 64, dtype=np.float32)
    eng.push("pin0", ones)  # each of 8 workers pushes ones -> sum = 8
    p1 = eng.pull("pin0")
    a1 = addrs(p1)
    np.testing.assert_allclose(np.asarray(p1), 8 * ones)
    eng.push("pin0", ones)
    p2 = eng.pull("pin0")
    a2 = addrs(p2)
    np.testing.assert_allclose(np.asarray(p2), 16 * ones)
    assert a1 == a2, f"pull output moved: {a1} vs {a2}"
    # A third pull without an intervening push: same address again.
    p3 = eng.pull("pin0")
    assert addrs(p3) == a1
    np.testing.assert_allclose(np.asarray(p3), 16 * ones)

    # Unregister restores plain (sliced, non-pinned) pulls.
    eng.unregister_pull_buffer("pin0")
    p4 = eng.pull("pin0")
    np.testing.assert_allclose(np.asarray(p4), 16 * ones)


def test_pinned_pull_padded_bucket(mesh):
    """Padding: the pinned buffer is padded-length; values beyond
    total_len are gather artifacts the caller ignores."""
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(3, dtype=np.uint64)
    eng.register_dense("pin1", keys, 33)  # total 99 -> padded 104
    eng.register_pull_buffer("pin1")
    base = np.arange(99, dtype=np.float32)
    grads = np.stack([base for _ in range(eng.num_shards)])
    eng.push("pin1", grads)
    pulled = eng.pull("pin1")
    assert pulled.shape[0] == eng._buckets["pin1"].padded_len
    np.testing.assert_allclose(
        np.asarray(pulled)[:99], 8 * base, rtol=1e-6
    )


def test_replay_matches_sequential_push_pull(mesh):
    """T fused scan steps must equal T separate push_pull dispatches,
    per step, for a stateless handle."""
    keys = np.arange(3, dtype=np.uint64)
    val_len = 100  # padded
    rng = np.random.default_rng(31)
    W = 8
    T = 4
    seq = rng.normal(size=(T, W, 3 * val_len)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh)
    ref.register_dense("rp_ref", keys, val_len)
    expected = [np.asarray(ref.push_pull("rp_ref", seq[t]))
                for t in range(T)]

    eng = CollectiveEngine(mesh=mesh)
    eng.register_dense("rp", keys, val_len)
    pulled = np.asarray(eng.replay("rp", seq))
    assert pulled.shape == (T, 3 * val_len)
    for t in range(T):
        np.testing.assert_allclose(pulled[t], expected[t], rtol=1e-5)
    # Store state advanced identically: one more single step agrees.
    extra = rng.normal(size=(W, 3 * val_len)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(eng.push_pull("rp", extra)),
        np.asarray(ref.push_pull("rp_ref", extra)),
        rtol=1e-5,
    )


def test_replay_keep_last_and_broadcast_grads(mesh):
    """keep='last' returns only the final pull; [T, total] grads
    broadcast to all workers like the single-step path."""
    keys = np.arange(2, dtype=np.uint64)
    eng = CollectiveEngine(mesh=mesh)
    eng.register_dense("rpl", keys, 64)
    T = 5
    seq = np.ones((T, 2 * 64), dtype=np.float32)
    out = np.asarray(eng.replay("rpl", seq, keep="last"))
    # Each step adds sum-over-8-workers of ones.
    np.testing.assert_allclose(out, T * 8 * np.ones(128, np.float32))


def test_replay_stateful_adam(mesh):
    """Replay threads optimizer state through the scan: must match the
    same steps dispatched one by one."""
    keys = np.arange(2, dtype=np.uint64)
    val_len = 64
    rng = np.random.default_rng(33)
    T = 3
    seq = rng.normal(size=(T, 8, 2 * val_len)).astype(np.float32)
    init = np.linspace(0, 1, 2 * val_len).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh, server_handle="adam:0.01")
    ref.register_dense("ra_ref", keys, val_len, init=init)
    expected = [np.asarray(ref.push_pull("ra_ref", seq[t]))
                for t in range(T)]

    eng = CollectiveEngine(mesh=mesh, server_handle="adam:0.01")
    eng.register_dense("ra", keys, val_len, init=init)
    pulled = np.asarray(eng.replay("ra", seq))
    for t in range(T):
        np.testing.assert_allclose(pulled[t], expected[t],
                                   rtol=2e-5, atol=2e-5)


def test_replay_two_axis_mesh():
    """Replay on a 2-D (dp, kv) mesh: worker reduction over dp inside
    the scan."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh2, worker_axis="dp")
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("rp2d", keys, 40)
    rng = np.random.default_rng(35)
    T = 3
    seq = rng.normal(size=(T, 2, 80)).astype(np.float32)
    pulled = np.asarray(eng.replay("rp2d", seq))
    acc = np.zeros(80, np.float32)
    for t in range(T):
        acc = acc + seq[t].sum(axis=0)
        np.testing.assert_allclose(pulled[t], acc, rtol=1e-5)


def test_two_axis_stateful_fused_handles():
    """Stateful (fused optimizer) handles on a 2-D (dp, kv) mesh — the
    dp-psum aggregation feeding the Pallas optimizer pass, state sharded
    over kv / replicated over dp.  Must match the recurrence step for
    step."""
    from pslite_tpu.parallel.mesh import make_mesh

    lr, mu = 0.1, 0.9
    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh2, worker_axis="dp",
                           server_handle=f"sgd_momentum:{lr},{mu}")
    keys = np.arange(3, dtype=np.uint64)
    val_len = 100
    init = np.linspace(1, 2, 3 * val_len).astype(np.float32)
    eng.register_dense("st2", keys, val_len, init=init)
    rng = np.random.default_rng(47)

    ref_store = init.copy()
    ref_mom = np.zeros_like(ref_store)
    for _ in range(3):
        grads = rng.normal(size=(2, 3 * val_len)).astype(np.float32)
        pulled = np.asarray(eng.push_pull("st2", grads))
        agg = grads.sum(axis=0)
        ref_mom = mu * ref_mom + agg
        ref_store = ref_store - lr * ref_mom
        np.testing.assert_allclose(pulled, ref_store, rtol=2e-5, atol=2e-5)


def test_two_axis_adam_replay():
    """Stateful replay on a 2-D mesh: adam state threaded through the
    scan with the dp-psum reduction."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    keys = np.arange(2, dtype=np.uint64)
    val_len = 64
    init = np.linspace(0, 1, 2 * val_len).astype(np.float32)
    rng = np.random.default_rng(49)
    T = 3
    seq = rng.normal(size=(T, 2, 2 * val_len)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh2, worker_axis="dp",
                           server_handle="adam:0.01")
    ref.register_dense("ar_ref", keys, val_len, init=init)
    expected = [np.asarray(ref.push_pull("ar_ref", seq[t]))
                for t in range(T)]

    eng = CollectiveEngine(mesh=mesh2, worker_axis="dp",
                           server_handle="adam:0.01")
    eng.register_dense("ar", keys, val_len, init=init)
    pulled = np.asarray(eng.replay("ar", seq))
    for t in range(T):
        np.testing.assert_allclose(pulled[t], expected[t],
                                   rtol=2e-5, atol=2e-5)


def test_two_axis_push_pull_group():
    """Grouped dispatch on a 2-D mesh must match per-bucket singles —
    the W != S decoupling covers the model-step group path."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    eng = CollectiveEngine(mesh=mesh2, worker_axis="dp")
    ref = CollectiveEngine(mesh=mesh2, worker_axis="dp")
    rng = np.random.default_rng(51)
    names, grads_list = [], []
    for i, val_len in enumerate((40, 700, 256)):
        name = f"gb{i}"
        keys = np.arange(2, dtype=np.uint64)
        eng.register_dense(name, keys, val_len)
        ref.register_dense(name, keys, val_len)
        names.append(name)
        grads_list.append(
            rng.normal(size=(2, 2 * val_len)).astype(np.float32)
        )
    grouped = eng.push_pull_group(names, grads_list)
    for name, g, out in zip(names, grads_list, grouped):
        want = np.asarray(ref.push_pull(name, g))
        np.testing.assert_allclose(np.asarray(out), want,
                                   rtol=1e-5, atol=1e-5)


def test_push_pull_stream_matches_sequential(mesh):
    """push_pull_stream (background-staged host transfers) must produce
    exactly the sequence of results that per-op push_pull does."""
    keys = np.arange(2, dtype=np.uint64)
    val_len = 100
    rng = np.random.default_rng(53)
    T = 5
    seq = [rng.normal(size=(8, 2 * val_len)).astype(np.float32)
           for _ in range(T)]

    ref = CollectiveEngine(mesh=mesh)
    ref.register_dense("ps_ref", keys, val_len)
    expected = [np.asarray(ref.push_pull("ps_ref", g)) for g in seq]

    eng = CollectiveEngine(mesh=mesh)
    eng.register_dense("ps", keys, val_len)
    outs = [np.asarray(o)
            for o in eng.push_pull_stream("ps", iter(seq), depth=2)]
    assert len(outs) == T
    for got, want in zip(outs, expected):
        np.testing.assert_allclose(got, want, rtol=1e-5)

    # Early abandonment must not wedge the stager thread.
    gen = eng.push_pull_stream("ps", iter(seq), depth=1)
    next(gen)
    gen.close()


def test_resnet_trace_host_origin_overlap(mesh):
    """Host-origin trace replay (serial and overlapped staging) runs and
    moves the advertised bytes."""
    from pslite_tpu.models.resnet_trace import replay

    eng = CollectiveEngine(mesh=mesh)
    for overlap in (False, True):
        nbytes, dt = replay(eng, steps=1, bucket_bytes=16 << 20,
                            host_origin=True, overlap=overlap)
        assert nbytes > 100 << 20 and dt > 0


def test_push_pull_stream_overlaps_staging_latency(mesh):
    """The stream pipeline must PIPELINE: the stager thread pulls (and
    stages) item i+1 while the consumer is still working on item i.

    Asserted structurally (event ordering), not by wall-clock margins —
    on a contended 1-vCPU host the CPU-bound legs can't overlap each
    other, so timing-based assertions are inherently flaky; what the
    pipeline guarantees on ANY host is that source latency (the
    transfer leg) runs concurrently with consumption."""
    import time

    keys = np.arange(1, dtype=np.uint64)
    val_len = 1024
    eng = CollectiveEngine(mesh=mesh)
    eng.register_dense("ov", keys, val_len)
    g = np.ones(val_len, np.float32)
    T = 4
    hold = 0.15  # how long the consumer keeps each result

    pulled_at = []
    done_at = []

    def source():
        for i in range(T):
            pulled_at.append(time.perf_counter())
            yield g

    for out in eng.push_pull_stream("ov", source(), depth=2):
        np.asarray(out)
        time.sleep(hold)  # consumer-side work on this result
        done_at.append(time.perf_counter())

    assert len(pulled_at) == len(done_at) == T
    # Pipelining: the stager asked the source for item i+1 while the
    # consumer was still holding item i (i.e. before done_at[i]).  A
    # serial implementation would only pull i+1 after the consumer
    # finished i.
    for i in range(T - 1):
        assert pulled_at[i + 1] < done_at[i], (
            f"no pipelining at step {i}: pull(i+1)="
            f"{pulled_at[i + 1]:.3f} >= done(i)={done_at[i]:.3f}"
        )


def test_push_pull_zero_copy_single_device():
    """In-place pull delivery on a degenerate gather (kv axis size 1):
    values match the copying path, the returned array IS the store, and
    the next mutating op invalidates stale holders (the reference's
    RegisterRecvBuffer contract: the next pull overwrites the registered
    buffer in place, rdma_van.h:520-548)."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh1 = make_mesh((1,), ("kv",))
    keys = np.arange(3, dtype=np.uint64)
    rng = np.random.default_rng(71)
    g1 = rng.normal(size=(1, 300)).astype(np.float32)
    g2 = rng.normal(size=(1, 300)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh1)
    ref.register_dense("zr", keys, 100)
    exp1 = np.asarray(ref.push_pull("zr", g1))
    exp2 = np.asarray(ref.push_pull("zr", g2))

    eng = CollectiveEngine(mesh=mesh1)
    eng.register_dense("zc", keys, 100)
    out1 = eng.push_pull("zc", g1, zero_copy=True)
    assert out1 is eng._stores["zc"]  # aliases, no gather copy
    np.testing.assert_allclose(np.asarray(out1), exp1, rtol=1e-5)
    out2 = eng.push_pull("zc", g2, zero_copy=True)
    np.testing.assert_allclose(np.asarray(out2), exp2, rtol=1e-5)
    # out1's buffer was donated into the second step: stale holders see
    # a deleted array (clear error), never torn data.
    assert out1.is_deleted()


def test_push_pull_zero_copy_falls_back_multi_device(mesh):
    """On a real multi-shard gather zero_copy degrades to the copying
    path: correct values, prior results stay live."""
    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("zf", keys, 64)
    ones = np.ones((8, 128), dtype=np.float32)
    out1 = eng.push_pull("zf", ones, zero_copy=True)
    out2 = eng.push_pull("zf", ones, zero_copy=True)
    np.testing.assert_allclose(np.asarray(out1), 8 * np.ones(128))
    np.testing.assert_allclose(np.asarray(out2), 16 * np.ones(128))
    assert not out1.is_deleted()


def test_push_pull_zero_copy_stateful():
    """Stateful handles ride the same in-place delivery."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh1 = make_mesh((1,), ("kv",))
    keys = np.arange(2, dtype=np.uint64)
    init = np.linspace(0, 1, 128).astype(np.float32)
    rng = np.random.default_rng(73)
    seq = rng.normal(size=(3, 1, 128)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh1, server_handle="adam:0.01")
    ref.register_dense("sr", keys, 64, init=init)
    eng = CollectiveEngine(mesh=mesh1, server_handle="adam:0.01")
    eng.register_dense("sz", keys, 64, init=init)
    for t in range(3):
        exp = np.asarray(ref.push_pull("sr", seq[t]))
        got = eng.push_pull("sz", seq[t], zero_copy=True)
        assert got is eng._stores["sz"]
        np.testing.assert_allclose(np.asarray(got), exp,
                                   rtol=2e-5, atol=2e-5)


def test_replay_flat_slab_matches_sequential(mesh):
    """The flat [W, T*padded] slab layout (large per-step payloads, see
    _flat_replay) must reproduce the stacked layout's numerics for every
    keep mode and input form."""
    keys = np.arange(3, dtype=np.uint64)
    val_len = 100
    rng = np.random.default_rng(75)
    W, T = 8, 4
    seq = rng.normal(size=(T, W, 3 * val_len)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh)
    ref.register_dense("fr", keys, val_len)
    expected = [np.asarray(ref.push_pull("fr", seq[t])) for t in range(T)]

    eng = CollectiveEngine(mesh=mesh)
    eng.replay_flat_min_bytes = 4  # force the slab layout on tiny buckets
    eng.register_dense("ff", keys, val_len)
    assert eng._flat_replay(eng.bucket("ff").padded_len, np.float32,
                            False, 4)
    pulled = np.asarray(eng.replay("ff", seq))
    assert pulled.shape == (T, 3 * val_len)
    for t in range(T):
        np.testing.assert_allclose(pulled[t], expected[t], rtol=1e-5)

    # keep="last" + broadcast [T, total] form on a fresh engine.
    eng2 = CollectiveEngine(mesh=mesh)
    eng2.replay_flat_min_bytes = 4
    eng2.register_dense("fb", keys, val_len)
    bseq = np.ones((5, 3 * val_len), dtype=np.float32)
    out = np.asarray(eng2.replay("fb", bseq, keep="last"))
    np.testing.assert_allclose(out, 5 * 8 * np.ones(300, np.float32))


def test_replay_zero_copy_last_single_device():
    """replay(keep='last', zero_copy=True) on a 1-device mesh skips the
    final gather: result aliases the store and matches T sequential
    steps."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh1 = make_mesh((1,), ("kv",))
    keys = np.arange(2, dtype=np.uint64)
    rng = np.random.default_rng(77)
    T = 4
    seq = rng.normal(size=(T, 1, 128)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh1)
    ref.register_dense("zl_ref", keys, 64)
    for t in range(T):
        exp = np.asarray(ref.push_pull("zl_ref", seq[t]))

    eng = CollectiveEngine(mesh=mesh1)
    eng.register_dense("zl", keys, 64)
    out = eng.replay("zl", seq, keep="last", zero_copy=True)
    assert out is eng._stores["zl"]
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-5)


def test_three_axis_torus_parity():
    """3-D torus (dp, kv1, kv2): store sharded over BOTH kv axes, the
    worker sum a psum along dp, pulled broadcast gathered over both kv
    axes (VERDICT r03 missing #4)."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh3 = make_mesh((2, 2, 2), ("dp", "kv1", "kv2"))
    keys = np.arange(3, dtype=np.uint64)
    val_len = 101  # total 303: not divisible by 4 -> padding path
    rng = np.random.default_rng(91)
    g = rng.normal(size=(2, 303)).astype(np.float32)

    eng = CollectiveEngine(mesh=mesh3, axis_name=("kv1", "kv2"),
                           worker_axis="dp")
    assert eng.num_shards == 4
    eng.register_dense("t3", keys, val_len)
    assert eng.bucket("t3").padded_len > eng.bucket("t3").total_len
    np.testing.assert_allclose(np.asarray(eng.push_pull("t3", g)),
                               g.sum(axis=0), rtol=1e-4, atol=1e-4)


def test_three_axis_torus_stateful_and_replay():
    """Stateful handles + replay on the 3-D torus match a 1-D reference
    engine step for step."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh3 = make_mesh((2, 2, 2), ("dp", "kv1", "kv2"))
    mesh1 = default_mesh()
    keys = np.arange(2, dtype=np.uint64)
    rng = np.random.default_rng(93)
    T = 3
    # 1-D reference: 8 workers; 3-D: 2 workers — use grads that sum the
    # same: each of the 2 dp rows carries 4x the base row.
    base = rng.normal(size=(T, 128)).astype(np.float32)
    seq3 = np.stack([np.stack([4 * b, 4 * b]) for b in base])  # [T,2,128]
    seq1 = np.stack([np.stack([b] * 8) for b in base])         # [T,8,128]

    ref = CollectiveEngine(mesh=mesh1, server_handle="adam:0.01")
    ref.register_dense("r1", keys, 64)
    eng = CollectiveEngine(mesh=mesh3, axis_name=("kv1", "kv2"),
                           worker_axis="dp", server_handle="adam:0.01")
    eng.register_dense("r3", keys, 64)
    exp = np.asarray(ref.replay("r1", seq1, keep="last"))
    got = np.asarray(eng.replay("r3", seq3, keep="last"))
    np.testing.assert_allclose(got, exp, rtol=2e-5, atol=2e-5)


def test_tuple_axis_without_worker_axis_colocated():
    """A composite kv axis with no worker axis: the 1-D colocated
    semantics hold (workers = product of the axes)."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh3 = make_mesh((2, 4), ("kv1", "kv2"))
    eng = CollectiveEngine(mesh=mesh3, axis_name=("kv1", "kv2"))
    assert eng.num_shards == 8
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("c2", keys, 64)
    rng = np.random.default_rng(95)
    g = rng.normal(size=(8, 128)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(eng.push_pull("c2", g)), g.sum(axis=0), rtol=1e-5
    )


def test_three_axis_torus_reshard():
    """The elastic tier handles composite kv axes: a (2,2,2)-torus
    engine reshards onto a 1-D mesh and back without losing state."""
    from pslite_tpu.parallel.mesh import make_mesh

    mesh3 = make_mesh((2, 2, 2), ("dp", "kv1", "kv2"))
    eng = CollectiveEngine(mesh=mesh3, axis_name=("kv1", "kv2"),
                           worker_axis="dp")
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("rs3", keys, 64)
    ones = np.ones((2, 128), np.float32)
    eng.push_pull("rs3", ones)  # store = 2

    mesh2 = make_mesh((2, 4), ("dp", "kv"))
    eng.reshard(mesh2, axis_name="kv")
    assert eng.num_shards == 4
    np.testing.assert_allclose(np.asarray(eng.pull("rs3"))[:128],
                               2 * np.ones(128))
    eng.reshard(mesh3, axis_name=("kv1", "kv2"))
    assert eng.num_shards == 4
    np.testing.assert_allclose(
        np.asarray(eng.push_pull("rs3", ones)), 4 * np.ones(128)
    )


def test_replay_flat_odd_step_count(mesh):
    """Non-power-of-two T exercises the unrolled bulk + tail split of
    the flat replay scan (both keep modes match sequential steps)."""
    keys = np.arange(2, dtype=np.uint64)
    val_len = 64
    rng = np.random.default_rng(97)
    T = 7  # bulk 4 + tail 3 at U=4 (min-bytes lowered below)
    seq = rng.normal(size=(T, 8, 128)).astype(np.float32)

    ref = CollectiveEngine(mesh=mesh)
    ref.register_dense("od_ref", keys, val_len)
    expected = [np.asarray(ref.push_pull("od_ref", seq[t]))
                for t in range(T)]

    eng = CollectiveEngine(mesh=mesh)
    eng.replay_flat_min_bytes = 4
    eng.register_dense("od", keys, val_len)
    assert eng._replay_unroll(eng.bucket("od").padded_len,
                              np.float32, T) == 4
    pulled = np.asarray(eng.replay("od", seq))
    for t in range(T):
        np.testing.assert_allclose(pulled[t], expected[t], rtol=1e-5)

    eng2 = CollectiveEngine(mesh=mesh)
    eng2.replay_flat_min_bytes = 4
    eng2.register_dense("od2", keys, val_len)
    out = np.asarray(eng2.replay("od2", seq, keep="last"))
    np.testing.assert_allclose(out, expected[-1], rtol=1e-5)


def test_sparse_adagrad_segment_sum_matches_dense_reference(mesh):
    """The O(batch) segment-sum adagrad (packed-layout path) must match
    the dense [R, d]-aggregate recurrence exactly, including DUPLICATE
    rows within and across workers (the segment sum exists to combine
    them before squaring)."""
    import jax.numpy as jnp

    from pslite_tpu.parallel.sparse import (
        SparseEngine,
        _adagrad_rows,
        _deinterleave_rows,
    )

    rows, dim, lr, eps = 37, 4, 0.1, 1e-8
    rng = np.random.default_rng(101)
    se = SparseEngine(mesh)
    se.register_sparse("sa", rows, dim)
    assert se.table("sa").pack == 32  # the packed layout is in play

    # Host reference: dense-aggregate recurrence over global rows.
    ref_store = np.zeros((rows, dim), np.float64)
    ref_acc = np.zeros(rows, np.float64)
    for step in range(3):
        # Heavy collisions: 8 workers x 6 entries over 37 rows, plus a
        # forced shared hot row.
        idx = rng.integers(0, rows, size=(8, 6)).astype(np.int32)
        idx[:, 0] = 5
        g = rng.normal(size=(8, 6, dim)).astype(np.float32)
        se.push("sa", idx, g, handle=f"row_adagrad:{lr},{eps}")
        se.block("sa")
        G = np.zeros((rows, dim), np.float64)
        np.add.at(G, idx.reshape(-1), g.reshape(-1, dim).astype(np.float64))
        ref_acc = ref_acc + np.mean(G ** 2, axis=1)
        ref_store = ref_store - lr * G / (np.sqrt(ref_acc)[:, None] + eps)

    got = np.asarray(
        se.pull("sa", np.tile(np.arange(rows, dtype=np.int32), (8, 1)))
    )[0]
    np.testing.assert_allclose(got, ref_store, rtol=1e-4, atol=1e-4)
    t = se.table("sa")
    acc = _deinterleave_rows(
        np.asarray(se.acc_array("sa")), rows, t.rows_per_shard,
        se.num_shards,
    )
    np.testing.assert_allclose(acc, ref_acc, rtol=1e-4, atol=1e-4)
    # Anchor the retained dense reference recurrence to the same host
    # model with NONZERO gradients (one step).
    G1 = jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))
    s2, a2 = _adagrad_rows(jnp.zeros((rows, dim)), jnp.zeros(rows),
                           G1, lr, eps)
    Gh = np.asarray(G1, np.float64)
    ah = np.mean(Gh ** 2, axis=1)
    sh = -lr * Gh / (np.sqrt(ah)[:, None] + eps)
    np.testing.assert_allclose(np.asarray(s2), sh, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a2), ah, rtol=1e-5)


def test_set_opt_state_device_restore_rejects_bad_dtype(mesh):
    """The orbax-v2 device-restore branch must reject an optimizer slot
    whose dtype doesn't match the bucket's (mirroring set_store_array's
    dense 'bad restore dtype' check) instead of deferring to an opaque
    XLA error steps later."""
    import jax.numpy as jnp

    from pslite_tpu.utils import logging as log

    eng = CollectiveEngine(mesh=mesh)
    keys = np.arange(2, dtype=np.uint64)
    eng.register_dense("odt", keys, 10)  # float32, total 20, padded 24
    bucket = eng._buckets["odt"]
    bad = jnp.zeros(bucket.padded_len, jnp.int32)  # device array, wrong dtype
    with pytest.raises(log.CheckError, match="bad opt restore dtype"):
        eng.set_opt_state("odt", "sgd_momentum", [bad])
    # Matching dtype passes through the same branch.
    good = jnp.zeros(bucket.padded_len, jnp.float32)
    eng.set_opt_state("odt", "sgd_momentum", [good])
    kind, slots = eng.opt_state("odt")
    assert kind == "sgd_momentum" and len(slots) == 1
