"""The dense engine's one data plane at the edge shapes: ``_padded_len``,
the reduce-scatter / psum, the all-gather and the cut at ``total_len`` on
lengths around the tile and shard borders (1, 7, 127, 129, 1023, 1025,
4095, 8191) and on meshes of 1, 2, 3, 5, 6 and 8 devices, through
``push_pull``, ``push`` + ``pull``, ``push_pull_group`` and ``replay``.

Every case runs two steps (the second reads the store the first left)
against a float64 numpy recurrence written here: no twin engine, nothing of
the program's.  A CPU run proves values, never a speed.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from pslite_tpu.environment import Environment  # noqa: E402
from pslite_tpu.message import Role  # noqa: E402
from pslite_tpu.parallel import CollectiveEngine  # noqa: E402
from pslite_tpu.parallel.mesh import make_mesh  # noqa: E402
from pslite_tpu.postoffice import Postoffice  # noqa: E402
from pslite_tpu.utils.logging import CheckError  # noqa: E402

EDGES = (1, 7, 127, 129, 1023, 1025, 4095, 8191)
ONE_KEY = np.arange(1, dtype=np.uint64)
F32_TOL = dict(rtol=1e-5, atol=1e-5)


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


class _Ref:
    """The float64 recurrence of a server handle over one bucket."""

    def __init__(self, handle, init):
        self.kind, _, rest = handle.partition(":")
        self.args = [float(x) for x in rest.split(",")] if rest else []
        self.p = np.asarray(init, np.float64).copy()
        self.state = [np.zeros_like(self.p), np.zeros_like(self.p)]
        self.t = 0

    def step(self, grads):
        """``grads``: ``[W, total]``; returns the new parameters."""
        g = np.asarray(grads, np.float64).sum(axis=0)
        a, (m, v) = self.args, self.state
        self.t += 1
        if self.kind == "sum":
            self.p += g
        elif self.kind == "sgd":
            self.p -= a[0] * g
        elif self.kind == "sgd_momentum":
            m[:] = a[1] * m + g
            self.p -= a[0] * m
        elif self.kind == "adagrad":
            m += g * g
            self.p -= a[0] * g / (np.sqrt(m) + 1e-8)
        elif self.kind == "adam":
            b1, b2 = 0.9, 0.999
            m[:] = b1 * m + (1 - b1) * g
            v[:] = b2 * v + (1 - b2) * g * g
            alpha = a[0] * np.sqrt(1 - b2 ** self.t) / (1 - b1 ** self.t)
            self.p -= alpha * m / (np.sqrt(v) + 1e-8)
        else:
            raise ValueError(self.kind)
        return self.p.copy()


def _grads(rng, workers, total):
    """``[W, total]`` f32 whose sum over W stays away from zero, where an
    optimizer's ``g/|g|`` would turn an f32 rounding into a sign."""
    sign = rng.choice([-1.0, 1.0], size=total)
    return (rng.uniform(0.5, 1.5, size=(workers, total)) * sign).astype(
        np.float32)


def _init(rng, total):
    return rng.normal(size=total).astype(np.float32)


def _held(pulled, want, total, **tol):
    pulled = np.asarray(pulled)
    assert pulled.shape == (total,)
    np.testing.assert_allclose(pulled, want, **(tol or F32_TOL))


def _two_steps(eng, name, ref, rng, total):
    for _ in range(2):
        g = _grads(rng, eng.num_workers, total)
        _held(eng.push_pull(name, g), ref.step(g), total)
    _held(eng.pull(name), ref.p, total)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("total", EDGES)
def test_edge_lengths(total, dtype):
    """Every edge length on four devices, two ``push_pull``s under the sum.
    The bfloat16 gradients are small integers, whose sums over W and over
    both steps bfloat16 holds exactly: equal, whatever order the
    reduce-scatter adds in."""
    rng = np.random.default_rng(total)
    eng = CollectiveEngine(mesh=_mesh(4))
    bucket = eng.register_dense("e", ONE_KEY, total, dtype=jnp.dtype(dtype))
    assert bucket.padded_len == -(-total // 4) * 4
    if dtype == "float32":
        _two_steps(eng, "e", _Ref("sum", np.zeros(total)), rng, total)
        return
    acc = np.zeros(total)
    for _ in range(2):
        g = rng.integers(-8, 9, size=(4, total)).astype(jnp.bfloat16)
        acc += np.asarray(g, np.float64).sum(axis=0)
        pulled = eng.push_pull("e", g)
        assert pulled.dtype == jnp.bfloat16 and pulled.shape == (total,)
        np.testing.assert_array_equal(
            np.asarray(pulled).astype(np.float64), acc)


@pytest.mark.parametrize("handle", ["sum", "sgd:0.01"])
@pytest.mark.parametrize("n", [1, 2, 3, 5, 6, 8])
def test_mesh_sizes(n, handle):
    """1,027 values (13 x 79: no mesh size here divides it) on every mesh
    size, the padding a different length on each."""
    total = 13 * 79
    rng = np.random.default_rng(100 + n)
    init = _init(rng, total)
    eng = CollectiveEngine(mesh=_mesh(n), server_handle=handle)
    bucket = eng.register_dense("m", np.arange(13, dtype=np.uint64), 79,
                                init=init)
    assert bucket.padded_len == -(-total // n) * n
    _two_steps(eng, "m", _Ref(handle, init), rng, total)


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("total", [1, 1023, 1025])
def test_push_then_pull_edge(total, n):
    """``push`` alone, then ``pull`` alone, twice: the store a push left
    is the one the next push adds to."""
    rng = np.random.default_rng(200 + total + n)
    eng = CollectiveEngine(mesh=_mesh(n))
    eng.register_dense("p", ONE_KEY, total)
    ref = _Ref("sum", np.zeros(total))
    for _ in range(2):
        g = _grads(rng, n, total)
        eng.push("p", g).block_until_ready()
        _held(eng.pull("p"), ref.step(g), total)


@pytest.mark.parametrize("handle", ["sum", "sgd:0.01"])
@pytest.mark.parametrize("n", [2, 4, 8])
def test_group_edge(n, handle):
    """The eight edge lengths as one ``push_pull_group``, twice."""
    rng = np.random.default_rng(300 + n)
    eng = CollectiveEngine(mesh=_mesh(n), server_handle=handle)
    names, refs = [f"g{t}" for t in EDGES], []
    for name, total in zip(names, EDGES):
        init = _init(rng, total)
        eng.register_dense(name, ONE_KEY, total, init=init)
        refs.append(_Ref(handle, init))
    for _ in range(2):
        grads = [_grads(rng, n, total) for total in EDGES]
        outs = eng.push_pull_group(names, grads)
        assert len(outs) == len(EDGES)
        for out, ref, g, total in zip(outs, refs, grads, EDGES):
            _held(out, ref.step(g), total)
    for name, ref, total in zip(names, refs, EDGES):
        _held(eng.pull(name), ref.p, total)


@pytest.mark.parametrize("keep", ["all", "last"])
@pytest.mark.parametrize("total", [1025, 8191])
def test_replay_edge(total, keep):
    """Three steps scanned in one program on eight devices, twice."""
    T, handle = 3, "sgd:0.01"
    rng = np.random.default_rng(400 + total)
    init = _init(rng, total)
    eng = CollectiveEngine(mesh=_mesh(8), server_handle=handle)
    eng.register_dense("r", ONE_KEY, total, init=init)
    ref = _Ref(handle, init)
    for _ in range(2):
        seq = np.stack([_grads(rng, 8, total) for _ in range(T)])
        want = np.stack([ref.step(seq[t]) for t in range(T)])
        pulled = np.asarray(eng.replay("r", seq, keep=keep))
        if keep == "all":
            assert pulled.shape == (T, total)
            np.testing.assert_allclose(pulled, want, **F32_TOL)
        else:
            _held(pulled, want[-1], total)
    _held(eng.pull("r"), ref.p, total)


@pytest.mark.parametrize("handle", ["sum", "adam:1e-3"])
@pytest.mark.parametrize("shape,axes", [((2, 4), ("dp", "kv")),
                                        ((4, 2), ("dp", "kv"))])
def test_two_axis_edge(shape, axes, handle):
    """A (dp, kv) torus, W != S: the psum along dp, the store over kv;
    ``push_pull``, then ``push`` and ``pull`` apart, then a third step."""
    total = 3 * 700  # padded, shards off every tile border
    rng = np.random.default_rng(500 + shape[0])
    init = _init(rng, total)
    eng = CollectiveEngine(mesh=make_mesh(shape, axes), worker_axis="dp",
                           server_handle=handle)
    assert (eng.num_workers, eng.num_shards) == shape
    eng.register_dense("x", np.arange(3, dtype=np.uint64), 700, init=init)
    ref = _Ref(handle, init)
    g = _grads(rng, shape[0], total)
    _held(eng.push_pull("x", g), ref.step(g), total)
    g = _grads(rng, shape[0], total)
    eng.push("x", g).block_until_ready()
    _held(eng.pull("x"), ref.step(g), total)
    g = _grads(rng, shape[0], total)
    _held(eng.push_pull("x", g), ref.step(g), total)


@pytest.mark.parametrize(
    "handle", ["adam:1e-3", "sgd_momentum:0.01,0.9", "adagrad:0.01"])
@pytest.mark.parametrize("total", [1, 1025, 8191])
def test_stateful_edge(total, handle):
    """The fused optimizer kernels between the reduce-scatter and the
    all-gather on four devices: the padding behind ``total_len`` takes a
    zero gradient and is cut from what is pulled."""
    rng = np.random.default_rng(600 + total)
    init = _init(rng, total)
    eng = CollectiveEngine(mesh=_mesh(4), server_handle=handle)
    eng.register_dense("s", ONE_KEY, total, init=init)
    _two_steps(eng, "s", _Ref(handle, init), rng, total)


def test_interleaved_ops_soak():
    """Random interleavings of ``push_pull``, ``push``, ``push_pull_group``
    and ``pull`` over three buckets of one engine track a host replay: the
    donated stores and the program cache under a mix of ops."""
    n = 8
    rng = np.random.default_rng(11)
    eng = CollectiveEngine(mesh=_mesh(n))
    totals = {"a": 1200, "b": 129, "c": 8191}
    refs = {}
    for name, total in totals.items():
        eng.register_dense(name, ONE_KEY, total)
        refs[name] = _Ref("sum", np.zeros(total))
    for _ in range(16):
        op = rng.choice(["push_pull", "push", "group", "pull"])
        name = str(rng.choice(list(totals)))
        total = totals[name]
        if op == "pull":
            _held(eng.pull(name), refs[name].p, total)
        elif op == "group":
            names = [str(x) for x in rng.permutation(list(totals))[:2]]
            grads = [_grads(rng, n, totals[x]) for x in names]
            for out, x, g in zip(eng.push_pull_group(names, grads), names,
                                 grads):
                _held(out, refs[x].step(g), totals[x], rtol=1e-4, atol=1e-4)
        else:
            g = _grads(rng, n, total)
            want = refs[name].step(g)
            if op == "push_pull":
                _held(eng.push_pull(name, g), want, total, rtol=1e-4,
                      atol=1e-4)
            else:
                eng.push(name, g)
    for name, total in totals.items():
        _held(eng.pull(name), refs[name].p, total, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("var,value", [("PS_ICI_IMPL", "pallas"),
                                       ("PS_ICI_COMPRESS", "int8")],
                         ids=["PS_ICI_IMPL=pallas", "PS_ICI_COMPRESS=int8"])
def test_removed_switch_is_refused(var, value):
    """A job that still asks for the ring kernel is refused by the ICI
    van before it starts, by the variable's name: never run on XLA's
    collectives in silence.  (``PS_ICI_IMPL=xla`` asks for what runs.)"""
    env = {"DMLC_NUM_WORKER": "1", "DMLC_NUM_SERVER": "1",
           "DMLC_PS_ROOT_URI": "lo", "DMLC_PS_ROOT_PORT": "49999",
           "DMLC_NODE_HOST": "lo", "PS_VAN_TYPE": "ici", var: value}
    po = Postoffice(Role.WORKER, env=Environment(env))
    with pytest.raises(CheckError, match=f"{var}.*removed at PR 46"):
        po.van.start(0)
    assert po.van.engine is None
