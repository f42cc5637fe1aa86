"""Interpret-mode fuzz of the fused ring kernel over tile-edge shapes
and ring sizes (r04 verdict ask #4: cheaper hardware evidence than
execution).

Every case runs the ENGINE surface twice — ``impl="pallas"`` (the ring
kernel under the Pallas TPU interpreter, full semaphore/DMA protocol)
vs ``impl="xla"`` (psum_scatter/all_gather, independently trustworthy)
— on identical data, so the kernel's internal padding (`_pad_ring_chunks`
to the (8,128) tile, sliced back out) is exercised at every edge:
1-element buckets, odd lengths, non-multiples of 1024, exact tile
boundaries ±1, and ring sizes 2..16 (16 via a subprocess with a larger
virtual device count).  Reference analog: the RDMA pipeline's chunking
edge cases, rdma_transport.h:323-357.

INTERPRETER ENVELOPE (found by this fuzz, r05): on the 1-vCPU box the
interpret-mode DMA simulator DEADLOCKS (0%% CPU, threads parked in
``_allocate_buffer`` io_callbacks) past a work threshold that scales
with ring size x chunk x per-hop callback count: f32 n=8 hangs at
chunk 12288 (fine at 4096); int8-wire n=8 hangs at its minimum chunk
8192 (fine at n=4, the existing engine-int8 coverage).  Reproducible
with the raw kernel and the pre-r05 grads layout alike, so it is a
simulator callback-pool starvation, not a kernel-protocol or engine
bug; the identical geometries pass real-v5e Mosaic compilation in
docs/AOT_RING.json.  The in-suite sweep therefore stays inside the
envelope (f32 n=8 chunk <= 4096, int8 n=4), and the n=16 subprocess
case runs the UNIDIRECTIONAL kernel at minimum chunk — half the
per-hop work, inside the envelope (~9 s) — for a definitive 16-ring
schedule-closure parity instead of a skip.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp
from jax.sharding import Mesh

from pslite_tpu.parallel.engine import CollectiveEngine

# Tile-edge lengths (f32 tile = 1024 elems; bidir chunk quantum 2048):
# 1-element bucket, sub-tile odds, one-over/one-under tile and lane
# boundaries, and prime-ish larger odds — capped so the per-device
# chunk stays within the interpreter envelope (module docstring).
EDGE_LENGTHS = [1, 7, 127, 129, 1023, 1025, 4095, 8191]


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("kv",))


def _pair(n, dtype=None, wire=None, handle="sum"):
    mesh = _mesh(n)
    ex = CollectiveEngine(mesh=mesh, impl="xla", server_handle=handle)
    ep = CollectiveEngine(mesh=mesh, impl="pallas", server_handle=handle,
                          wire_compress=wire)
    assert ep._effective_impl(dtype or jnp.float32, handle) == "pallas", \
        "fuzz case fell back to xla — not testing the kernel"
    return ex, ep


def _grads(n, total, seed, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n, total)).astype(dtype)


def _roundtrip(eng, name, total, grads_rows, dtype=None):
    """register + two push_pulls (the second catches store corruption
    from the first); returns (pulled1, pulled2) as f32 numpy."""
    eng.register_dense(name, np.arange(1, dtype=np.uint64), total,
                       dtype=dtype)
    p1 = np.asarray(eng.push_pull(name, grads_rows), np.float32)
    p2 = np.asarray(eng.push_pull(name, 0.5 * grads_rows), np.float32)
    return p1, p2


@pytest.mark.parametrize("total", EDGE_LENGTHS)
def test_edge_lengths_f32(total):
    n = 8
    ex, ep = _pair(n)
    g = _grads(n, total, seed=total)
    want1, want2 = _roundtrip(ex, "b", total, g)
    got1, got2 = _roundtrip(ep, "b", total, g)
    np.testing.assert_allclose(got1, want1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got2, want2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("n", [2, 3, 5, 6, 8])
def test_ring_sizes(n):
    """Non-power-of-two rings included: the ring schedule's modular
    chunk walk must close for every n, not just the 2^k meshes."""
    total = 1025
    ex, ep = _pair(n)
    g = _grads(n, total, seed=n)
    want1, want2 = _roundtrip(ex, "b", total, g)
    got1, got2 = _roundtrip(ep, "b", total, g)
    np.testing.assert_allclose(got1, want1, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got2, want2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("total", [129, 4097])
def test_edge_bf16(total):
    n = 8
    ex, ep = _pair(n, dtype=jnp.bfloat16)
    g = _grads(n, total, seed=total)
    want1, want2 = _roundtrip(ex, "b", total, g.astype(jnp.bfloat16),
                              dtype=jnp.bfloat16)
    got1, got2 = _roundtrip(ep, "b", total, g.astype(jnp.bfloat16),
                            dtype=jnp.bfloat16)
    # bf16 stores: both paths quantize, but reduction orders differ.
    np.testing.assert_allclose(got1, want1, rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(got2, want2, rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("total", [1025, 8191])
def test_edge_int8_wire(total):
    """int8 wire compression at tile edges, vs the UNCOMPRESSED XLA
    result: the error budget is the documented per-hop requantization
    bound (O(hops) * absmax/127), not bit equality.  n=4: int8 at n=8
    is outside the interpreter envelope (module docstring)."""
    n = 4
    ex, ep = _pair(n, wire="int8")
    g = _grads(n, total, seed=total)
    want1, want2 = _roundtrip(ex, "b", total, g)
    got1, got2 = _roundtrip(ep, "b", total, g)
    amax = float(np.abs(g).sum(axis=0).max())
    tol = 3.0 * n * amax / 127.0
    np.testing.assert_allclose(got1, want1, atol=tol)
    np.testing.assert_allclose(got2, want2, atol=tol)


@pytest.mark.parametrize("total", [1, 1023])
def test_push_only_edge(total):
    """Push-only (reduce + update, no gather) at edge lengths: read the
    store back via a zero-gradient push_pull on both engines."""
    n = 8
    ex, ep = _pair(n)
    g = _grads(n, total, seed=total + 100)
    zeros = np.zeros_like(g)
    for eng in (ex, ep):
        eng.register_dense("b", np.arange(1, dtype=np.uint64), total)
        eng.push("b", g)
    want = np.asarray(ex.push_pull("b", zeros), np.float32)
    got = np.asarray(ep.push_pull("b", zeros), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("total", [1025])
def test_replay_edge(total):
    """The fused replay scan (pallas ring per step) at an odd length."""
    n = 8
    steps = 3
    ex, ep = _pair(n)
    rng = np.random.default_rng(7)
    seq = rng.normal(size=(steps, total)).astype(np.float32)
    for eng in (ex, ep):
        eng.register_dense("b", np.arange(1, dtype=np.uint64), total)
    want = np.asarray(ex.replay("b", seq, keep="last"), np.float32)
    got = np.asarray(ep.replay("b", seq, keep="last"), np.float32)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


_RING16_CHILD = r"""
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P
from pslite_tpu.ops.ring_collective import ring_push_pull, ring_chunk_len

# UNIDIRECTIONAL, minimum chunk: half the per-hop work of the bidir
# form, which keeps a 16-ring inside the interpreter envelope (the
# bidir 16-ring at its minimum chunk starves the simulator — module
# docstring); the modular chunk schedule being proven is the same walk
# the bidir halves each run.
n = 16
chunk = ring_chunk_len(n * 1024, n, bidir=False)
assert jax.device_count() >= n, jax.device_count()
mesh = Mesh(np.array(jax.devices()[:n]), ("kv",))
rng = np.random.RandomState(1)
total = n * chunk
grads = rng.randn(n, total).astype(np.float32)
store0 = rng.randn(total).astype(np.float32)

def body(store_l, grads_l):
    g = grads_l[0].reshape(n, chunk)
    return ring_push_pull(g, store_l, lambda s, a: s + a, "kv", n,
                          bidir=False, interpret=True)

f = jax.jit(jax.shard_map(body, mesh=mesh,
                      in_specs=(P("kv"), P("kv", None)),
                      out_specs=(P("kv"), P(None)),
                      check_vma=False))
new_store, pulled = f(jnp.asarray(store0), jnp.asarray(grads))
want = store0 + grads.sum(0)
np.testing.assert_allclose(np.asarray(pulled), want,
                           rtol=1e-4, atol=1e-4)
# new_store is the global updated store (each shard owns its chunk).
np.testing.assert_allclose(np.asarray(new_store), want,
                           rtol=1e-4, atol=1e-4)
print("RING16_OK")
"""


def test_ring_16_subprocess():
    """Ring size 16 — beyond this process's 8 virtual devices, so a
    child process brings up a 16-device CPU mesh (the verdict's 2..16
    sweep upper end).  Runs the unidirectional kernel at minimum chunk
    (definitive n=16 schedule-closure parity in ~seconds); the bidir
    16-ring sits outside the interpreter envelope."""
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=16",
        PYTHONPATH=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))),
    )
    out = subprocess.run(
        [sys.executable, "-c", _RING16_CHILD],
        capture_output=True,
        text=True,
        timeout=900,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RING16_OK" in out.stdout
