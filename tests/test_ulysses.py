"""Ulysses (all-to-all) sequence parallelism vs the single-device
reference, and agreement with ring attention, on the 8-shard CPU mesh."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from pslite_tpu.parallel.mesh import default_mesh
from pslite_tpu.parallel.ring_attention import (
    reference_attention,
    ring_attention,
)
from pslite_tpu.parallel.ulysses import ulysses_attention


def _inputs(S, H):
    B, T, D = 2, 4 * S, 16
    rng = np.random.default_rng(1)
    q = rng.normal(size=(B, T, H, D)).astype(np.float32)
    k = rng.normal(size=(B, T, H, D)).astype(np.float32)
    v = rng.normal(size=(B, T, H, D)).astype(np.float32)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(causal):
    mesh = default_mesh(axis_name="sp")
    S = mesh.shape["sp"]
    H = 2 * S  # heads divisible by the axis (Ulysses requirement)
    q, k, v = _inputs(S, H)

    ref = np.asarray(
        reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                            causal=causal)
    )  # [B, T, H, D]

    fn = jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = np.asarray(jax.jit(fn)(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)


def test_ulysses_agrees_with_ring():
    """The two sequence-parallel strategies are drop-in interchangeable:
    same sharded layout, same output."""
    mesh = default_mesh(axis_name="sp")
    S = mesh.shape["sp"]
    H = S
    q, k, v = _inputs(S, H)

    def run(attn):
        fn = jax.shard_map(
            lambda a, b, c: attn(a, b, c, "sp", causal=True),
            mesh=mesh,
            in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
            out_specs=P(None, "sp"),
            check_vma=False,
        )
        return np.asarray(jax.jit(fn)(q, k, v))

    np.testing.assert_allclose(
        run(ulysses_attention), run(ring_attention), rtol=2e-4, atol=2e-5
    )
