"""Ring attention vs single-device reference on the 8-shard CPU mesh."""

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


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_reference(causal):
    mesh = default_mesh(axis_name="sp")
    S = mesh.shape["sp"]
    B, T, H, D = 2, 4 * S, 3, 8
    rng = np.random.default_rng(0)
    q = rng.normal(size=(B, T, H, D)).astype(np.float32)
    k = rng.normal(size=(B, T, H, D)).astype(np.float32)
    v = rng.normal(size=(B, T, H, D)).astype(np.float32)

    ref = np.asarray(reference_attention(jnp.asarray(q), jnp.asarray(k),
                                         jnp.asarray(v), causal=causal))

    fn = jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=causal),
        mesh=mesh,
        in_specs=(P(None, "sp"), P(None, "sp"), P(None, "sp")),
        out_specs=P(None, "sp"),
        check_vma=False,
    )
    out = np.asarray(jax.jit(fn)(q, k, v))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)
