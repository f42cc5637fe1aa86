"""Sharded forward (sp ring attention + Megatron-style TP, and EP MoE)
must match the single-device forward bit-for-tolerance."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from pslite_tpu.models.transformer import (
    ModelConfig,
    ParallelCtx,
    forward,
    init_params,
)
from pslite_tpu.parallel.mesh import default_mesh
from pslite_tpu.parallel.ring_attention import ring_attention


def _sharded_forward(params, tokens, cfg, mesh, axis="sp", moe=False):
    def local(p, tok_l):
        sp_idx = lax.axis_index(axis)
        ctx = ParallelCtx(
            attn_fn=lambda q, k, v: ring_attention(q, k, v, axis, causal=True),
            pos_offset=sp_idx * tok_l.shape[1],
            tp_axis=None if moe else axis,
            ep_axis=axis if moe else None,
        )
        return forward(p, tok_l, cfg, ctx=ctx)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis, None),
        check_vma=False,
    )
    return jax.jit(fn)(params, tokens)


def test_tp_sp_forward_matches_single_device():
    cfg = ModelConfig(vocab=32, dim=32, heads=2, layers=2)
    mesh = default_mesh(axis_name="sp")
    S = mesh.shape["sp"]
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 32, size=(2, 4 * S)),
        dtype=jnp.int32,
    )
    ref = forward(params, tokens, cfg)
    out = _sharded_forward(params, tokens, cfg, mesh)
    # bf16 matmuls reduce in different orders across shardings; exactness
    # is checked in float64 (diff == 0.0), tolerance here covers bf16 noise.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)


def test_tp_sp_forward_exact_in_float64():
    cfg = ModelConfig(vocab=32, dim=32, heads=2, layers=2, dtype="float64")
    if not jax.config.jax_enable_x64:
        jax.config.update("jax_enable_x64", True)
    try:
        mesh = default_mesh(axis_name="sp")
        S = mesh.shape["sp"]
        params = init_params(jax.random.PRNGKey(0), cfg)
        tokens = jnp.asarray(
            np.random.default_rng(0).integers(0, 32, size=(2, 4 * S)),
            dtype=jnp.int32,
        )
        ref = forward(params, tokens, cfg)
        out = _sharded_forward(params, tokens, cfg, mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-9, atol=1e-9)
    finally:
        jax.config.update("jax_enable_x64", False)


def test_ep_moe_forward_matches_single_device():
    cfg = ModelConfig(vocab=32, dim=32, heads=2, layers=1, moe_experts=16)
    mesh = default_mesh(axis_name="sp")
    S = mesh.shape["sp"]
    params = init_params(jax.random.PRNGKey(1), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 32, size=(2, 4 * S)),
        dtype=jnp.int32,
    )
    ref = forward(params, tokens, cfg)
    out = _sharded_forward(params, tokens, cfg, mesh, moe=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=2e-2)


def test_moe_gate_receives_gradient():
    """The router must be trainable: d(loss)/d(gate) != 0 (the selected
    expert's output is scaled by its gate probability)."""
    cfg = ModelConfig(vocab=16, dim=16, heads=2, layers=1, moe_experts=4)
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(3).integers(0, 16, size=(2, 8)), jnp.int32
    )

    def loss(p):
        return forward(p, tokens, cfg).sum()

    grads = jax.grad(loss)(params)
    gate_grad = np.asarray(grads["layers"][0]["moe"]["gate"])
    assert np.abs(gate_grad).max() > 0


def test_moe_single_device_routes_all_tokens():
    cfg = ModelConfig(vocab=16, dim=16, heads=2, layers=1, moe_experts=4)
    params = init_params(jax.random.PRNGKey(2), cfg)
    tokens = jnp.zeros((1, 8), jnp.int32)
    logits = forward(params, tokens, cfg)
    assert np.isfinite(np.asarray(logits)).all()
