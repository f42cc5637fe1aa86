"""Pipeline parallelism (parallel/pipeline.py): GPipe microbatch schedule
over a ``pp`` mesh axis — forward parity, gradient parity, and dp x pp
composition against a single-device sequential reference.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from pslite_tpu.parallel.pipeline import (
    pipeline_apply,
    pipeline_loss,
    stack_layers,
)

D = 16


def _params(rng, n_layers):
    ws = [
        {"w": (rng.randn(D, D) * 0.3).astype(np.float32)}
        for _ in range(n_layers)
    ]
    head = (rng.randn(D, D) * 0.3).astype(np.float32)
    return ws, head


def _layer(w, x):
    return x + jnp.tanh(x @ w)


def _stage_fn(stage_params, x):
    # stage_params["w"]: [layers_per_stage, D, D]
    def body(x, w):
        return _layer(w, x), None

    x, _ = jax.lax.scan(body, x, stage_params["w"])
    return x


def _seq_forward(ws, x):
    for layer in ws:
        x = _layer(layer["w"], x)
    return x


def _head_loss(head, outs, tgt_micros):
    pred = outs @ head
    return jnp.mean((pred - tgt_micros) ** 2)


def test_forward_parity():
    S, L, M, mb = 4, 8, 4, 2
    rng = np.random.RandomState(0)
    ws, _ = _params(rng, L)
    x = rng.randn(M, mb, D).astype(np.float32)
    stacked = stack_layers([jax.tree.map(jnp.asarray, w) for w in ws])

    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))

    def body(stacked_l, x_micros):
        outs = pipeline_apply(_stage_fn, stacked_l, x_micros, "pp", S)
        # Valid on the last stage only; psum replicates (others are 0).
        return jax.lax.psum(outs, "pp")

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("pp"), P(None)),
            out_specs=P(None),
            check_vma=False,
        )
    )
    outs = np.asarray(f(stacked, jnp.asarray(x)))
    want = np.asarray(_seq_forward(ws, jnp.asarray(x.reshape(M * mb, D))))
    np.testing.assert_allclose(
        outs.reshape(M * mb, D), want, rtol=1e-5, atol=1e-5
    )


def test_gradient_parity():
    S, L, M, mb = 4, 8, 4, 2
    rng = np.random.RandomState(1)
    ws, head = _params(rng, L)
    x = rng.randn(M, mb, D).astype(np.float32)
    tgt = rng.randn(M, mb, D).astype(np.float32)
    stacked = stack_layers([jax.tree.map(jnp.asarray, w) for w in ws])

    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))

    def pp_loss(stacked_l, head_r, x_micros, tgt_micros):
        return pipeline_loss(
            _stage_fn,
            lambda h, outs: _head_loss(h, outs, tgt_micros),
            stacked_l,
            head_r,
            x_micros,
            "pp",
            S,
        )

    def body(stacked_l, head_r, x_micros, tgt_micros):
        loss, grads = jax.value_and_grad(pp_loss, argnums=(0, 1))(
            stacked_l, head_r, x_micros, tgt_micros
        )
        gw, gh = grads
        # Head stays replicated: sum its per-stage grads (zero off-last).
        return loss, gw, jax.lax.psum(gh, "pp")

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("pp"), P(None), P(None), P(None)),
            out_specs=(P(), P("pp"), P(None)),
            check_vma=False,
        )
    )
    loss, gw, gh = f(stacked, jnp.asarray(head), jnp.asarray(x),
                     jnp.asarray(tgt))

    # Sequential reference (microbatch mean == full mean: equal sizes).
    def seq_loss(stacked_r, head_r, x_all, tgt_all):
        def body(x, w):
            return _layer(w, x), None

        out, _ = jax.lax.scan(body, x_all, stacked_r["w"])
        return jnp.mean((out @ head_r - tgt_all) ** 2)

    want_loss, (want_gw, want_gh) = jax.value_and_grad(
        seq_loss, argnums=(0, 1)
    )(stacked, jnp.asarray(head), jnp.asarray(x.reshape(M * mb, D)),
      jnp.asarray(tgt.reshape(M * mb, D)))

    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gw["w"]), np.asarray(want_gw["w"]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gh), np.asarray(want_gh), rtol=1e-4, atol=1e-5
    )


def test_dp_pp_composition():
    """(dp=2, pp=4): batch sharded over dp, layers over pp; dp-psum'd
    gradients match the single-device whole-batch gradients."""
    S, L, M, mb = 4, 4, 2, 2
    dp = 2
    rng = np.random.RandomState(2)
    ws, head = _params(rng, L)
    # Global batch: dp shards each see [M, mb, D].
    x = rng.randn(dp, M, mb, D).astype(np.float32)
    tgt = rng.randn(dp, M, mb, D).astype(np.float32)
    stacked = stack_layers([jax.tree.map(jnp.asarray, w) for w in ws])

    devs = np.array(jax.devices()[: dp * S]).reshape(dp, S)
    mesh = Mesh(devs, ("dp", "pp"))

    def body(stacked_l, head_r, x_l, tgt_l):
        def pp_loss(sl, hr):
            return pipeline_loss(
                _stage_fn,
                lambda h, outs: _head_loss(h, outs, tgt_l[0]),
                sl,
                hr,
                x_l[0],
                "pp",
                S,
            )

        loss, grads = jax.value_and_grad(pp_loss, argnums=(0, 1))(
            stacked_l, head_r
        )
        gw, gh = grads
        # Average over data-parallel replicas; sum head over stages.
        loss = jax.lax.pmean(loss, "dp")
        gw = jax.tree.map(lambda g: jax.lax.pmean(g, "dp"), gw)
        gh = jax.lax.pmean(jax.lax.psum(gh, "pp"), "dp")
        return loss, gw, gh

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("pp"), P(None), P("dp"), P("dp")),
            out_specs=(P(), P("pp"), P(None)),
            check_vma=False,
        )
    )
    loss, gw, gh = f(stacked, jnp.asarray(head), jnp.asarray(x),
                     jnp.asarray(tgt))

    def seq_loss(stacked_r, head_r):
        def body(xc, w):
            return _layer(w, xc), None

        x_all = jnp.asarray(x.reshape(-1, D))
        out, _ = jax.lax.scan(body, x_all, stacked_r["w"])
        return jnp.mean((out @ head_r - jnp.asarray(tgt.reshape(-1, D))) ** 2)

    want_loss, (want_gw, want_gh) = jax.value_and_grad(
        seq_loss, argnums=(0, 1)
    )(stacked, jnp.asarray(head))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gw["w"]), np.asarray(want_gw["w"]), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(gh), np.asarray(want_gh), rtol=1e-4, atol=1e-5
    )


def test_single_microbatch_and_full_mesh():
    # Degenerate schedules: M=1 (pure fill/drain) and S=8 (whole mesh).
    S, L, M, mb = 8, 8, 1, 3
    rng = np.random.RandomState(3)
    ws, _ = _params(rng, L)
    x = rng.randn(M, mb, D).astype(np.float32)
    stacked = stack_layers([jax.tree.map(jnp.asarray, w) for w in ws])
    mesh = Mesh(np.array(jax.devices()[:S]), ("pp",))

    def body(stacked_l, x_micros):
        outs = pipeline_apply(_stage_fn, stacked_l, x_micros, "pp", S)
        return jax.lax.psum(outs, "pp")

    f = jax.jit(
        jax.shard_map(
            body, mesh=mesh, in_specs=(P("pp"), P(None)), out_specs=P(None),
            check_vma=False,
        )
    )
    outs = np.asarray(f(stacked, jnp.asarray(x)))
    want = np.asarray(_seq_forward(ws, jnp.asarray(x[0])))
    np.testing.assert_allclose(outs[0], want, rtol=1e-5, atol=1e-5)
