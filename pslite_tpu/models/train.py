"""PS-integrated SPMD training step for the flagship model.

One jit-compiled program over a ``(dp, sp)`` mesh:

1. **pull**: ``all_gather`` the flat parameter store (sharded over both
   axes — every device is a PS server shard) and unravel into the params
   pytree — the ``ZPull`` leg.
2. forward/backward with **ring attention over sp** (long context) on the
   local ``[B/dp, T/sp]`` token block — the worker compute.
3. **push**: ``psum_scatter`` of the flat gradient over ``(dp, sp)`` — the
   cross-worker aggregation ``KVServerDefaultHandle`` performs, executed as
   a collective (the ``ZPush`` leg).
4. **server update**: SGD applied to the local store shard.

This is the reference's async PS loop (docs/overview.md:44-125) re-derived
as a synchronous SPMD program — the "sync mode" SURVEY §7 requires, with
the async per-message mode still available through KVServer handlers.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

from .transformer import ModelConfig, init_params, loss_fn


def make_ps_train_step(cfg: ModelConfig, mesh, lr: float = 0.1,
                       seed: int = 0, sp_strategy: str = "ring"):
    """Returns (step_fn, flat_store, token_sharding, store_sharding).

    ``step_fn(flat_store, inputs, targets) -> (flat_store, loss)`` is jitted
    with donated store; inputs/targets are ``[B, T]`` int32 sharded
    ``P('dp', 'sp')``.

    ``sp_strategy`` picks the sequence-parallel attention: ``"ring"``
    (ppermute K/V ring, minimal residency) or ``"ulysses"`` (all-to-all
    head/sequence swap, 2 collectives — needs heads % sp == 0).
    """
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.ring_attention import ring_attention
    from ..parallel.ulysses import ulysses_attention
    from .ps_step import make_flat_ps_step
    from .transformer import ParallelCtx

    axes = tuple(mesh.axis_names)  # e.g. ('dp', 'sp')
    sp_axis = axes[-1]
    sp = mesh.shape[sp_axis]

    # Non-divisible shardings would silently drop feature columns /
    # experts inside shard_map; fail loudly up front instead.
    if cfg.moe_experts:
        if cfg.moe_experts % sp != 0:
            raise ValueError(
                f"moe_experts={cfg.moe_experts} must divide evenly over the "
                f"{sp}-way model axis"
            )
    elif (cfg.mlp_ratio * cfg.dim) % sp != 0:
        raise ValueError(
            f"mlp hidden width {cfg.mlp_ratio * cfg.dim} must divide evenly "
            f"over the {sp}-way model axis"
        )
    if sp_strategy not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp_strategy {sp_strategy!r}")
    if sp_strategy == "ulysses" and cfg.heads % sp != 0:
        raise ValueError(
            f"ulysses needs heads ({cfg.heads}) divisible by the "
            f"{sp}-way sequence axis"
        )
    attn = ring_attention if sp_strategy == "ring" else ulysses_attention

    params0 = init_params(jax.random.PRNGKey(seed), cfg)

    def _local_loss(params, inp_l, tgt_l):
        sp_idx = lax.axis_index(sp_axis)
        t_local = inp_l.shape[1]
        # The model axis carries sequence parallelism (ring attention),
        # tensor parallelism (sharded MLP matmuls), and — for MoE configs —
        # expert parallelism, all at once.
        ctx = ParallelCtx(
            attn_fn=lambda q, k, v: attn(
                q, k, v, sp_axis, causal=True
            ),
            pos_offset=sp_idx * t_local,
            tp_axis=None if cfg.moe_experts else sp_axis,
            ep_axis=sp_axis if cfg.moe_experts else None,
        )
        return loss_fn(params, inp_l, tgt_l, cfg, ctx=ctx)

    token_spec = P(axes[0], sp_axis)
    step, flat_store, (token_sharding, _), store_sharding, _ = (
        make_flat_ps_step(
            mesh, params0, _local_loss, [token_spec, token_spec], lr=lr
        )
    )
    return step, flat_store, token_sharding, store_sharding


def make_pp_train_step(cfg: ModelConfig, mesh, lr: float = 0.1,
                       num_micro: int = 4, seed: int = 0):
    """PS training step with PIPELINE parallelism over the mesh's last
    axis (optionally data parallelism over a leading ``dp`` axis).

    The PS view: each pipeline stage owns the key range covering its
    layer block — the stacked layer params are sharded ``P('pp', ...)``
    and the stage-local SGD update IS the server-shard update (no
    cross-stage reduction exists because each stage is the sole owner of
    its range, the same invariant as key-range server sharding,
    postoffice.cc:257-268).  Replicated head params (embed / final norm)
    behave like a fully-replicated bucket: grads psum over pp (only the
    last stage holds non-zero head cotangents), pmean over dp, applied
    identically everywhere.

    Returns ``(step_fn, state, token_sharding)`` with
    ``state = (stacked_layers, head)`` already device_put onto the mesh;
    ``step_fn(state, inputs, targets) -> (state, loss)``; inputs/targets
    ``[dp, M, mb, T]`` int32 (microbatched along M).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.pipeline import (
        pipeline_loss,
        stack_layers,
    )
    from .transformer import _rmsnorm

    axes = tuple(mesh.axis_names)
    pp_axis = axes[-1]
    S = mesh.shape[pp_axis]
    dp_axis = axes[0] if len(axes) > 1 else None
    if cfg.layers % S != 0:
        raise ValueError(
            f"layers={cfg.layers} must divide over the {S}-stage pipeline"
        )
    if cfg.moe_experts:
        raise ValueError("pp step supports dense layers only for now")

    params0 = init_params(jax.random.PRNGKey(seed), cfg)
    stacked0 = stack_layers(params0["layers"])
    head0 = {"embed": params0["embed"], "ln_f": params0["ln_f"]}

    D, H = cfg.dim, cfg.heads
    hd = D // H

    def _embed(head, tokens):
        x = head["embed"][tokens]  # [mb, T, D]
        T = x.shape[1]
        pos = jnp.arange(T)
        freqs = jnp.exp(-jnp.arange(0, D, 2) / D * jnp.log(10000.0))
        ang = pos[:, None] * freqs[None, :]
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        return x + pe[None].astype(x.dtype)

    def _one_layer(layer, x):
        from ..parallel.ring_attention import reference_attention

        compute_dt = jnp.bfloat16 if x.dtype != jnp.float64 else x.dtype
        B, T, _ = x.shape
        h = _rmsnorm(x, layer["ln1"])
        qkv = (
            h.astype(compute_dt) @ layer["qkv"].astype(compute_dt)
        ).astype(x.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        o = reference_attention(
            q.reshape(B, T, H, hd),
            k.reshape(B, T, H, hd),
            v.reshape(B, T, H, hd),
            causal=True,
        ).reshape(B, T, D)
        x = x + (
            o.astype(compute_dt) @ layer["proj"].astype(compute_dt)
        ).astype(x.dtype)
        h = _rmsnorm(x, layer["ln2"])
        h1 = jax.nn.gelu(
            (h.astype(compute_dt) @ layer["mlp_in"].astype(compute_dt)
             ).astype(x.dtype)
        )
        return x + (
            h1.astype(compute_dt) @ layer["mlp_out"].astype(compute_dt)
        ).astype(x.dtype)

    def _stage_fn(stage_layers, x):
        def body(xc, layer):
            return _one_layer(layer, xc), None

        x, _ = lax.scan(body, x, stage_layers)
        return x

    def _head_loss(head, outs, tgt_micros):
        # outs: [M, mb, T, D] finished activations (last stage).
        compute_dt = jnp.bfloat16
        x = _rmsnorm(outs, head["ln_f"])
        logits = (
            x.astype(compute_dt) @ head["embed"].T.astype(compute_dt)
        ).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(
            logp, tgt_micros[..., None], axis=-1
        )[..., 0]
        return nll.mean()

    def _local_step(stacked_l, head_r, inp_l, tgt_l):
        if dp_axis is not None:
            inp_l, tgt_l = inp_l[0], tgt_l[0]

        def _loss(sl, hr):
            x_micros = jax.vmap(lambda t: _embed(hr, t))(inp_l)
            return pipeline_loss(
                _stage_fn,
                lambda h, outs: _head_loss(h, outs, tgt_l),
                sl,
                hr,
                x_micros,
                pp_axis,
                S,
            )

        loss, (g_sl, g_hr) = jax.value_and_grad(_loss, argnums=(0, 1))(
            stacked_l, head_r
        )
        # Head grads live on the last stage only: sum over pp; average
        # both over dp replicas.
        g_hr = jax.tree.map(lambda g: lax.psum(g, pp_axis), g_hr)
        if dp_axis is not None:
            g_sl = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_sl)
            g_hr = jax.tree.map(lambda g: lax.pmean(g, dp_axis), g_hr)
            loss = lax.pmean(loss, dp_axis)
        new_sl = jax.tree.map(lambda p, g: p - lr * g, stacked_l, g_sl)
        new_hr = jax.tree.map(lambda p, g: p - lr * g, head_r, g_hr)
        return new_sl, new_hr, loss

    layer_spec = P(pp_axis)
    repl_spec = P()
    tok_spec = P(dp_axis) if dp_axis is not None else P(None)
    fn = jax.shard_map(
        _local_step,
        mesh=mesh,
        in_specs=(layer_spec, repl_spec, tok_spec, tok_spec),
        out_specs=(layer_spec, repl_spec, repl_spec),
        check_vma=False,
    )
    jitted = jax.jit(fn, donate_argnums=(0, 1))

    def step(state, inputs, targets):
        sl, hr = state
        new_sl, new_hr, loss = jitted(sl, hr, inputs, targets)
        return (new_sl, new_hr), loss

    stacked = jax.device_put(
        stacked0,
        jax.tree.map(
            lambda _: NamedSharding(mesh, P(pp_axis)), stacked0
        ),
    )
    head = jax.device_put(
        head0, jax.tree.map(lambda _: NamedSharding(mesh, P()), head0)
    )
    token_sharding = NamedSharding(mesh, tok_spec)
    return step, (stacked, head), token_sharding


def kv_train_loop(worker, cfg: ModelConfig, steps: int = 30,
                  lr: float = 0.5, batch: int = 8, seq: int = 16,
                  codec=None, pull_codec="raw", seed: int = 0,
                  data_seed: int = 1, val_len: int = 1024):
    """Train the toy LM over the MESSAGE-PATH parameter server: the
    flat parameter vector lives in the KV store (``KVServerDefaultHandle``
    on the server side), and each step pulls params, computes the
    gradient locally (jit), and pushes ``-lr * grad`` as the delta —
    the async-PS loop of the reference, on the wire instead of the
    collective plane.

    ``codec`` compresses the gradient-delta PUSHES through the
    quantized transport tier (docs/compression.md) — the classic
    EF-SGD setting; ``pull_codec`` (default ``"raw"``) optionally
    compresses the parameter pulls too (each gradient is then computed
    at a perturbed point, which shifts the trajectory beyond what
    error feedback alone corrects — see the guard test).  The initial
    parameter seed always travels raw so compressed and uncompressed
    runs start from identical state.  This is the convergence-guard
    harness: with ``fp8_e4m3`` + error feedback the final loss must
    land within tolerance of the uncompressed run
    (tests/test_model_train.py).

    Returns the per-step loss list.
    """
    import jax
    import jax.flatten_util
    import jax.numpy as jnp
    import numpy as np

    from .transformer import loss_fn

    params0 = init_params(jax.random.PRNGKey(seed), cfg)
    flat0, unravel = jax.flatten_util.ravel_pytree(params0)
    flat0 = np.asarray(flat0, np.float32)
    n = flat0.size
    pad = (-n) % val_len
    flat_pad = np.concatenate([flat0, np.zeros(pad, np.float32)])
    keys = np.arange(flat_pad.size // val_len, dtype=np.uint64)

    @jax.jit
    def grad_fn(flat, inp, tgt):
        loss, g = jax.value_and_grad(
            lambda f: loss_fn(unravel(f[:n]), inp, tgt, cfg)
        )(flat)
        return loss, g

    inputs, targets = toy_batch(cfg, batch, seq, seed=data_seed)
    # Seed the store with the exact initial params (raw: both runs of a
    # comparison must start bit-identical), then train through the
    # registered bucket codec.
    worker.wait(worker.push(keys, flat_pad, codec="raw"))
    worker.register_bucket(keys, codec=codec)
    buf = np.empty_like(flat_pad)
    losses = []
    for _ in range(steps):
        worker.wait(worker.pull(keys, buf, codec=pull_codec))
        loss, g = grad_fn(jnp.asarray(buf), inputs, targets)
        # g is padded-length (grad of the padded flat vector; the pad
        # tail is exactly zero since loss only reads f[:n]).
        worker.wait(worker.push(keys, (-lr) * np.asarray(g, np.float32)))
        losses.append(float(loss))
    return losses


def toy_batch(cfg: ModelConfig, batch: int, seq: int, seed: int = 1):
    """Deterministic toy LM data: predict (token + 1) mod vocab."""
    import numpy as np

    rng = np.random.default_rng(seed)
    inputs = rng.integers(0, cfg.vocab, size=(batch, seq)).astype(np.int32)
    targets = (inputs + 1) % cfg.vocab
    return inputs, targets
