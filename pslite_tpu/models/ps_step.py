"""Shared PS training cycle over a flat parameter store.

Every model family trains through the same four-phase SPMD program
(SURVEY §7 / docs/overview.md):

    pull    params = all_gather(store_shards)        # over ALL mesh axes
    compute loss, grads = value_and_grad(local_loss)
    push    agg = psum_scatter(flat_grads)           # cross-worker sum
    update  store_shard -= lr * agg / num_devices    # mean-gradient SGD

This module is that cycle, written once: the transformer (dp x sp mesh,
ring attention / TP / EP inside ``local_loss``) and the CNN (1-D dp mesh)
both build on it, so the padding math, mean scaling, donation, and
sharding specs cannot drift apart.
"""

from __future__ import annotations

from typing import Callable, Sequence


def make_flat_ps_step(
    mesh,
    params0,
    local_loss: Callable,
    batch_specs: Sequence,
    lr: float = 0.1,
):
    """Build the jitted step.

    - ``params0``: initial params pytree (defines the flat layout).
    - ``local_loss(params, *batch_local) -> scalar``: per-shard loss; runs
      inside shard_map, so it may use ``lax.axis_index``/collectives for
      sp/tp/ep.  Cross-shard loss scaling is handled here (psum / n_dev).
    - ``batch_specs``: one PartitionSpec per batch argument.

    Returns ``(step, flat_store, batch_shardings, store_sharding,
    unravel)`` where ``step(flat_store, *batch) -> (flat_store, loss)``
    donates the store.
    """
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.flatten_util import ravel_pytree
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = tuple(mesh.axis_names)
    n_dev = int(np.prod([mesh.shape[a] for a in axes]))

    flat0, unravel = ravel_pytree(params0)
    n_params = flat0.shape[0]
    padded = -(-n_params // n_dev) * n_dev
    flat0 = jnp.pad(flat0, (0, padded - n_params))
    store_sharding = NamedSharding(mesh, P(axes))
    flat_store = jax.device_put(flat0, store_sharding)
    batch_shardings = [NamedSharding(mesh, spec) for spec in batch_specs]

    def _local(store_l, *batch_l):
        flat = lax.all_gather(store_l, axes, tiled=True)[:n_params]
        params = unravel(flat)
        loss, grads = jax.value_and_grad(
            lambda p: local_loss(p, *batch_l)
        )(params)
        flat_g, _ = ravel_pytree(grads)
        flat_g = jnp.pad(flat_g, (0, padded - n_params))
        agg = lax.psum_scatter(flat_g, axes, scatter_dimension=0, tiled=True)
        new_store = store_l - lr * (agg / n_dev)
        return new_store, lax.psum(loss, axes) / n_dev

    fn = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(axes), *batch_specs),
        out_specs=(P(axes), P()),
        check_vma=False,
    )
    step = jax.jit(fn, donate_argnums=(0,))
    return step, flat_store, batch_shardings, store_sharding, unravel
