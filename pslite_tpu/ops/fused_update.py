"""Fused optimizer-update kernels (Pallas, TPU).

The server-side update is the aggregation hot loop of the reference
(``KVServerDefaultHandle``, kv_app.h:430-452, executed per push).  On TPU
the update is HBM-bandwidth-bound; these kernels apply the whole optimizer
step (SGD+momentum / Adagrad / Adam) in **one** tiled pass over the shard
with in-place aliasing — guaranteeing the single-pass fusion rather than
hoping XLA finds it.

Layout: flat vectors are zero-padded and reshaped to ``(rows, 128)`` with
``rows`` a multiple of the dtype's sublane tile (8 for 4-byte, 16 for
2-byte dtypes), and the kernels use 2-D ``(block_rows, 128)`` BlockSpecs —
rank-1 blocks and sub-tile blocks pass the interpreter but fail Mosaic
lowering on real TPU hardware.

Arithmetic runs in float32 whatever the bucket dtype and the result is
rounded once on the store: the v5e vector and transcendental units have no
bf16 forms (Mosaic refuses a bf16 ``sqrt`` outright), and a bf16 momentum
recurrence would round twice per step.

``interpret`` is decided by the caller, who knows the mesh the kernel is
compiled for (``CollectiveEngine``: TPU mesh → Mosaic, anything else → the
Pallas interpreter); nothing here looks at the process default backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
_SUBLANES = 8
_MAX_BLOCK_ROWS = 512  # (512, 128) fp32 block = 256 KiB per operand


def _tile_geometry(n: int, dtype):
    """(padded_len, block_rows, grid) for a flat length n of ``dtype``."""
    sublanes = _SUBLANES * max(1, 4 // jnp.dtype(dtype).itemsize)
    rows0 = -(-n // _LANES)
    block_rows = min(_MAX_BLOCK_ROWS, -(-rows0 // sublanes) * sublanes)
    rows = -(-rows0 // block_rows) * block_rows
    return rows * _LANES, block_rows, rows // block_rows


def _to_tiles(x, padded_len: int):
    pad = padded_len - x.shape[0]
    if pad:
        x = jnp.pad(x, (0, pad))
    return x.reshape(-1, _LANES)


def _f32(ref):
    return ref[:, :].astype(jnp.float32)


def _store(ref, value):
    ref[:, :] = value.astype(ref.dtype)


def _elementwise_call(name: str, kernel, state, agg, interpret: bool,
                      scalars=None):
    """Run ``kernel`` over ``(*state, agg)`` tiled ``(block_rows, 128)``,
    every ``state`` vector updated in place; returns the new state vectors
    at their original length.  ``scalars`` (a small f32 vector) rides
    scalar prefetch and arrives as the kernel's first ref.  ``name`` is
    the kernel's name in a device trace: the jitted wrapper's own, which
    the operation carried before it was given one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = state[0].shape[0]
    padded, block_rows, grid = _tile_geometry(n, state[0].dtype)
    tiles = [_to_tiles(x, padded) for x in (*state, agg)]
    n_prefetch = 0 if scalars is None else 1
    # Index maps receive the prefetched scalar ref as a trailing argument.
    spec = pl.BlockSpec((block_rows, _LANES), lambda i, *_: (i, 0))
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tiles[:-1]
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(grid,),
            in_specs=[spec] * len(tiles),
            out_specs=tuple([spec] * len(state)),
        ),
        input_output_aliases={
            n_prefetch + i: i for i in range(len(state))
        },
        interpret=interpret,
        name=name,
    )(*(() if scalars is None else (scalars,)), *tiles)
    return tuple(o.reshape(-1)[:n] for o in outs)


@functools.partial(jax.jit, static_argnames=("lr", "momentum", "interpret"))
def sgd_update(store, mom, agg, *, interpret: bool, lr: float = 0.01,
               momentum: float = 0.9):
    """One fused pass: ``mom = momentum*mom + agg; store -= lr*mom``.

    Returns ``(new_store, new_mom)``; both alias their inputs' buffers.
    """

    def kernel(store_ref, mom_ref, agg_ref, out_store_ref, out_mom_ref):
        m = momentum * _f32(mom_ref) + _f32(agg_ref)
        _store(out_mom_ref, m)
        _store(out_store_ref, _f32(store_ref) - lr * m)

    return _elementwise_call("sgd_update", kernel, (store, mom), agg,
                             interpret)


@functools.partial(jax.jit, static_argnames=("lr", "eps", "interpret"))
def adagrad_update(store, acc, agg, *, interpret: bool, lr: float = 0.01,
                   eps: float = 1e-8):
    """One fused Adagrad pass: ``acc += agg**2;
    store -= lr*agg/(sqrt(acc)+eps)``.

    Returns ``(new_store, new_acc)``; both alias their inputs' buffers —
    the elementwise twin of the sparse engine's row-wise variant
    (parallel/sparse.py), completing the server-optimizer family
    (kv_app.h:430-452 hot loop as one HBM pass).
    """

    def kernel(store_ref, acc_ref, agg_ref, out_store_ref, out_acc_ref):
        g = _f32(agg_ref)
        a = _f32(acc_ref) + g * g
        _store(out_acc_ref, a)
        _store(out_store_ref,
               _f32(store_ref) - lr * g / (jnp.sqrt(a) + eps))

    return _elementwise_call("adagrad_update", kernel, (store, acc), agg,
                             interpret)


@functools.partial(
    jax.jit,
    static_argnames=("lr", "beta1", "beta2", "eps", "interpret"),
)
def adam_update(store, m, v, agg, step, *, interpret: bool,
                lr: float = 1e-3, beta1: float = 0.9, beta2: float = 0.999,
                eps: float = 1e-8):
    """Fused Adam step: one HBM pass updating (store, m, v) in place.

    ``step`` is the 1-based step count (dynamic scalar) for bias
    correction; the correction is folded into a per-call scalar
    ``alpha_t = lr * sqrt(1-b2^t) / (1-b1^t)`` (the standard efficient
    form) so the kernel consumes only vectors plus one prefetched scalar.
    """
    t = jnp.asarray(step, jnp.float32)
    alpha_t = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    scalars = jnp.stack([alpha_t]).astype(jnp.float32)

    def kernel(scalar_ref, store_ref, m_ref, v_ref, agg_ref,
               out_store_ref, out_m_ref, out_v_ref):
        g = _f32(agg_ref)
        m_new = beta1 * _f32(m_ref) + (1 - beta1) * g
        v_new = beta2 * _f32(v_ref) + (1 - beta2) * g * g
        _store(out_m_ref, m_new)
        _store(out_v_ref, v_new)
        _store(out_store_ref,
               _f32(store_ref)
               - scalar_ref[0] * m_new / (jnp.sqrt(v_new) + eps))

    return _elementwise_call("adam_update", kernel, (store, m, v), agg,
                             interpret, scalars=scalars)
