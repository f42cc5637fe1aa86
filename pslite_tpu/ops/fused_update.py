"""Fused optimizer-update kernels (Pallas, TPU).

The server-side update is the aggregation hot loop of the reference
(``KVServerDefaultHandle``, kv_app.h:430-452, executed per push).  On TPU
the update is HBM-bandwidth-bound; these kernels apply the whole optimizer
step (SGD+momentum / Adagrad / Adam) in **one** tiled pass over the shard
with in-place aliasing — guaranteeing the single-pass fusion rather than
hoping XLA finds it.  LAMB alone takes two (``lamb_moments``,
``lamb_apply``): its step of a tensor is scaled by the norms of the whole
tensor, so nothing may be written before every element has been read;
where one shard holds the bucket, one pass that keeps a tensor in VMEM
until then (``lamb_one_pass``) for every tensor that fits.

Layout: flat vectors are zero-padded and reshaped to ``(rows, 128)`` with
``rows`` a multiple of the dtype's sublane tile (8 for 4-byte, 16 for
2-byte dtypes), and the kernels use 2-D ``(block_rows, 128)`` BlockSpecs —
rank-1 blocks and sub-tile blocks pass the interpreter but fail Mosaic
lowering on real TPU hardware.  (The one rank-1 block here, the vector
the LAMB kernels leave beside the store, is whole 1,024-element tiles of a
vector the chip lays out in such: :func:`lamb_apply_pulls`.)

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
import math
from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

_LANES = 128
_SUBLANES = 8
_MAX_BLOCK_ROWS = 512  # (512, 128) fp32 block = 256 KiB per operand


def _tile_geometry(n: int, *dtypes):
    """(padded_len, block_rows, grid) for a flat length n of ``dtypes``:
    the narrowest of them sets the sublane tile (a gradient may be
    narrower than the state it updates)."""
    sublanes = _SUBLANES * max(
        max(1, 4 // jnp.dtype(dt).itemsize) for dt in dtypes)
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
    padded, block_rows, grid = _tile_geometry(n, state[0].dtype, agg.dtype)
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


# -- LAMB: the keys' norms between the moments and the store -------------------

# Elements a grid step of the two LAMB kernels.  A bucket registered with
# per-key lengths is kept in whole tiles a shard (``parallel/engine.py``
# ``_padded_len``), so store and moments reshape to ``(rows, 128)`` in
# place.  The gradient does not: see :func:`lamb_moments`.
LAMB_TILE = _MAX_BLOCK_ROWS * _LANES

# Tiles a key may reach into and still be updated in one pass
# (:func:`lamb_plan`).  ``lamb_one_pass`` holds ``p`` and ``u`` of that many
# tiles in VMEM, 8 B an element: half of a v5e TensorCore's 128 MiB
# (Google Cloud TPU v5e documentation; ``benchmark/lamb_bytes.py`` counts
# by the same figure), the other half left to the eight streams' double
# buffers (4 MiB) and to Mosaic.  A key of up to 127 tiles and one value,
# 8.3 M values, lies in 128 tiles wherever it starts.
LAMB_HELD_TILES = 128


def lamb_blocks(starts, padded_len: int, shards: int):
    """``int32[shards, 2 * tiles]``: for each tile of each shard of a
    bucket ``padded_len`` long, the first key that reaches into it and one
    past the last (``starts`` as :func:`_tile_keys` has them)."""
    offs = np.arange(padded_len // LAMB_TILE, dtype=np.int64) * LAMB_TILE
    return np.stack(
        [np.searchsorted(starts[1:], offs, "right"),
         np.searchsorted(starts[:-1], offs + LAMB_TILE, "left")],
        axis=1).astype(np.int32).reshape(shards, -1)


class LambPlan(NamedTuple):
    """Which keys of a bucket :func:`lamb_one_pass` updates in one pass, and
    what the kernels need to know of it (:func:`lamb_plan`)."""

    blocks: np.ndarray   # lamb_blocks
    held: np.ndarray     # bool[K]: the key takes one pass
    tiles: np.ndarray    # int32[n]: a shard's tiles lamb_moments walks
    walked: np.ndarray   # int32[tiles a shard]: 1 where it walks tile i
    stepped: np.ndarray  # int32[tiles a shard]: i, or for a walked tile
    #                      the nearest that is not (before it if any)
    lag: int             # grid steps lamb_one_pass's store lies behind
    one_pass_len: int    # the held keys' elements


def lamb_plan(starts, padded_len: int, shards: int) -> LambPlan:
    """How LAMB goes over a bucket ``padded_len`` long whose keys begin at
    ``starts`` (one more entry closes the last).

    No element of a key may be written before the norms of the whole key
    are known.  Where one shard holds the bucket, a key that reaches into
    at most ``LAMB_HELD_TILES`` tiles is *held*: :func:`lamb_one_pass`
    reads it once, keeps its ``p`` and ``u`` in VMEM until its last element
    has passed and writes the store ``lag`` grid steps behind what it
    reads, ``lag`` + 1 the most tiles a held key of this bucket reaches
    into.  Every other key takes two passes: :func:`lamb_moments` walks its
    ``tiles`` (with their other keys' elements: a tile is walked whole),
    the program makes its ratio, the kernel that writes the store reads
    its p, m and v again.  A key that lies in walked tiles alone is one of
    these, however short: the first pass has its whole sums, and holding
    it would save no byte.  So a held key has a tile that is not walked,
    and where every tile is walked no key is held and the program is the
    two passes (``stepped`` names such a tile for every walked one: a
    kernel that held a key over walked tiles alone would write none of
    its m and v blocks, and the chip writes back a block's window whether
    or not the kernel stored to it).  Over several shards a key's norm is
    a sum over the shards, so no key is held and every tile is walked."""
    starts = np.asarray(starts, dtype=np.int64)
    n = padded_len // LAMB_TILE // shards
    first = starts[:-1] // LAMB_TILE
    reach = np.where(starts[1:] > starts[:-1],
                     (starts[1:] - 1) // LAMB_TILE - first + 1, 0)
    held = (reach > 0) & (reach <= LAMB_HELD_TILES) & (shards == 1)
    walked = np.full(n, shards > 1)
    if shards == 1:
        for k in np.flatnonzero((reach > 0) & ~held):
            walked[first[k]:first[k] + reach[k]] = True
        before = np.concatenate([[0], np.cumsum(walked)])
        held &= before[first + reach] - before[first] < reach
    own = np.flatnonzero(~walked)
    stepped = np.zeros(n, dtype=np.int32)
    if own.size:
        stepped = own[np.maximum(
            np.searchsorted(own, np.arange(n), "right") - 1, 0)]
    return LambPlan(lamb_blocks(starts, padded_len, shards), held,
                    np.flatnonzero(walked).astype(np.int32),
                    walked.astype(np.int32), stepped.astype(np.int32),
                    int(reach[held].max()) - 1 if held.any() else 0,
                    int(np.diff(starts)[held].sum()))


def _bias_corrections(step, beta1: float, beta2: float):
    """``[1/(1-b1^t), 1/(1-b2^t)]``.  ``1 - b**t`` is ``-expm1(t*log(b))``:
    ``b ** t`` in f32 loses the digits of ``1 - 0.999**t`` at small t."""
    t = jnp.asarray(step, jnp.float32)
    return jnp.stack([
        -1.0 / jnp.expm1(t * math.log(beta1)),
        -1.0 / jnp.expm1(t * math.log(beta2)),
    ]).astype(jnp.float32)


def _lamb_direction(scal_ref, m, v, eps: float):
    """``mh / (sqrt(vh) + eps)``: the part of LAMB's ``u`` that knows no
    key.  Both kernels compute it from the same m and v with the same
    operations, so the ``u`` whose norm was taken is the ``u`` applied."""
    return (m * scal_ref[0]) / (jnp.sqrt(v * scal_ref[1]) + eps)


def _lamb_moments_of(m_old, v_old, g, beta1: float, beta2: float):
    """``b*x + (1-b)*y`` as ``x + (1-b)*(y - x)``: f32 holds 0.999 to
    1.3e-8, which in the first form is 1.3e-5 of the 0.001 that v settles
    by."""
    return (m_old + (1 - beta1) * (g - m_old),
            v_old + (1 - beta2) * (g * g - v_old))


def _tile_iota():
    """Each element's place in its tile, ``int32[rows, 128]``."""
    shape = (_MAX_BLOCK_ROWS, _LANES)
    return (lax.broadcasted_iota(jnp.int32, shape, 0) * _LANES
            + lax.broadcasted_iota(jnp.int32, shape, 1))


def _row_tile(g_ref, t, width: int):
    """Tile ``t`` of the gradient row (``[1, width]``, ``(1, LAMB_TILE)``
    of it in ``g_ref``) as f32 ``(rows, 128)``, zeros behind the row's
    end.  Widened, then folded: the fold is of f32 tiles whatever the
    row's dtype."""
    g = g_ref[...].astype(jnp.float32).reshape(_MAX_BLOCK_ROWS, _LANES)
    if width % LAMB_TILE:
        g = jnp.where(_tile_iota() < width - t * LAMB_TILE, g, 0.0)
    return g


def _tile_keys(starts_ref, blocks_ref, base_ref, t, body, init):
    """Fold ``body(k, mask, carry)`` over the keys that own an element of
    the shard's tile ``t``; ``mask`` picks key k's elements in the tile.
    ``starts`` are the keys' first elements in the whole bucket (one more
    entry closes the last key), ``base`` this shard's first element there,
    ``blocks[2*t]`` / ``[2*t+1]`` the first key of tile t and one past its
    last.  A key's border lies on no tile's, so a tile is asked about
    every key that reaches into it; padding belongs to none."""
    off = base_ref[0] + t * LAMB_TILE
    idx = _tile_iota()

    def step(k, carry):
        lo = starts_ref[k] - off
        hi = starts_ref[k + 1] - off
        return body(k, (idx >= lo) & (idx < hi), carry)

    return lax.fori_loop(blocks_ref[2 * t], blocks_ref[2 * t + 1], step,
                         init)


def _lamb_call(name: str, kernel, prefetch, tiles, n_out: int, sums: int,
               interpret: bool, row=None, pulled_len: int = 0,
               pulled_dtype=None, steps=None, read=None, fresh=None,
               lag: int = 0, scratch=()):
    """One LAMB kernel over ``tiles`` (flat, whole tiles long): the first
    ``n_out`` of them updated in place; with ``sums`` also an
    ``f32[sums]`` vector the kernel adds to in SMEM from tile to tile.
    ``row`` is one more input, ``[1, n]`` with ``n`` anywhere in the last
    tile: the kernel is handed ``(1, LAMB_TILE)`` of it a grid step, and
    behind its end whatever lies there.  ``pulled_len`` is the length of one
    more result, the last and aliased to nothing, that may end anywhere in
    the last tile too: a vector ``[pulled_len]`` (of ``pulled_dtype``; the
    first tile's where none is given) of which the kernel writes
    ``(LAMB_TILE,)`` a grid step, and what it writes behind the vector's
    end goes nowhere.  (A vector and not a row ``[1, pulled_len]``: the chip
    lays a row out in tiles of 128 and a vector in tiles of 1,024, so a
    row reshaped to the vector a program returns is a copy of it.)

    The grid has ``steps`` steps (one a tile) and at step i the kernel
    holds tile ``read(i, *prefetch)`` (i) of every operand, but for the
    first result and the vector, of which it holds tile ``i - lag`` (the
    first until then): with ``lag`` they are written that many steps
    behind what is read, out of ``scratch`` (VMEM and SMEM the kernel
    keeps from step to step, its last arguments).  Of the row and of the
    results after the first it holds tile ``fresh(i, *prefetch)`` where
    that is given: a step that names its neighbour's tile moves neither
    (a block is fetched, and written back, when its index changes), and
    must leave the results' as it finds them."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = tiles[0].shape[0]
    assert n % LAMB_TILE == 0, (n, LAMB_TILE)
    steps = n // LAMB_TILE if steps is None else steps
    read = read or (lambda i, *_: i)
    fresh = fresh or read

    def late(i, *_):
        return jnp.maximum(i - lag, 0) if lag else read(i, *_)

    def spec(at):
        return pl.BlockSpec((_MAX_BLOCK_ROWS, _LANES),
                            lambda i, *_: (at(i, *_), 0))

    tiles = [t.reshape(-1, _LANES) for t in tiles]
    in_specs = [spec(read)] * len(tiles)
    if row is not None:
        assert row.ndim == 2 and n - LAMB_TILE < row.shape[1] <= n, (
            row.shape, n)
        tiles.append(row)
        in_specs.append(pl.BlockSpec((1, LAMB_TILE),
                                     lambda i, *_: (0, fresh(i, *_))))
    out_shape = [jax.ShapeDtypeStruct(t.shape, t.dtype)
                 for t in tiles[:n_out]]
    out_specs = [spec(late)] + [spec(fresh)] * (n_out - 1)
    if sums:
        out_shape.append(jax.ShapeDtypeStruct((sums,), jnp.float32))
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    if pulled_len:
        assert n - LAMB_TILE < pulled_len <= n, (pulled_len, n)
        out_shape.append(jax.ShapeDtypeStruct(
            (pulled_len,), pulled_dtype or tiles[0].dtype))
        out_specs.append(pl.BlockSpec((LAMB_TILE,),
                                      lambda i, *_: (late(i, *_),)))
    held = sum(math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
               for s in scratch if hasattr(s, "dtype"))
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(steps,),
            in_specs=in_specs,
            out_specs=tuple(out_specs),
            scratch_shapes=tuple(scratch),
        ),
        input_output_aliases={len(prefetch) + i: i for i in range(n_out)},
        # What is held, and 16 MiB (the scoped default on a v5e) for the
        # streams' double buffers and Mosaic's own.
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=held + 16 * 2 ** 20) if held else None),
        interpret=interpret,
        name=name,
    )(*prefetch, *tiles)
    return tuple(o.reshape(-1) if i < n_out else o
                 for i, o in enumerate(outs))


@functools.partial(jax.jit,
                   static_argnames=("beta1", "beta2", "eps", "interpret"))
def lamb_moments(store, m, v, agg, step, starts, decay, blocks, base,
                 tiles, *, interpret: bool, beta1: float = 0.9,
                 beta2: float = 0.999, eps: float = 1e-6):
    """LAMB's first pass over one shard: the moments, in place, and what
    the trust ratios are made of.  It walks ``tiles`` (``int32[n]``, the
    shard's tiles that hold a key of two passes, :func:`lamb_plan`) and
    leaves every other tile as it is.

    ``agg`` is the summed gradient as a row, ``[1, n]``, and may end
    anywhere in the shard's last tile: a job's gradient has the length of
    its keys, not of the kernel's tiles, and a pad in front of the kernel
    is a copy of the whole gradient (5.2 ms of a 26 ms step at 336 M
    values, PERF.md, PR 33).  The kernel takes ``(1, LAMB_TILE)`` of the
    row a grid step, folds it to ``(rows, 128)`` in VMEM and reads zeros
    behind the row's end.  The row may be narrower than the state (a bf16
    job's gradient over an f32 store): it is widened here in VMEM and by
    no pass before the kernel.  (The chip lays a single row of a 2-byte
    type out in tiles of two rows, ``T(2,128)(2,1)``, half of each
    padding, so the bf16 row is held and read at the f32 row's bytes;
    PERF.md, PR 41, has what a packed vector read instead.)

    ``m = b1*m + (1-b1)*g; v = b2*v + (1-b2)*g*g`` (computed as
    ``m + (1-b1)*(g-m)``, which settles at g whatever f32 makes of the
    betas).  With ``u = mh/(sqrt(vh)+eps) + decay[k]*p`` for key k's
    elements it returns ``(new_m, new_v, sums)``: ``sums[2k]`` the sum of
    ``p*p`` and ``sums[2k+1]`` of ``u*u`` over the elements of key k that
    lie in the walked tiles of this shard (see :func:`_tile_keys` for
    ``starts``, ``blocks``, ``base``): the whole of it for a key of two
    passes, and of no use for a held one.
    ``u`` is not kept: ``lamb_apply`` makes it again from m and v, the
    same bytes as writing and reading it and one vector less to hold.
    """
    scal = _bias_corrections(step, beta1, beta2)
    n_sums = 2 * decay.shape[0]

    def kernel(scal_ref, base_ref, starts_ref, decay_ref, blocks_ref,
               tiles_ref, m_ref, v_ref, p_ref, g_ref, out_m_ref, out_v_ref,
               sums_ref):
        from jax.experimental import pallas as pl

        @pl.when(pl.program_id(0) == 0)
        def _():
            def zero(j, c):
                sums_ref[j] = 0.0
                return c

            lax.fori_loop(0, n_sums, zero, 0)

        t = tiles_ref[pl.program_id(0)]
        p = _f32(p_ref)
        m_new, v_new = _lamb_moments_of(
            _f32(m_ref), _f32(v_ref), _row_tile(g_ref, t, agg.shape[1]),
            beta1, beta2)
        _store(out_m_ref, m_new)
        _store(out_v_ref, v_new)
        d = _lamb_direction(scal_ref, m_new, v_new, eps)
        pp = p * p

        def add(k, mask, c):
            u = d + decay_ref[k] * p
            sums_ref[2 * k] += jnp.sum(jnp.where(mask, pp, 0.0))
            sums_ref[2 * k + 1] += jnp.sum(jnp.where(mask, u * u, 0.0))
            return c

        _tile_keys(starts_ref, blocks_ref, base_ref, t, add, 0)

    return _lamb_call("lamb_moments", kernel,
                      (scal, base, starts, decay, blocks, tiles),
                      (m, v, store), 2, n_sums, interpret, row=agg,
                      steps=tiles.shape[0],
                      read=lambda i, *refs: refs[5][i])


def lamb_apply_pulls(total_len: int) -> bool:
    """Whether :func:`lamb_apply` can leave the new parameters as a vector
    of ``total_len`` besides: the kernel writes it in blocks of whole
    1,024-element tiles, which is how the chip lays out a vector of more
    than 512 elements, of 4 bytes or of 2; a shorter one lies in a single
    tile of its own length, and Mosaic refuses the kernel (a cut of so few
    is nothing)."""
    return total_len > _SUBLANES * _LANES // 2


def _lamb_put(new_p, out_p_ref, pulled_ref):
    """The new parameters of a tile into the store's block and, where the
    kernel leaves the pulled vector, into its block of that (of the
    vector's dtype: the stored value rounded to nearest-even)."""
    new_p = new_p.astype(out_p_ref.dtype)
    out_p_ref[:, :] = new_p
    for ref in pulled_ref:
        ref[...] = new_p.astype(ref.dtype).reshape(LAMB_TILE)


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "interpret", "pulled_len",
                     "pulled_dtype"))
def lamb_apply(store, m, v, step, starts, decay, scale, blocks, base, *,
               interpret: bool, beta1: float = 0.9, beta2: float = 0.999,
               eps: float = 1e-6, pulled_len: int = 0, pulled_dtype=None):
    """LAMB's second pass over one shard: ``p -= scale[k] * u`` for key
    k's elements, ``scale[k] = lr * r_k`` and ``u`` as in
    :func:`lamb_moments`, whose m and v these are: ``u`` is made again
    from them.  The store in place; padding keeps its value.

    Returns ``(new_store, pulled)``.  With ``pulled_len`` (the bucket's
    ``total_len``, anywhere in the shard's last tile;
    :func:`lamb_apply_pulls`) ``pulled`` is the new parameters once more,
    ``[pulled_len]`` in a buffer of its own: the pulled values of a bucket
    that one shard holds whole, written from VMEM where every new ``p``
    already is (4 B an element in place of the 8 B of a cut after the
    kernel: the mirror of how :func:`lamb_moments` reads the gradient),
    and of ``pulled_dtype`` where the job's parameters are narrower than
    the store: each the stored value rounded to nearest-even, 2 B an
    element where a narrowing pass after the kernel reads 4 and writes 2.
    Without ``pulled_len`` ``pulled`` is None."""
    from jax.experimental import pallas as pl

    scal = _bias_corrections(step, beta1, beta2)

    def kernel(scal_ref, base_ref, starts_ref, decay_ref, scale_ref,
               blocks_ref, p_ref, m_ref, v_ref, out_p_ref, *pulled_ref):
        p = _f32(p_ref)
        d = _lamb_direction(scal_ref, _f32(m_ref), _f32(v_ref), eps)

        def pick(k, mask, upd):
            return jnp.where(mask, scale_ref[k] * (d + decay_ref[k] * p),
                             upd)

        _lamb_put(p - _tile_keys(starts_ref, blocks_ref, base_ref,
                                 pl.program_id(0), pick, jnp.zeros_like(p)),
                  out_p_ref, pulled_ref)

    new_store, *pulled = _lamb_call(
        "lamb_apply", kernel, (scal, base, starts, decay, scale, blocks),
        (store, m, v), 1, 0, interpret, pulled_len=pulled_len,
        pulled_dtype=pulled_dtype)
    return new_store, (pulled[0] if pulled else None)


@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "interpret", "pulled_len",
                     "pulled_dtype", "lr", "lag"))
def lamb_one_pass(store, m, v, agg, step, starts, decay, scale, blocks,
                  base, held, adapt, walked, stepped, *, lr: float, lag: int,
                  interpret: bool, beta1: float = 0.9, beta2: float = 0.999,
                  eps: float = 1e-6, pulled_len: int = 0, pulled_dtype=None):
    """LAMB over one shard that holds its bucket whole, in one pass over
    every key that is ``held`` (``int32[K]``, 1 for a held key;
    :func:`lamb_plan`, whose ``walked``, ``stepped`` and ``lag`` it takes
    too): the store, m and v in place.  Its custom call goes by the name
    of :func:`lamb_apply`, whose place in the program it takes: the pass
    that writes the store.

    A grid step reads tile i of ``agg`` (the gradient, the row
    :func:`lamb_moments` takes), m, v and p, writes m and v, and puts
    ``p`` and ``u`` into a ring of ``lag`` + 1 tiles in VMEM while the
    keys' ``sum(p*p)`` and ``sum(u*u)`` add up in SMEM; with a held key's
    last element its ``scale`` is made on the spot (``lr * |p|/|u|``,
    ``lr`` where a norm is zero or ``adapt[k]`` is: ``engine.py``
    ``_lamb_ratios``' formula).  The same step writes tile ``i - lag`` of
    the store from the ring: every key that reaches into it has ended by
    then.  So the ``u`` normed is the ``u`` applied, one value in VMEM,
    and p, m, v are read once: 32 B an element where two passes move 44.
    A tile :func:`lamb_moments` ``walked`` has its new m and v already,
    all of it: there they are read and neither stepped nor written, no
    gradient is fetched (the step names tile ``stepped[i]`` for those
    three), and a key of two passes finds its ``scale`` given.  The order
    in which a held key's partial sums are added is the tiles' (two passes
    add in the same order and then across the shards).

    Returns ``(new_store, new_m, new_v, pulled)``, ``pulled`` as
    :func:`lamb_apply` leaves it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scal = _bias_corrections(step, beta1, beta2)
    n_tiles = store.shape[0] // LAMB_TILE
    n_keys = decay.shape[0]

    def kernel(scal_ref, base_ref, starts_ref, decay_ref, scale_ref,
               blocks_ref, held_ref, adapt_ref, walked_ref, stepped_ref,
               p_ref, m_ref, v_ref, g_ref, out_p_ref, out_m_ref, out_v_ref,
               *rest):
        *pulled_ref, ring_p, ring_u, sums_ref, made_ref = rest
        i = pl.program_id(0)

        @pl.when(i == 0)
        def _():
            def first(k, c):
                sums_ref[2 * k] = 0.0
                sums_ref[2 * k + 1] = 0.0
                made_ref[k] = scale_ref[k]
                return c

            lax.fori_loop(0, n_keys, first, 0)

        @pl.when(i < n_tiles)
        def _():
            p = _f32(p_ref)
            m_old, v_old = _f32(m_ref), _f32(v_ref)
            m_new, v_new = _lamb_moments_of(
                m_old, v_old, _row_tile(g_ref, i, agg.shape[1]), beta1,
                beta2)
            walked = walked_ref[i] != 0
            m_new = jnp.where(walked, m_old, m_new)
            v_new = jnp.where(walked, v_old, v_new)

            @pl.when(jnp.logical_not(walked))
            def _():
                _store(out_m_ref, m_new)
                _store(out_v_ref, v_new)

            d = _lamb_direction(scal_ref, m_new, v_new, eps)
            pp = p * p
            end = base_ref[0] + (i + 1) * LAMB_TILE

            def add(k, mask, u):
                u_k = d + decay_ref[k] * p

                @pl.when(held_ref[k] != 0)
                def _():
                    sums_ref[2 * k] += jnp.sum(jnp.where(mask, pp, 0.0))
                    sums_ref[2 * k + 1] += jnp.sum(
                        jnp.where(mask, u_k * u_k, 0.0))

                    @pl.when(starts_ref[k + 1] <= end)
                    def _():
                        shape = (_SUBLANES, _LANES)
                        p_norm = jnp.sqrt(jnp.full(shape, sums_ref[2 * k]))
                        u_norm = jnp.sqrt(
                            jnp.full(shape, sums_ref[2 * k + 1]))
                        ratio = jnp.max(jnp.where(
                            (p_norm > 0) & (u_norm > 0), p_norm / u_norm,
                            1.0))
                        made_ref[k] = lr * jnp.where(adapt_ref[k] != 0,
                                                     ratio, 1.0)

                return jnp.where(mask, u_k, u)

            slot = i % (lag + 1)
            ring_p[slot] = p
            ring_u[slot] = _tile_keys(starts_ref, blocks_ref, base_ref, i,
                                      add, jnp.zeros_like(p))

        @pl.when(i >= lag)
        def _():
            slot = (i - lag) % (lag + 1)
            p, u = ring_p[slot], ring_u[slot]
            _lamb_put(p - _tile_keys(
                starts_ref, blocks_ref, base_ref, i - lag,
                lambda k, mask, upd: jnp.where(mask, made_ref[k] * u, upd),
                jnp.zeros_like(p)), out_p_ref, pulled_ref)

    ring = pltpu.VMEM((lag + 1, _MAX_BLOCK_ROWS, _LANES), jnp.float32)
    new_store, new_m, new_v, *pulled = _lamb_call(
        "lamb_apply", kernel,
        (scal, base, starts, decay, scale, blocks, held, adapt, walked,
         stepped),
        (store, m, v), 3, 0, interpret, row=agg, pulled_len=pulled_len,
        pulled_dtype=pulled_dtype, steps=n_tiles + lag,
        read=lambda i, *_: jnp.minimum(i, n_tiles - 1),
        fresh=lambda i, *refs: refs[9][jnp.minimum(i, n_tiles - 1)], lag=lag,
        scratch=(ring, ring, pltpu.SMEM((2 * n_keys,), jnp.float32),
                 pltpu.SMEM((n_keys,), jnp.float32)))
    return new_store, new_m, new_v, (pulled[0] if pulled else None)
