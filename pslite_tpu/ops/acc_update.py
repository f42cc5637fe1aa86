"""Accumulator read-update-write kernel (Pallas, TPU): row-wise Adagrad's
touched accumulators taken, stepped and written back in one pass.

The rows a stateful sparse push touches arrive combined
(``parallel/sparse.py`` ``_combine_rows``): ascending, each once, the valid
ones first.  XLA is told none of that: its 1-D gather fetches the touched
accumulators one scalar at a time (20 ns a slot on a v5e, dropped slots
included) and its scatter writes them back the same way.  ``acc_update``
walks the accumulator once, as ``[R / 128, 128]``, in tiles of
``_TILE_ROWS`` rows, beside the list of touched rows in chunks of ``_CHUNK``
ids.  Because the ids ascend, the ids of a tile are a run of the list, and a
grid step holds one (tile, chunk) pair of the merge of the two walks: a
step moves on to the next tile while the chunk reaches beyond this one, and
to the next chunk otherwise, ``tiles + chunks - 1`` steps in all, set out
before the kernel starts from each chunk's last id; the grid ends with the
last chunk that holds one of the first ``n`` ids (a step costs ~0.4 us
whatever it holds).  Inside a step gather
and scatter are products on the MXU with one one-hot ``A[r, k]`` (id ``k``
lies in the tile's row ``r``): ``tile^T @ A`` brings each id's 128-lane row,
of which a mask from ``id % 128`` keeps one lane, and ``A @ placed^T`` puts
each id's ``g2``, placed in its lane, onto the tile.  No two steps update one
accumulator and a tile is resident while chunks pass under it, so nothing is
ordered but the walk itself.

The arithmetic is ``acc + g2`` in f32, once an accumulator, as XLA's: a
one-hot is exact in bf16, every f32 is split exactly into three bf16 parts
(``segment_sum.py``), every product is exact and the MXU adds zeros to it.
A tile or a chunk that holds a non-finite value is moved without it and
given it back from one more product with flags: ``0 * inf`` is NaN, so in
the plain product it would spill into every id of the chunk, or every row
of the tile.  An accumulator no id names is written back as it was read
(``-0.0`` and NaN payloads too); the one departure from XLA's bits is a
touched accumulator of ``-0.0`` under a ``g2`` of zero, which stays ``-0.0``.

Conventions as in ``row_add.py``: the caller decides ``interpret``, the
trace is kept between processes, and the kernel carries its name into a
device trace (``%acc_update.<n>``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compile_cache import call_traced
from .segment_sum import _join, _split

_LANES = 128
# Rows of 128 accumulators a tile: (256, 128) f32 = 32,768 accumulators.
_TILE_ROWS = 256
# Touched ids a chunk: a (256, 256) one-hot in bf16.
_CHUNK = 256
_NEVER = jnp.iinfo(jnp.int32).max  # an id past ``n``: in no tile


def acc_update(acc, rows, g2, n, *, interpret: bool):
    """``acc[rows[i]] += g2[i]`` for ``i < n``; returns the new accumulator,
    which aliases ``acc``'s buffer where that is donated, and ``f32[m]``
    whose entry ``i < n`` is the new ``acc[rows[i]]``.

    ``acc`` is ``f32[R]``, ``rows`` ``s32[m]`` ascending and unique in its
    first ``n`` entries, ``g2`` ``f32[m]``, ``n`` an integer scalar on the
    device.  Entries ``i >= n`` of ``rows`` and ``g2`` may hold anything:
    they are neither read into a result nor written, and what the second
    result holds there is not for use.  ``R`` is whole 128s wherever the
    array is to be updated in place (the sparse engine keeps every
    accumulator so, ``parallel/sparse.py`` ``_acc_rows``); any other length
    is padded to them and cut again, a copy each way.

    Compiled for the chip, the kernel's trace is kept between processes
    (``utils/compile_cache.py`` ``call_traced``), as ``row_add``'s is.
    """
    rows = rows.astype(jnp.int32)
    n = jnp.reshape(n, (1,)).astype(jnp.int32)
    (R,) = acc.shape
    # The interpreter returns an aliased operand with a ragged last block
    # padded to whole blocks, which its own result type then refuses: it
    # is given whole tiles (the chip takes the ragged one).
    ragged = -R % (_tile_rows(R) * _LANES if interpret else _LANES)
    if ragged:
        acc = jnp.pad(acc, (0, ragged))
    new_acc, new_rows = (
        _acc_update(acc, rows, g2, n, True) if interpret
        else call_traced(_acc_update, __file__, "tpu", acc, rows, g2, n))
    return (new_acc[:R] if ragged else new_acc), new_rows


def _tile_rows(R: int) -> int:
    """Rows of 128 accumulators a tile: ``_TILE_ROWS``, or all ``R`` holds
    in whole sublanes where that is fewer."""
    return min(_TILE_ROWS, -(-R // (8 * _LANES)) * 8)


def steps(R: int, m: int) -> int:
    """Grid steps of the pass over ``R`` accumulators beside ``m`` ids."""
    tiles = -(-R // (_tile_rows(R) * _LANES))
    return tiles + -(-m // _CHUNK) - 1


def _join_rows(tall):
    """The three 128-row thirds of a product with ``_split`` parts stacked
    by row, added in ``_split``'s order."""
    return (tall[:_LANES] + tall[_LANES:2 * _LANES]) + tall[2 * _LANES:]


def _walk(rows, n, K: int, T: int, tile: int):
    """The merged walk of ``T`` tiles of ``tile`` accumulators and the
    chunks of ``K`` of ``rows`` (whole chunks), of which the first ``n[0]``
    ids are live: per chunk its ids (``_NEVER`` where not live) and its
    first and last live id, per step its chunk (its tile is the step less
    the chunk), and the steps to take.

    A chunk is left at the tile of its last live id, so the step that
    leaves chunk ``c`` is ``c + that tile``; chunks of no live id trail at
    the last tile, and the last chunk is never left: where it is live and
    ends below the last tile, the tiles that follow pass under it (and
    hold none of its ids).  The steps end with the last live chunk's
    last; what follows them would stand on the last tile under chunks of
    no live id."""
    C = rows.shape[0] // K
    S = T + C - 1
    live = (lax.iota(jnp.int32, C * K) < n[0]).reshape(C, K)
    ids = jnp.where(live, rows.reshape(C, K), _NEVER)
    first = ids[:, 0]
    last = jnp.max(jnp.where(live, ids, -1), axis=1)
    leave = lax.iota(jnp.int32, C) + jnp.where(
        last >= 0, jnp.minimum(last // tile, T - 1), T - 1)
    chunk = jnp.minimum(
        jnp.sum(leave[None, :] < lax.iota(jnp.int32, S)[:, None],
                axis=1, dtype=jnp.int32), C - 1)
    walk = T + jnp.clip(-(-n[0] // K), 1, C) - 1
    return ids, first, last, chunk, walk


def _acc_update(acc, rows, g2, n, interpret: bool = False):
    """The Pallas call and the little around it; of its four arrays alone
    it is what the chip runs (``call_traced`` traces it so)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (R,), (m,) = acc.shape, rows.shape
    assert acc.dtype == g2.dtype == jnp.float32, (acc.dtype, g2.dtype)
    assert R % _LANES == 0 and g2.shape == (m,), (acc.shape, g2.shape)
    K = _CHUNK
    pad = -m % K
    if pad:  # no batch of a cell; past ``n`` whatever ``n`` is
        rows = jnp.pad(rows, (0, pad))
        g2 = jnp.pad(g2, (0, pad))
    C = (m + pad) // K
    RR = R // _LANES
    TR = _tile_rows(R)
    T = -(-RR // TR)
    tile = TR * _LANES  # accumulators a tile
    acc = acc.reshape(RR, _LANES)

    ids, first, last, chunk, walk = _walk(rows, n, K, T, tile)

    def tn(lhs, rhs):  # lhs^T @ rhs
        return lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def nt(lhs, rhs):  # lhs @ rhs^T
        return lax.dot_general(lhs, rhs, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)

    def flags(x):
        return (x != x, x == jnp.inf, x == -jnp.inf)

    def kernel(chunk_ref, first_ref, last_ref, ids_ref, g2_ref, acc_ref,
               out_ref, new_ref, old):
        i = pl.program_id(0)
        c = chunk_ref[i]
        t = i - c
        before = jnp.maximum(i - 1, 0)

        # A tile the walk did not stand on before: the output block is a
        # new buffer.
        @pl.when(jnp.logical_or(i == 0, t != before - chunk_ref[before]))
        def _():
            out_ref[...] = acc_ref[...]

        lo = t * tile

        @pl.when(jnp.logical_and(first_ref[c] - lo < tile,
                                 last_ref[c] >= lo))
        def _():
            rel = ids_ref[...] - lo                              # (1, K)
            mine = jnp.logical_and(rel >= 0, rel < tile)
            # An id of another tile names a row no iota holds.
            A = jnp.where(
                rel // _LANES == lax.broadcasted_iota(jnp.int32, (TR, K), 0),
                1.0, 0.0).astype(jnp.bfloat16)                   # (TR, K)
            lane = rel % _LANES == lax.broadcasted_iota(
                jnp.int32, (_LANES, K), 0)                       # (128, K)

            def pick(tall):  # (128, K), a row a lane -> each id's own lane
                return jnp.sum(jnp.where(lane, tall, 0.0), axis=0,
                               keepdims=True)

            def place(row):  # (1, K) f32 -> (128, K), each in its own lane
                return jnp.where(lane, row, 0.0).astype(jnp.bfloat16)

            x = out_ref[...]
            finite = jnp.abs(x) < jnp.inf  # False for NaN too
            old[...] = pick(_join_rows(tn(
                _split(jnp.where(finite, x, 0.0)), A)))

            @pl.when(jnp.max(jnp.where(finite, 0.0, 1.0)) > 0.0)
            def _():
                count = tn(jnp.concatenate(
                    [jnp.where(f, 1.0, 0.0).astype(jnp.bfloat16)
                     for f in flags(x)], axis=1), A)             # (384, K)
                nan, pos, neg = (
                    pick(count[k * _LANES:(k + 1) * _LANES]) > 0.0
                    for k in range(3))
                old[...] = jnp.where(
                    nan, jnp.nan,
                    jnp.where(pos, jnp.inf,
                              jnp.where(neg, -jnp.inf, old[...])))

            g = g2_ref[...]                                      # (1, K)
            new_ref[...] = jnp.where(mine, old[...] + g, new_ref[...])

            sound = jnp.abs(g) < jnp.inf
            parts = _split(jnp.where(sound, g, 0.0))             # (1, 384)
            add = _join(nt(A, jnp.concatenate(
                [place(parts[:, k * K:(k + 1) * K].astype(jnp.float32))
                 for k in range(3)], axis=0)))                   # (TR, 128)
            # Adding the zero of a row no id names would turn its -0.0
            # into 0.0.
            out_ref[...] = jnp.where(add != 0.0, x + add, x)

            @pl.when(jnp.max(jnp.where(sound, 0.0, 1.0)) > 0.0)
            def _():
                count = nt(A, jnp.concatenate(
                    [place(jnp.where(f, 1.0, 0.0)) for f in flags(g)],
                    axis=0))                                     # (TR, 384)
                nan, pos, neg = (
                    count[:, k * _LANES:(k + 1) * _LANES] > 0.0
                    for k in range(3))
                out_ref[...] = jnp.where(
                    nan, x + jnp.nan,
                    jnp.where(pos, x + jnp.inf,
                              jnp.where(neg, x - jnp.inf, out_ref[...])))

    def by_chunk(i, chunk_ref, first_ref, last_ref):
        return (chunk_ref[i], 0, 0)

    def by_tile(i, chunk_ref, first_ref, last_ref):
        return (i - chunk_ref[i], 0)

    new_acc, new_rows = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(acc.shape, jnp.float32),
                   jax.ShapeDtypeStruct((C, 1, K), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(walk,),
            in_specs=[
                pl.BlockSpec((None, 1, K), by_chunk),
                pl.BlockSpec((None, 1, K), by_chunk),
                pl.BlockSpec((TR, _LANES), by_tile),
            ],
            out_specs=(pl.BlockSpec((TR, _LANES), by_tile),
                       pl.BlockSpec((None, 1, K), by_chunk)),
            scratch_shapes=[pltpu.VMEM((1, K), jnp.float32)],
        ),
        input_output_aliases={5: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=(
            pltpu.InterpretParams(dma_execution_mode="eager")
            if interpret else False
        ),
        name="acc_update",
    )(chunk, first, last, ids.reshape(C, 1, K), g2.reshape(C, 1, K), acc)
    return new_acc.reshape(R), new_rows.reshape(m + pad)[:m]
