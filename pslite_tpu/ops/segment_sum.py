"""Segment sum over sorted rows (Pallas, TPU): the sum of a sparse push's
duplicates, left compacted.

``parallel/sparse.py`` ``_combine_rows`` sorts a gathered batch by row and
numbers its runs: ``seg`` starts at 0 and rises by 0 or 1 from slot to slot.
XLA's ``zeros.at[seg].add(sg)`` is told none of that and pays a serial
read-modify-write for every slot (9-13 ns a slot on a v5e, a tenth of what
its bytes need).  ``segment_sum`` walks blocks of ``_BLOCK`` slots in order.
A block's slots fall into at most ``_BLOCK`` consecutive segments, which
begin inside a window of ``2 * _BLOCK`` result rows that starts on a whole
block of the result, so their sums are one product on the MXU,
``P @ sg_block`` with ``P[k, j] = (seg[j] == window's first row + k)``: a
one-hot product that sums and compacts at once, onto the window's own rows.
The window's lower half is the kernel's resident output block, its upper
half a scratch; a run that crosses a block border just goes on adding into
its row, and a half is written to HBM once, when the walk has left it.

The arithmetic is the f32 sum in another order of addition: ``P`` is exact
in bf16, every f32 is split exactly into three bf16 parts (8 + 8 + 8
mantissa bits), every product is exact, and the MXU adds in f32.  A block
that holds a non-finite value is summed without it and given it back where
IEEE addition would leave it, from one more product that counts each row's
NaN, +inf and -inf lane by lane: ``0 * inf`` is NaN, so in the plain product
it would spill into every row of the window.

Conventions as in ``row_add.py``: the caller decides ``interpret``, the
trace is kept between processes, and the kernel carries its name into a
device trace (``%segment_sum.<n>``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compile_cache import call_traced

_LANES = 128
# Sorted slots a grid step: a (2 * 256, 256) one-hot in bf16 against
# (256, 3 * 128) parts.
_BLOCK = 256


def segment_sum(seg, sg, *, interpret: bool):
    """``out[k] = sum(sg[j] for j where seg[j] == k)``, as ``f32[m, 128]``.

    ``seg`` is ``s32[m]``, non-decreasing from 0 in steps of 0 or 1; ``sg``
    ``f32[m, 128]``.  Rows of ``out`` past the whole blocks of ``_BLOCK``
    rows that hold ``seg``'s last value are never written: they hold
    whatever the buffer held.

    Compiled for the chip, the kernel's trace is kept between processes
    (``utils/compile_cache.py`` ``call_traced``), as ``row_add``'s is.
    """
    seg = seg.astype(jnp.int32)
    if interpret:
        return _segment_sum(seg, sg, True)
    return call_traced(_segment_sum, __file__, "tpu", seg, sg)


def _split(x):
    """``x`` (f32) as three bf16 parts side by side that add up to it."""
    parts = []
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        parts.append(part)
        x = x - part.astype(jnp.float32)
    return jnp.concatenate(parts, axis=1)


def _join(wide):
    """The three 128-lane thirds of a product with :func:`_split`, added."""
    return (wide[:, :_LANES] + wide[:, _LANES:2 * _LANES]
            + wide[:, 2 * _LANES:])


def _segment_sum(seg, sg, interpret: bool = False):
    """The Pallas call and the little around it; of its two arrays alone it
    is what the chip runs (``call_traced`` traces it so)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, width = sg.shape
    assert sg.dtype == jnp.float32 and width == _LANES, (sg.dtype, sg.shape)
    B = _BLOCK
    pad = -m % B
    if pad:  # no batch of a cell; zeros added to the last run
        seg = jnp.pad(seg, (0, pad), mode="edge")
        sg = jnp.pad(sg, ((0, pad), (0, 0)))
    nb = (m + pad) // B
    # The result block under each step's window.  A block's first segment
    # lies in it, and it moves on by at most one from step to step, since
    # ``seg`` rises by at most ``B`` over a block.  One more step flushes
    # the upper half left by the last.
    win = lax.slice(seg, (0,), (m + pad,), (B,)) // B
    win = jnp.concatenate([win, jnp.minimum(win[-1:] + 1, nb - 1)])

    def kernel(win_ref, seg_ref, sg_ref, out_ref, upper, prod):
        i = pl.program_id(0)
        w = win_ref[i]
        # The window stands on a result block it did not stand on before:
        # the output block is a new buffer, and takes over the upper half.
        fresh = jnp.logical_or(i == 0, w != win_ref[jnp.maximum(i - 1, 0)])

        @pl.when(i == 0)
        def _():
            upper[...] = jnp.zeros_like(upper)

        @pl.when(i < nb)
        def _():
            x = sg_ref[...]
            finite = jnp.abs(x) < jnp.inf          # False for NaN too
            rows = lax.broadcasted_iota(jnp.int32, (2 * B, B), 0) + w * B
            P = jnp.where(seg_ref[0] == rows, 1.0, 0.0).astype(jnp.bfloat16)
            prod[...] = _join(jnp.dot(
                P, _split(jnp.where(finite, x, 0.0)),
                preferred_element_type=jnp.float32))

            @pl.when(jnp.max(jnp.where(finite, 0.0, 1.0)) > 0.0)
            def _():
                flags = jnp.concatenate(
                    [jnp.where(c, 1.0, 0.0).astype(jnp.bfloat16)
                     for c in (x != x, x == jnp.inf, x == -jnp.inf)], axis=1)
                count = jnp.dot(P, flags, preferred_element_type=jnp.float32)
                nan, pos, neg = (count[:, k * _LANES:(k + 1) * _LANES] > 0.0
                                 for k in range(3))
                prod[...] += jnp.where(
                    nan | (pos & neg), jnp.nan,
                    jnp.where(pos, jnp.inf, jnp.where(neg, -jnp.inf, 0.0)))

            @pl.when(fresh)
            def _():
                out_ref[...] = upper[...] + prod[:B]
                upper[...] = prod[B:]

            @pl.when(jnp.logical_not(fresh))
            def _():
                out_ref[...] += prod[:B]
                upper[...] += prod[B:]

        @pl.when(jnp.logical_and(i == nb, fresh))
        def _():
            out_ref[...] = upper[...]

    def slots(i):
        # The flushing step names the last block again: nothing is fetched.
        return jnp.minimum(i, nb - 1)

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m + pad, width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nb + 1,),
            in_specs=[
                pl.BlockSpec((None, 1, B),
                             lambda i, win_ref: (slots(i), 0, 0)),
                pl.BlockSpec((B, width), lambda i, win_ref: (slots(i), 0)),
            ],
            out_specs=pl.BlockSpec((B, width),
                                   lambda i, win_ref: (win_ref[i], 0)),
            scratch_shapes=[pltpu.VMEM((B, width), jnp.float32),
                            pltpu.VMEM((2 * B, width), jnp.float32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=(
            pltpu.InterpretParams(dma_execution_mode="eager")
            if interpret else False
        ),
        name="segment_sum",
    )(win, seg.reshape(nb, 1, B), sg)
    return out[:m] if pad else out
