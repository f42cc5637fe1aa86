"""Row read-add-write kernel (Pallas, TPU): the sparse table's write by
distinct row.

The rows a sparse push touches arrive combined (``parallel/sparse.py``
``_combine_rows``): ascending, each once, the valid ones first, under a
stateful handle (``_adagrad_sparse``: the rows' steps) and under the plain
sum (``_scatter_rows``: the rows' summed gradients) alike.  XLA's scatter
is told none of that and pays a serial
read-modify-write for every slot of the batch, dropped sentinel slots
included.  ``row_add`` visits only the first ``n`` slots and moves whole
512 B rows: a block of row ids reaches the scalar core, one DMA a row brings
the rows from HBM into VMEM, one vector add applies the block's deltas, one
DMA a row writes them back (~15 ns a DMA on a v5e, against the scatter's 68
ns a slot).  The table never leaves HBM and is updated in place; slots past
``n`` are neither read nor written.

Conventions as in ``fused_update.py``: float32 arithmetic, the caller
decides ``interpret`` (it knows what the program is lowered for), and the
kernel carries its name into a device trace (``%row_add.<n>``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.compile_cache import call_traced

_LANES = 128
_SUBLANES = 8
# Row ids a grid step, and (1024, 128) f32 = 512 KiB of VMEM for their rows.
# A multiple of 1024: XLA tiles a 1-D s32 operand by 1024, and the block of
# ids that reaches SMEM has to agree with it.
_BLOCK_ROWS = 1024
_GROUP = 8  # rows a trip of the kernel's loops


def row_add(store, rows, delta, n, *, interpret: bool):
    """``store[rows[i]] += delta[i]`` for ``i < n``; returns the new store,
    which aliases ``store``'s buffer where that is donated.

    ``store`` is ``f32[R, 128]``, ``rows`` ``s32[m]`` ascending and unique
    in its first ``n`` entries, ``delta`` ``f32[m, 128]``, ``n`` an integer
    scalar on the device.  Entries ``i >= n`` of ``rows`` and ``delta`` may
    hold anything.

    Compiled for the chip, the kernel's trace is kept between processes
    (``utils/compile_cache.py`` ``call_traced``): a process that finds it
    imports no Pallas and traces no kernel, which on a v5e host is 1.9 s
    of a first push.
    """
    rows = rows.astype(jnp.int32)
    n = jnp.reshape(n, (1,)).astype(jnp.int32)
    if interpret:
        return _row_add(store, rows, delta, n, True)
    return call_traced(_row_add, __file__, "tpu", store, rows, delta, n)


def _row_add(store, rows, delta, n, interpret: bool = False):
    """The Pallas call; of its four arrays alone it is what the chip runs
    (``call_traced`` traces it so)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, width = delta.shape
    assert store.dtype == delta.dtype == jnp.float32, (store.dtype,
                                                        delta.dtype)
    # Rows of exactly one (8, 128) tile's width: Mosaic refuses the slice
    # of one row out of a table that is several tiles wide.
    assert width == _LANES == store.shape[1], (store.shape, delta.shape)
    block = min(_BLOCK_ROWS, -(-m // _SUBLANES) * _SUBLANES)
    pad = -m % block
    if pad:  # no batch of a cell: its 131,072 slots are whole blocks
        rows = jnp.pad(rows, (0, pad))
        delta = jnp.pad(delta, ((0, pad), (0, 0)))
    steps = (m + pad) // block

    def kernel(n_ref, ids_ref, delta_ref, _, store_ref, buf, sem):
        # ``store_ref`` is the aliased result: the table itself, in HBM.
        count = jnp.minimum(block, n_ref[0] - pl.program_id(0) * block)

        def copy(table_row, j, rows, back):
            hbm = store_ref.at[pl.ds(table_row, rows)]
            vmem = buf.at[pl.ds(j, rows)]
            return pltpu.make_async_copy(
                *((vmem, hbm) if back else (hbm, vmem)), sem)

        def loop(lo, hi, fn):
            # Trip counts come from the data: loops, never unrolled whole.
            def body(j, carry):
                fn(j)
                return carry

            lax.fori_loop(lo, hi, body, 0)

        def move(back):
            # One DMA a row, started ``_GROUP`` to a trip (a trip a row
            # costs half as much again), then waited for ``_GROUP`` at a
            # time: the semaphore counts bytes, so a wait names any rows
            # of the size it is to take, here the first.
            groups = count // _GROUP

            def start(j):
                copy(ids_ref[j], j, 1, back).start()

            def start_group(g):
                for u in range(_GROUP):
                    start(g * _GROUP + u)

            loop(0, groups, start_group)
            loop(groups * _GROUP, count, start)
            loop(0, groups, lambda g: copy(0, 0, _GROUP, back).wait())
            loop(groups * _GROUP, count, lambda j: copy(0, 0, 1, back).wait())

        @pl.when(count > 0)
        def _():
            move(False)
            buf[...] = buf[...] + delta_ref[...]
            move(True)

    def last(n_ref):
        # Whole steps past ``n`` do nothing: they name the last block in
        # use again, which the pipeline then does not fetch a second time.
        return jnp.maximum(n_ref[0] - 1, 0) // block

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(store.shape, store.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(steps,),
            in_specs=[
                pl.BlockSpec((block,),
                             lambda i, n_ref: (jnp.minimum(i, last(n_ref)),),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((block, width),
                             lambda i, n_ref: (jnp.minimum(i, last(n_ref)),
                                               0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((block, width), jnp.float32),
                            pltpu.SemaphoreType.DMA],
        ),
        input_output_aliases={3: 0},
        interpret=(
            pltpu.InterpretParams(dma_execution_mode="eager")
            if interpret else False
        ),
        name="row_add",
    )(n, rows, delta, store)
