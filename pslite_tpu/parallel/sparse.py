"""Sparse KV tables — embedding-style push/pull over the mesh.

The reference's sparse capability is KVPairs with arbitrary subsets of a
huge key space, sliced to servers by key range and aggregated server-side
(kv_app.h:430-452); its stress benchmark drives gather/scatter traffic
(test_benchmark_stress.cc:249-431).  The TPU-native design shards the table
rows over the ``kv`` mesh axis and turns push/pull into collectives with
static shapes:

- ``push``: all_gather the (indices, grads) of every worker shard, then each
  table shard scatter-adds the rows it owns (``segment-sum`` aggregation —
  the server handler as a reduction).
- ``pull``: every shard materializes the owned rows for every worker's
  index list (zeros elsewhere); a ``psum_scatter`` over the worker dimension
  both sums the one-hot contributions and routes each worker exactly its
  own batch.

What that costs between chips (compiled for a v5e 2x2 at W = 4, n = 131,072,
d = 128; ``PERF.md`` section 5 has the chip's times): every shard is SENT
every worker's whole batch, ids and gradients (two all-gathers, ``W*n`` slots
a shard though it owns about ``n`` of them), and the pull's ``psum_scatter``
is compiled as an ALL-REDUCE of the whole ``[W, n, d]`` workspace with the
worker's slice cut afterwards, not as a reduce-scatter: about six times the
bytes an exchange routed by owner would move.  On the chip the three
collectives are 5.4 ms of that cell's 25.3 ms step, and the gathers, sorts
and sums over the ``W*n`` slots most of the rest.  The scopes
``ps.sparse.route.ids`` / ``.grads`` / ``.rows`` tell the three exchanges
apart in a trace and ``engine.sparse.route.slots`` counts the slots a shard
works on.

Row ownership is round-robin (``row % num_shards``) rather than contiguous
range: skewed key distributions (the 1M-key embedding workload,
BASELINE.md config 5) then load-balance across shards by construction.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from dataclasses import dataclass
from typing import Callable, Dict, Optional

import numpy as np

from ..utils import logging as log
from ..utils.profiling import (ENGINE_OP, SPARSE_ROUTE, stage_clock,
                               stamp)
from .placement import staging_xp


@dataclass
class SparseTable:
    name: str
    num_rows: int  # global rows
    dim: int
    rows_per_shard: int
    dtype: object
    # Lane packing factor: pack logical rows per physical store row
    # (pack*dim = 128 lanes).  TPU tiling gives a [rows, dim<128] table
    # no good layout — XLA's scatter wants it column-major, its gather
    # wants row-major, and whichever the store commits, the other op
    # transposes the WHOLE table every step (1.65 ms of the 1M-row
    # embedding step).  Packing to full 128-lane rows makes row-major
    # canonical for BOTH ops: measured 2.0 -> 0.35 ms/step.  pack == 1
    # means unpacked (dim >= 128, dim not dividing 128, or a table
    # demoted by the orbax demotion-era checkpoint compat shim).
    pack: int = 1

    @property
    def phys_rows(self) -> int:
        """Physical store rows per shard."""
        return self.rows_per_shard // self.pack



@dataclass(eq=False, slots=True)
class _BoundPush:
    """What every ``push`` of one table under one handle at one batch size
    works out the same way, worked out once by ``SparseEngine._bind_push``
    (the sparse twin of the dense engine's ``_BoundOp``).  Invariants
    only: never a store, an accumulator or a gradient."""

    prog: Callable
    kind: Optional[str]  # the handle's kind; None: the plain sum
    params: tuple  # the handle's numbers as device scalars; () for the sum
    row_kernel: bool  # the program's table write is ops/row_add.py
    segsum_kernel: bool  # it sums its segments with ops/segment_sum.py
    acc_kernel: bool  # it updates the accumulator with ops/acc_update.py
    packed: bool  # the table is lane-packed (pack > 1)
    slots: int  # batch-workspace rows a shard's program combines (W * batch)


def _interleave_rows(glob, num_rows: int, rps: int, S: int, dtype):
    """Global-order rows -> the sharded store layout: global row r
    lives on shard r % S at local row r // S.  ``glob`` is [num_rows]
    or [num_rows, dim]; returns the flat interleaved array of
    rps*S (x dim) entries.  The ONE definition of the layout —
    register_sparse init, reshard stores, and reshard accumulators all
    route through it (pull correctness depends on them agreeing)."""
    glob = np.asarray(glob, dtype=np.dtype(dtype))
    shape = (rps * S,) + glob.shape[1:]
    arr = np.zeros(shape, dtype=np.dtype(dtype))
    arr[:num_rows] = glob
    if arr.ndim == 1:
        return arr.reshape(rps, S).transpose(1, 0).reshape(-1)
    return arr.reshape(rps, S, -1).transpose(1, 0, 2).reshape(
        -1, arr.shape[1]
    )


def _deinterleave_rows(inter, num_rows: int, rps: int, S: int):
    """Inverse of :func:`_interleave_rows`: the sharded store layout
    back to global row order ([num_rows] or [num_rows, dim]).  Same
    one-definition rule — checkpoint saves and reshard snapshots route
    through it."""
    inter = np.asarray(inter)
    if inter.ndim == 1:
        return inter.reshape(S, rps).transpose(1, 0).reshape(
            -1
        )[:num_rows].copy()
    return inter.reshape(S, rps, -1).transpose(1, 0, 2).reshape(
        -1, inter.shape[1]
    )[:num_rows].copy()


def _pack_host(inter, rps: int, S: int, pack: int, dim: int):
    """Shard-interleaved LOGICAL rows [rps*S, dim] -> the PHYSICAL
    packed store [phys*S, pack*dim] (pure contiguous reshapes: each
    shard's rps logical rows become rps/pack 128-lane rows)."""
    if pack == 1:
        return inter
    inter = np.ascontiguousarray(inter)
    return inter.reshape(S, rps // pack, pack * dim).reshape(
        S * (rps // pack), pack * dim
    )


def _unpack_host(phys, rps: int, S: int, pack: int, dim: int):
    """Inverse of :func:`_pack_host`."""
    if pack == 1:
        return np.asarray(phys)
    return np.ascontiguousarray(phys).reshape(
        S, rps, dim
    ).reshape(S * rps, dim)


def _lies_as(arr, sharding, dtype, ndim: int) -> bool:
    """Whether ``arr`` is a device array of ``dtype`` laid out as
    ``sharding`` (the same one, not merely an equivalent: the program's
    cache is keyed by it): what ``jax.device_put(arr, sharding)`` returns."""
    import jax

    return (isinstance(arr, jax.Array) and arr.ndim == ndim
            and arr.dtype == dtype and arr.sharding == sharding)


def _store_out_format(store, mesh, axis):
    """Output Format pinning a program's donated store output to the
    LIVE store's committed layout (left alone, XLA commits the scatter
    output in a different layout than the pull program wants and every
    pull pays a full-table transpose).  The ONE definition the single
    and group program builders share; falls back to a plain
    NamedSharding when the layout API is unavailable."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    try:
        from jax.experimental.layout import Format

        fmt = getattr(store, "format", None)
        if fmt is not None and fmt.layout is not None:
            return Format(fmt.layout, NamedSharding(mesh, P(axis, None)))
    except Exception:  # noqa: BLE001 - layout API is optional
        pass
    return NamedSharding(mesh, P(axis, None))


def _route_ids(axis, S, idx_l):
    """What every sparse body starts with, inside its ``ps.sparse.route``:
    every worker's row ids gathered on every shard (``s32[W*n]``), and for
    each slot whether this shard owns its row (``row % S``) and the row's
    place here (``row // S``).  Scope ``ps.sparse.route.ids``."""
    import jax
    from jax import lax

    with jax.named_scope("ps.sparse.route.ids"):
        all_idx = lax.all_gather(idx_l[0], axis, tiled=True)  # [W*n]
        owned = (all_idx % S) == lax.axis_index(axis)
        return owned, all_idx // S


def _route_grads(axis, grads_l):
    """A push's gradient rows gathered on every shard beside their ids
    (``[W*n, d]``).  Scope ``ps.sparse.route.grads``."""
    import jax
    from jax import lax

    with jax.named_scope("ps.sparse.route.grads"):
        return lax.all_gather(grads_l[0], axis, tiled=True)


def _scatter_rows(axis, S, R, pack, dim, store_l, idx_l, grads_l):
    """Sum-handle push: add the owned rows DIRECTLY into the donated
    (possibly packed) store.  A dense-aggregate form reads + writes the
    whole table per push (768MB of traffic for a 4096-row update on the
    1M-row workload); this touches only the updated rows.  Shared by the
    single-table and group programs.

    Where ``ops/row_add.py`` takes the table's rows (:func:`_on_row_add`:
    a physical row of 128 f32 lanes, lane-packed or not, a program lowered
    for a TPU) the duplicates are combined first (:func:`_combine_rows`; on
    a lane-packed table :func:`_combine_phys_rows`, which also merges the
    row-mates of a physical row) and the kernel writes each distinct
    physical row once; anywhere else it is XLA's scatter-add, which pays
    for every slot of the batch but needs no combine.  Unowned rows map out
    of bounds and mode="drop" discards them.

    The sparse bodies carry ``jax.named_scope``s, by which a device trace
    is read: ``ps.sparse.route`` (indices and rows crossing the workers,
    and who owns what; inside it ``ps.sparse.route.ids``, the index
    all-gather and the ownership, ``ps.sparse.route.grads``, a push's
    gradient all-gather, and ``ps.sparse.route.rows``, the pull's
    ``psum_scatter``), ``ps.sparse.push.scatter_add``,
    ``ps.sparse.pull.gather``, ``ps.sparse.combine`` (sort and segment sum
    of duplicates: under a stateful handle, and before ``row_add`` in the
    sum), ``ps.sparse.pack.place`` (a lane-packed table's rows placed in
    their slot's lanes, and merged by physical row where ``row_add``
    follows) and under a stateful handle ``ps.update`` (accumulator and
    step)."""
    import jax
    import jax.numpy as jnp

    from ..ops.row_add import row_add

    with jax.named_scope("ps.sparse.route"):
        owned, local = _route_ids(axis, S, idx_l)
        all_g = _route_grads(axis, grads_l)  # [W*n, d]

    def scatter(store_l, owned, local, all_g):
        with jax.named_scope("ps.sparse.push.scatter_add"):
            masked = jnp.where(owned[:, None], all_g, 0)
            # R: out of bounds (as R // pack is in the packed table), drop
            masked, rows = _place_rows(masked, jnp.where(owned, local, R),
                                       pack)
            return store_l.at[rows].add(masked, mode="drop")

    def by_distinct_row(store_l, owned, local, all_g, interpret):
        with jax.named_scope("ps.sparse.combine"):
            G_seg, row_seg, valid = _combine_phys_rows(
                jnp.where(owned, local, R), all_g, R, pack)
        with jax.named_scope("ps.sparse.push.scatter_add"):
            return row_add(store_l, row_seg, G_seg, jnp.sum(valid),
                           interpret=interpret)

    return _on_row_add(scatter, by_distinct_row, store_l, owned, local,
                       all_g)


# Where a sparse push's table write is ``ops/row_add.py``: the platform a
# program is lowered for -> the kernel's ``interpret`` there.  A platform
# not named keeps XLA's scatter.
_ROW_ADD_INTERPRET = {"tpu": False}


# Where the combine's segment sum is ``ops/segment_sum.py``: a table of its
# own, so that a test can put either kernel alone into a CPU program
# (``row_add`` gives XLA's scatter's bits only while the sums before it are
# added in XLA's order).
_SEGMENT_SUM_INTERPRET = {"tpu": False}


# Where a stateful push's accumulator is updated by ``ops/acc_update.py``.
_ACC_UPDATE_INTERPRET = {"tpu": False}


def _row_add_takes(width: int, dtype) -> bool:
    """The rows ``ops/row_add.py`` moves: PHYSICAL rows of 128 f32 lanes,
    ``width = pack * dim``: one unpacked row, or the ``pack`` logical rows
    a lane-packed table keeps in one (a wider row spans tiles, and Mosaic
    refuses the slice of one).  ``ops/segment_sum.py`` sums the same rows
    and no others (:func:`_segment_sums`)."""
    return width == 128 and np.dtype(dtype) == np.float32


# What ``_acc_update_takes`` weighs, in ns on a v5e, both read with every slot
# a distinct row spread over the table, the pass's dearest batch (every step
# of the walk then holds ids) and XLA's cheapest (my chip runs, PR 34,
# ``PERF.md`` section 6: 20,000,000 accumulators under 8,192 to 131,072
# slots, 1,048,576 under 1,024 to 8,192): a grid step of
# ``ops/acc_update.py``'s pass (1.08-1.13 us at six batch sizes of the large
# table; 0.65 where it holds no id), and a slot of XLA's 1-D gather, add and
# scatter (19.6 ns from 32,768 slots to 131,072; 20.5 under Zipf duplicates).
# What either costs whatever the batch is left out (XLA's pair ~0.15 ms at
# 20,000,000 rows, a kernel's start ~0.04): the kernel is taken from ~43,000
# slots there and is the faster from ~34,000 (between 16,384 and 32,768
# under Zipf duplicates), XLA's kept at a loss of at most a tenth in between;
# at 1,048,576 rows taken from ~2,200 slots and the faster from ~1,700.
_ACC_STEP_NS = 1100
_ACC_SLOT_NS = 20


def _acc_update_takes(R: int, m: int) -> bool:
    """Whether ``ops/acc_update.py`` updates an accumulator of ``R`` rows
    under a batch of ``m`` slots: the accumulator is whole 128-lane rows,
    and the pass, which costs by ``R`` (and by ``m`` only in chunks), is
    reckoned cheaper than XLA's pair, which costs by the slot.  A small
    batch into a large table keeps XLA's."""
    from ..ops.acc_update import steps

    return (R % 128 == 0
            and steps(R, m) * _ACC_STEP_NS < m * _ACC_SLOT_NS)


def _where_lowered(interprets, xla, kernel, *operands):
    """``kernel(*operands, interpret)`` where the program is lowered for a
    platform of ``interprets`` (platform -> the kernel's ``interpret``
    there), ``xla(*operands)`` anywhere else: chosen at lowering, so one
    traced program serves whatever it is compiled for, and no caller says
    which."""
    from jax import lax

    kernels = {platform: functools.partial(kernel, interpret=interpret)
               for platform, interpret in interprets.items()}
    return lax.platform_dependent(*operands, default=xla, **kernels)


def _on_row_add(scatter, kernel, store_l, *operands):
    """``kernel(store_l, *operands, interpret)`` where the program is
    lowered for a platform of ``_ROW_ADD_INTERPRET`` and ``row_add`` takes
    the table's rows (:func:`_row_add_takes`), ``scatter(store_l,
    *operands)`` anywhere else (:func:`_where_lowered`).  The one rule of
    both pushes' table write (and of ``SparseEngine._row_kernel``, which
    counts it)."""
    if not _row_add_takes(store_l.shape[1], store_l.dtype):
        return scatter(store_l, *operands)
    return _where_lowered(_ROW_ADD_INTERPRET, scatter, kernel, store_l,
                          *operands)


def _segment_sums(seg, sg):
    """``G[k] = sum(sg[j] for j where seg[j] == k)`` as ``[m, d]``, for the
    sorted batch of :func:`_combine_rows`: ``ops/segment_sum.py`` where the
    program is lowered for a platform of ``_SEGMENT_SUM_INTERPRET`` and the
    rows are 128 f32 lanes (both pushes of an unpacked 128-wide table, and
    whatever is combined by physical row on a lane-packed one), XLA's
    scatter-add into a workspace anywhere else, which pays for every slot
    (:func:`_where_lowered`; ``SparseEngine._segsum_kernel`` counts it).
    The kernel leaves rows past the last segment's block unwritten: no
    caller reads a row that is not ``valid``."""
    import jax.numpy as jnp

    from ..ops.segment_sum import segment_sum

    def scatter(seg, sg):
        return jnp.zeros(sg.shape, sg.dtype).at[seg].add(sg)

    if not _row_add_takes(sg.shape[1], sg.dtype):
        return scatter(seg, sg)
    return _where_lowered(_SEGMENT_SUM_INTERPRET, scatter, segment_sum, seg,
                          sg)


def _update_acc(acc_l, row_seg, valid, g2):
    """``acc_l[row_seg[i]] += g2[i]`` where ``valid[i]``, for the combined
    logical rows of :func:`_combine_rows`; returns the new accumulator and
    ``f32[m]``, the new accumulators of the valid rows in ``row_seg``'s
    order (what it holds past them is not for use).  ``ops/acc_update.py``
    where the program is lowered for a platform of ``_ACC_UPDATE_INTERPRET``
    and the pass pays (:func:`_acc_update_takes`), XLA's 1-D gather and
    scatter anywhere else (:func:`_where_lowered`;
    ``SparseEngine._acc_kernel`` counts it).  The accumulator is by logical
    row whatever the table's width, dtype or packing."""
    import jax.numpy as jnp

    from ..ops.acc_update import acc_update

    R = acc_l.shape[0]

    def xla(acc_l, row_seg, valid, g2):
        new_rows = acc_l[jnp.where(valid, row_seg, 0)] + g2
        return acc_l.at[jnp.where(valid, row_seg, R)].set(
            new_rows, mode="drop"), new_rows

    def kernel(acc_l, row_seg, valid, g2, interpret):
        return acc_update(acc_l, row_seg, g2, jnp.sum(valid),
                          interpret=interpret)

    if not _acc_update_takes(R, row_seg.shape[0]):
        return xla(acc_l, row_seg, valid, g2)
    return _where_lowered(_ACC_UPDATE_INTERPRET, xla, kernel, acc_l, row_seg,
                          valid, g2)


def _add_rows(store_l, row_seg, valid, delta, R, pack):
    """``store_l[row_seg[i]] += delta[i]`` where ``valid[i]``, for combined
    LOGICAL rows (:func:`_combine_rows`): ascending, each once, the valid
    ones first, ``delta`` zero past them.  Where the kernel serves
    (:func:`_on_row_add`) the write visits the distinct physical rows only
    (``ops/row_add.py``; on a lane-packed table the rows are placed and
    their row-mates merged first, :func:`_combine_phys_rows`); anywhere
    else it is XLA's scatter, which pays for every slot."""
    import jax.numpy as jnp

    from ..ops.row_add import row_add

    def scatter(store_l, row_seg, valid, delta):
        delta, rows = _place_rows(delta, jnp.where(valid, row_seg, R), pack)
        return store_l.at[rows].add(delta, mode="drop")

    def kernel(store_l, row_seg, valid, delta, interpret):
        if pack != 1:
            delta, row_seg, valid = _combine_phys_rows(
                jnp.where(valid, row_seg, R), delta, R, pack)
        return row_add(store_l, row_seg, delta, jnp.sum(valid),
                       interpret=interpret)

    return _on_row_add(scatter, kernel, store_l, row_seg, valid, delta)


def _place_rows(g, rows, pack):
    """A lane-packed table's updates in the table's own layout: ``g[i]``
    (``[m, dim]``, for logical row ``rows[i]``) in the ``dim`` lanes of its
    slot ``rows[i] % pack`` of a ``pack * dim``-lane row, zero in its
    row-mates' lanes.  Returns them with the physical rows ``rows // pack``
    (a sentinel ``R``, a multiple of ``pack``, becomes ``R // pack``: the
    physical table's); an unpacked table's (``pack == 1``) as they are.  A
    select, not a product with a one-hot: a row-mate is added zero whatever
    ``g`` holds (``inf * 0`` is NaN)."""
    from jax import lax
    import jax.numpy as jnp

    if pack == 1:
        return g, rows
    dim = g.shape[1]
    lane_slot = lax.iota(jnp.int32, pack * dim) // dim
    mine = (rows % pack).astype(jnp.int32)[:, None] == lane_slot[None, :]
    return jnp.where(mine, jnp.tile(g, (1, pack)), 0), rows // pack


def _combine_phys_rows(local, g, R, pack):
    """:func:`_combine_rows` by PHYSICAL row, for the write by distinct row
    (``ops/row_add.py`` moves whole physical rows, and two logical rows of
    one physical row written by two DMAs would race): ``local`` are
    logical rows or the sentinel ``R``, ``g`` their ``[m, dim]`` updates.
    Each is placed in its slot's lanes (:func:`_place_rows`) and the
    combine runs over ``local // pack``, so duplicates and row-mates end
    in one entry a distinct physical row; every lane's sum is its own
    slot's gradients in the batch's order and zeros.  Unpacked
    (``pack == 1``) it is :func:`_combine_rows` itself."""
    import jax

    if pack == 1:
        return _combine_rows(local, g, R)
    with jax.named_scope("ps.sparse.pack.place"):
        placed, phys = _place_rows(g, local, pack)
        return _combine_rows(phys, placed, R // pack)


def _combine_rows(local, all_g, R):
    """Combine the duplicates of a gathered batch: ``local`` is ``s32[m]``,
    a slot's row on this shard or the sentinel ``R`` where another shard
    owns it, ``all_g`` the slots' gradient rows ``[m, d]``.  Returns
    ``G_seg [m, d]``, ``row_seg s32[m]``, ``valid pred[m]``: the distinct
    owned rows ascending, each once with the sum of its slots' gradients,
    the valid ones first.  Past them ``row_seg`` is ``R`` and ``G_seg`` is
    not for use (its first such row is the unowned slots' sum: masking
    them out was a pass over the batch, 0.2 ms of a v5e's step, and every
    caller drops what is not ``valid``).

    One sort carries what the combine needs of the batch: its keys come
    back sorted beside the permutation (``jnp.argsort`` is this sort with
    the keys thrown away, and ``local[order]`` a 1-D gather to get them
    back: 9.7 ns a slot on a v5e), and a slot is owned exactly where its
    sorted key is under ``R``, so ownership is not gathered either.
    Stable, so equal rows keep the batch's order and every f32 sum is that
    order's.  Shared by both pushes: the stateful handle's
    (:func:`_adagrad_sparse`) and, where ``row_add`` writes the table, the
    plain sum's (:func:`_scatter_rows`)."""
    from jax import lax
    import jax.numpy as jnp

    m = local.shape[0]
    sr, order = lax.sort((local.astype(jnp.int32), lax.iota(jnp.int32, m)),
                         num_keys=1, is_stable=True)
    sg = all_g[order]
    # One segment a distinct row; the sentinels sort last, into one.
    first = jnp.concatenate([jnp.ones((1,), bool), sr[1:] != sr[:-1]])
    seg = jnp.cumsum(first) - 1                                # [m]
    G_seg = _segment_sums(seg, sg)
    # Row of each segment: every segment's first id, the others at the
    # sentinel, sorted once more, which is "distinct rows ascending,
    # sentinels last" (0.09 ms on a v5e at m = 131,072, where
    # ``full(R).at[seg].set(sr)`` took 0.61).
    row_seg = lax.sort(jnp.where(first, sr, R), is_stable=False)
    return G_seg, row_seg, row_seg < R


def _adagrad_rows(store_l, acc_l, G, lr, eps):
    """Row-wise Adagrad on a DENSE aggregated gradient [R, d] (the
    DLRM-standard embedding update): acc += mean(G^2, rows); row -=
    lr*G/(sqrt+eps).  Untouched rows see G == 0 and are unchanged.
    Kept as the REFERENCE recurrence the sparse form below must match
    (tests assert parity); production paths use _adagrad_sparse."""
    import jax.numpy as jnp

    acc_new = acc_l + jnp.mean(G.astype(jnp.float32) ** 2, axis=1)
    step = (lr * G.astype(jnp.float32)
            / (jnp.sqrt(acc_new)[:, None] + eps))
    return store_l - step.astype(store_l.dtype), acc_new


def _adagrad_sparse(axis, S, R, pack, dim, store_l, acc_l, idx_l,
                    grads_l, lr, eps):
    """Row-wise Adagrad WITHOUT the dense [R, d] aggregate: the dense
    form reads+writes the whole table per push (a full-table pass even
    for a 4096-row batch) and cannot serve the lane-packed layout.
    Here duplicates are combined by a SEGMENT SUM over the sorted
    gathered indices (O(batch) workspaces, exact same per-row G as the
    dense form), the accumulator rows are read, stepped and written back
    1-D by logical row whatever the store's lane packing (_update_acc),
    and the store step is added by distinct row (_add_rows; a lane-packed
    table's placed in its slot's lanes and merged by physical row) — identical
    numerics to _adagrad_rows on the touched rows, untouched rows never
    read, and written only zeros where they share a touched physical
    row."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("ps.sparse.route"):
        owned, local = _route_ids(axis, S, idx_l)              # [m]
        all_g = _route_grads(axis, grads_l)                    # [m, d]
        local = jnp.where(owned, local, R)  # R = sentinel (dropped)

    with jax.named_scope("ps.sparse.combine"):
        G_seg, row_seg, valid = _combine_rows(local, all_g, R)

    with jax.named_scope("ps.update"):
        # Accumulator: the touched rows read, stepped and written back
        # (1-D logical rows — independent of the store's lane packing).
        g2 = jnp.mean(G_seg.astype(jnp.float32) ** 2, axis=1)
        new_acc, acc_new_rows = _update_acc(acc_l, row_seg, valid, g2)
        step = (lr * G_seg.astype(jnp.float32)
                / (jnp.sqrt(acc_new_rows)[:, None] + eps))
        step = jnp.where(valid[:, None], step, 0).astype(store_l.dtype)

    with jax.named_scope("ps.sparse.push.scatter_add"):
        # Store: subtract the step (a lane-packed table's by physical row).
        new_store = _add_rows(store_l, row_seg, valid, -step, R, pack)
    return new_store, new_acc


def _pull_rows(axis, S, store_l, idx_l, pack: int = 1, dim: int = None):
    """Per-shard pull body: materialize owned rows for every worker's
    index list, route each worker its batch via psum_scatter over the
    worker dimension.  Shared single/group; packed stores gather the
    128-lane physical row and select the logical slot (see
    SparseTable.pack)."""
    import jax
    from jax import lax
    import jax.numpy as jnp

    with jax.named_scope("ps.sparse.route"):
        owned, local = _route_ids(axis, S, idx_l)  # [W*n]
    with jax.named_scope("ps.sparse.pull.gather"):
        if pack == 1:
            rows = store_l[jnp.where(owned, local, 0)]  # [W*n, d]
            d = store_l.shape[1]
        else:
            d = dim
            m = local.shape[0]
            phys = store_l[jnp.where(owned, local // pack, 0)]  # [W*n, 128]
            slot = (local % pack).astype(jnp.int32)
            rows = jnp.take_along_axis(
                phys.reshape(m, pack, d), slot[:, None, None], axis=1
            )[:, 0]
        vals = jnp.where(owned[:, None], rows, 0)
        vals = vals.reshape(S, -1, d)  # [W, n, d]
    with jax.named_scope("ps.sparse.route"), \
            jax.named_scope("ps.sparse.route.rows"):
        return lax.psum_scatter(vals, axis, scatter_dimension=0,
                                tiled=True)[0]  # [n, d] for my indices


class SparseEngine:
    """Sparse tables on the same mesh/axis as a CollectiveEngine."""

    def __init__(self, mesh, axis_name: str = "kv"):
        from .placement import local_shard_count, mesh_is_multiprocess

        self.mesh = mesh
        self.axis = axis_name
        self.num_shards = mesh.shape[axis_name]
        self._multiprocess = mesh_is_multiprocess(mesh)
        self._local_shard_count = (
            local_shard_count(mesh) if self._multiprocess
            else self.num_shards
        )
        # Observability mirroring CollectiveEngine: byte counters, and
        # host time per stage of an op on the process's StageClock.
        self._clock = stage_clock()
        # An op notes (ENGINE_OP, t_end, select ns, prep ns, launch ns): one
        # C call (see StageClock).  The sparse stages run prep, select,
        # launch: the program is chosen under the table's lock.
        self._note = self._clock.note
        self.push_bytes = 0
        self.pull_bytes = 0
        self._counter_mu = threading.Lock()
        self._tables: Dict[str, SparseTable] = {}
        self._stores: Dict[str, object] = {}
        # Row-wise Adagrad accumulators ([rows], same modulo row-sharding
        # as the table), created lazily by push(handle="row_adagrad:...").
        self._acc: Dict[str, object] = {}
        self._programs: Dict[tuple, Callable] = {}
        # (table, handle, batch) -> see _bind_push.  Dropped with the
        # programs by a reshard, and a table's by a new registration of
        # its name or a change of its packing.
        self._bound: Dict[tuple, _BoundPush] = {}
        # Pushes that ran under a stateful handle, and pushes, under a
        # handle or not, whose program writes the table through
        # ops/row_add.py, sums its duplicates with ops/segment_sum.py, or
        # updates the accumulator with ops/acc_update.py (see export).
        self.stateful_pushes = 0
        self.row_kernel_pushes = 0
        self.segsum_kernel_pushes = 0
        self.acc_kernel_pushes = 0
        self.packed_pushes = 0  # pushes into a lane-packed table
        self._mu = threading.Lock()
        # Per-table write locks: push donates the store buffer, so the
        # load-run-store sequence must be atomic per table (same contract
        # as CollectiveEngine._bucket_mu).
        self._table_mu: Dict[str, threading.Lock] = {}

    def register_sparse(self, name: str, num_rows: int, dim: int, dtype=None,
                        init=None) -> SparseTable:
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        if dtype is None:
            dtype = jnp.float32
        pack = 128 // dim if (dim < 128 and 128 % dim == 0) else 1
        rows_per_shard = -(-num_rows // self.num_shards)
        # Round to the packing factor so each shard's logical rows fill
        # whole 128-lane physical rows (see SparseTable.pack).
        rows_per_shard = -(-rows_per_shard // pack) * pack
        table = SparseTable(name, num_rows, dim, rows_per_shard, dtype,
                            pack=pack)
        sharding = NamedSharding(self.mesh, P(self.axis, None))
        S = self.num_shards
        if init is not None:
            store = self._place(
                _pack_host(
                    _interleave_rows(init, num_rows, rows_per_shard,
                                     S, dtype),
                    rows_per_shard, S, pack, dim,
                ),
                sharding,
            )
        elif self._is_multiprocess():
            store = self._place(
                np.zeros((table.phys_rows * S, pack * dim),
                         np.dtype(dtype)),
                sharding,
            )
        else:
            # Each device zero-fills its own rows; the whole table never
            # exists on one device.
            store = jnp.zeros((table.phys_rows * S, pack * dim),
                              dtype=dtype, device=sharding)
        with self._mu:
            self._tables[name] = table
            self._stores[name] = store
            self._table_mu.setdefault(name, threading.Lock())
            self._unbind(name)
        return table

    def _unbind(self, name: str) -> None:
        """Drop the table's push records (call with ``_mu`` held)."""
        for key in [k for k in self._bound if k[0] == name]:
            del self._bound[key]

    def export(self, registry) -> None:
        """Lazily sampled gauges in a node's ``Registry``, beside the
        stage clock's (``docs/observability.md``, "Engine path")."""
        registry.gauge("engine.sparse.push.stateful",
                       fn=lambda: self.stateful_pushes)
        registry.gauge("engine.sparse.push.row_kernel",
                       fn=lambda: self.row_kernel_pushes)
        registry.gauge("engine.sparse.push.segsum_kernel",
                       fn=lambda: self.segsum_kernel_pushes)
        registry.gauge("engine.sparse.push.acc_kernel",
                       fn=lambda: self.acc_kernel_pushes)
        registry.gauge("engine.sparse.push.packed",
                       fn=lambda: self.packed_pushes)
        registry.gauge(
            "engine.sparse.acc.bytes",
            fn=lambda: sum(int(a.nbytes) for a in list(self._acc.values())))
        # The process's, as the stage clock is (several engines of one
        # process note into the one clock).
        registry.gauge("engine.sparse.route.slots",
                       fn=lambda: self._clock.routed_totals()[0])

    def _keep(self, key, prog):
        """Cache a program a lookup missed; the misses are counted here,
        off the hot path (the hits are the ops less the misses)."""
        with self._mu:
            self._programs[key] = prog
        self._clock.program_built()
        return prog

    def _sparse_program(self, op: str, table: SparseTable, batch: int):
        key = (op, table.name, batch, table.pack)
        with self._mu:
            prog = self._programs.get(key)
        if prog is not None:
            return prog

        import jax
        from jax import lax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis = self.axis
        S = self.num_shards
        R = table.rows_per_shard
        pack = table.pack
        dim = table.dim

        # Pin the store's OUTPUT layout to its live committed layout
        # (see _store_out_format).  Inputs stay AUTO (jit refuses
        # mismatched explicit input layouts instead of relayouting);
        # pinning only the output makes the layout a fixed point from
        # the first push onward, and the pull program then compiles
        # against that stable layout with no transpose.
        store_fmt = _store_out_format(
            self._stores[table.name], self.mesh, axis
        )

        def _sh(spec):
            return NamedSharding(self.mesh, spec)

        def _push(store_l, idx_l, grads_l):
            # Add directly into the donated (packed) store: see
            # _scatter_rows for the traffic / layout rationale.
            new = _scatter_rows(axis, S, R, pack, dim, store_l, idx_l,
                                grads_l)
            # Tiny non-donated completion token: callers block on this
            # instead of the store (which the next push donates).
            return new, new[:1, :1]

        def _push_row_adagrad(store_l, acc_l, idx_l, grads_l, lr, eps):
            # Sync-PS optimizer semantics (kv_app.h:430-452 as one fused
            # program); lr/eps arrive as traced scalars, so per-step
            # schedules reuse ONE compiled program.  Segment-sum form:
            # O(batch) work and packed-layout compatible (no dense
            # [R, d] aggregate, no full-table pass, no demotion).
            new, acc_new = _adagrad_sparse(
                axis, S, R, pack, dim, store_l, acc_l, idx_l, grads_l,
                lr, eps,
            )
            return new, acc_new, new[:1, :1]

        def _pull(store_l, idx_l):
            return _pull_rows(axis, S, store_l, idx_l, pack=pack,
                              dim=dim)

        if op == "push":
            fn = jax.shard_map(
                _push,
                mesh=self.mesh,
                in_specs=(P(axis, None), P(axis, None), P(axis, None, None)),
                out_specs=(P(axis, None), P(axis, None)),
                check_vma=False,
            )
            jitted = jax.jit(
                fn, donate_argnums=(0,),
                out_shardings=(store_fmt, _sh(P(axis, None))),
            )
        elif op == "push_row_adagrad":
            # lr/eps are traced scalar args (replicated): one compiled
            # program serves every learning-rate schedule step.
            fn = jax.shard_map(
                _push_row_adagrad,
                mesh=self.mesh,
                in_specs=(P(axis, None), P(axis), P(axis, None),
                          P(axis, None, None), P(), P()),
                out_specs=(P(axis, None), P(axis), P(axis, None)),
                check_vma=False,
            )
            jitted = jax.jit(
                fn, donate_argnums=(0, 1),
                out_shardings=(store_fmt, _sh(P(axis)),
                               _sh(P(axis, None))),
            )
        elif op == "pull":
            fn = jax.shard_map(
                _pull,
                mesh=self.mesh,
                in_specs=(P(axis, None), P(axis, None)),
                out_specs=P(axis, None),
                check_vma=False,
            )
            jitted = jax.jit(fn)
        else:
            raise ValueError(op)
        return self._keep(key, jitted)

    def _is_multiprocess(self) -> bool:
        return self._multiprocess

    def _local_shards(self) -> int:
        return self._local_shard_count

    def _place(self, host_arr, sharding):
        from .placement import place_host_array

        return place_host_array(
            self.mesh, host_arr, sharding, self._multiprocess
        )

    def _prep(self, table: SparseTable, indices, grads=None):
        """[W, n] indices (+ [W, n, d] grads) sharded over the worker axis.

        On a multi-process mesh the host inputs carry only THIS process's
        worker rows ([local, n] / [local, n, d])."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        idx_sharding = NamedSharding(self.mesh, P(self.axis, None))
        g_sharding = NamedSharding(self.mesh, P(self.axis, None, None))
        if self._is_multiprocess():
            idx = np.ascontiguousarray(np.asarray(indices, dtype=np.int32))
            local = self._local_shards()
            log.check_eq(int(idx.shape[0]), local,
                         "bad local worker dim (rows = this process's "
                         "devices on a multi-process mesh)")
            idx_sh = jax.make_array_from_process_local_data(
                idx_sharding, idx, (self.num_shards,) + idx.shape[1:]
            )
            if grads is None:
                return idx_sh, None
            g = np.ascontiguousarray(
                np.asarray(grads, dtype=np.dtype(table.dtype))
            )
            g_sh = jax.make_array_from_process_local_data(
                g_sharding, g, (self.num_shards,) + g.shape[1:]
            )
            return idx_sh, g_sh
        # Host inputs are cast on the host and placed row by row, each
        # worker's batch straight onto its device (see staging_xp).  A
        # device array that already lies as the program takes it (a
        # trainer's own batch) is passed on as it is: the cast and the
        # placement would both hand it back, ~0.13 ms of the host later.
        placed = _lies_as(indices, idx_sharding, jnp.int32, 2)
        idx = (indices if placed
               else staging_xp(indices).asarray(indices, dtype=jnp.int32))
        log.check_eq(int(idx.shape[0]), self.num_shards, "bad worker dim")
        idx_sh = idx if placed else jax.device_put(idx, idx_sharding)
        if grads is None:
            return idx_sh, None
        if _lies_as(grads, g_sharding, table.dtype, 3):
            return idx_sh, grads
        g = staging_xp(grads).asarray(grads, dtype=table.dtype)
        g_sh = jax.device_put(g, g_sharding)
        return idx_sh, g_sh

    def _observe(self, op: str, table: SparseTable, batch: int) -> None:
        payload = (
            self.num_shards * batch * table.dim
            * np.dtype(table.dtype).itemsize
        )
        with self._counter_mu:
            if op == "push":
                self.push_bytes += payload
            else:
                self.pull_bytes += payload

    def _ensure_acc(self, name: str, table: SparseTable) -> None:
        if name in self._acc:
            return
        from jax.sharding import NamedSharding, PartitionSpec as P

        t0 = stamp()
        self._acc[name] = self._place(
            np.zeros(table.rows_per_shard * self.num_shards, np.float32),
            NamedSharding(self.mesh, P(self.axis)),
        )
        self._clock.state_created(stamp() - t0)

    def ensure_acc(self, name: str) -> None:
        """Create the (zero) Adagrad accumulator for a registered table —
        needed before an orbax restore in a fresh process, where the
        restore target must exist without running a push first."""
        with self._table_mu[name]:
            self._ensure_acc(name, self._tables[name])

    def acc_array(self, name: str):
        """Adagrad accumulator snapshot (checkpointing); row-interleaved
        like the table store."""
        import jax.numpy as jnp

        with self._table_mu[name]:
            log.check(name in self._acc, f"no accumulator for {name!r}")
            return jnp.copy(self._acc[name])

    def set_acc_array(self, name: str, value,
                      global_rows: bool = False) -> None:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        table = self._tables[name]
        expected = (table.rows_per_shard * self.num_shards,)
        sharding = NamedSharding(self.mesh, P(self.axis))
        if global_rows and isinstance(value, jax.Array):
            # Device-side global restore (see set_store_array).
            import jax.numpy as jnp

            S, rps = self.num_shards, table.rows_per_shard
            log.check_eq(tuple(value.shape), (table.num_rows,),
                         "bad global-rows accumulator shape")
            v = jnp.pad(value.astype(np.float32),
                        (0, rps * S - table.num_rows))
            inter = v.reshape(rps, S).transpose(1, 0).reshape(-1)
            placed = jax.device_put(inter, sharding)
            with self._table_mu[name]:
                self._acc[name] = placed
            return
        if global_rows and not isinstance(value, jax.Array):
            host = np.asarray(value, np.float32)
            log.check_eq(tuple(host.shape), (table.num_rows,),
                         "bad global-rows accumulator shape")
            value = _interleave_rows(
                host, table.num_rows, table.rows_per_shard,
                self.num_shards, np.float32,
            )
        if isinstance(value, jax.Array):
            # Sharded restores (multi-host): assign directly, same
            # contract as set_store_array.
            equivalent = value.sharding == sharding or (
                hasattr(value.sharding, "is_equivalent_to")
                and value.sharding.is_equivalent_to(sharding, value.ndim)
            )
            if equivalent:
                log.check_eq(tuple(value.shape), expected,
                             "bad accumulator shape")
                with self._table_mu[name]:
                    self._acc[name] = value
                return
        host = np.asarray(value, np.float32)
        log.check_eq(host.shape, expected, "bad accumulator shape")
        placed = self._place(host, sharding)
        with self._table_mu[name]:
            self._acc[name] = placed

    def _ensure_unpacked(self, name: str) -> None:
        """Demote a lane-packed table to the unpacked layout (one-time
        host round trip).  COMPAT SHIM only: adagrad once required the
        unpacked layout (the dense-aggregate era) and orbax checkpoints
        saved then hold unpacked stores; restore_engine_orbax demotes a
        packed table to match.  Collective on multi-process meshes.
        Call with the table lock HELD."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .placement import to_host_global

        t = self._tables[name]
        if t.pack == 1:
            return
        host = _unpack_host(
            to_host_global(self._stores[name], self._multiprocess),
            t.rows_per_shard, self.num_shards, t.pack, t.dim,
        )
        # Place FIRST: a placement failure must not leave t.pack
        # describing a layout the live store doesn't have.
        placed = self._place(
            np.ascontiguousarray(host),
            NamedSharding(self.mesh, P(self.axis, None)),
        )
        self._stores[name] = placed
        t.pack = 1
        with self._mu:
            self._unbind(name)

    @staticmethod
    def _parse_handle(handle: str) -> tuple:
        kind, _, rest = handle.partition(":")
        log.check(kind == "row_adagrad", f"unknown sparse handle {handle!r}")
        lr, eps = 0.01, 1e-8
        if rest:
            parts = rest.split(",")
            lr = float(parts[0])
            if len(parts) > 1:
                eps = float(parts[1])
        return kind, (lr, eps)

    def _handle_scalars(self, handle: str) -> tuple:
        """(kind, the handle's numbers as f32 device scalars)."""
        import jax.numpy as jnp

        kind, params = self._parse_handle(handle)
        return kind, tuple(jnp.float32(p) for p in params)

    def _bind_push(self, name: str, handle: Optional[str], batch: int
                   ) -> _BoundPush:
        """Stage ``select`` of the first ``push(name, ., ., handle)`` at
        this batch size (and of the first after a reshard, a new
        registration of ``name`` or a change of its packing dropped the
        record): the handle parsed, its numbers placed as device scalars,
        the program.  An unknown handle fails here, by name.  Call with
        the table's lock held."""
        table = self._tables[name]
        kind, params = (None, ()) if handle is None \
            else self._handle_scalars(handle)
        bound = _BoundPush(
            self._sparse_program("push" if kind is None else "push_" + kind,
                                 table, batch),
            kind, params, self._row_kernel(table),
            self._segsum_kernel(table, kind is not None),
            kind is not None and self._acc_kernel(table, batch),
            table.pack != 1, self._route_slots(batch))
        with self._mu:
            # A new registration meanwhile: the next push binds.
            if self._tables.get(name) is table:
                self._bound[(name, handle, batch)] = bound
        return bound

    def _route_slots(self, batch: int) -> int:
        """The rows of the batch workspace one shard's program works on in
        an op of ``batch`` lookups a worker (the push combines them, the
        pull gathers them): every shard is sent every worker's batch, so W
        x ``batch``, whatever share of them it owns.  From shapes alone;
        an op notes it once (``SPARSE_ROUTE``, gauge
        ``engine.sparse.route.slots``)."""
        return self.num_shards * batch

    def _row_kernel(self, table: SparseTable) -> bool:
        """Whether this mesh's push programs of ``table``, the sum's and a
        stateful handle's, write it through ``ops/row_add.py`` (the rule of
        :func:`_on_row_add`)."""
        return (self._platform() in _ROW_ADD_INTERPRET
                and _row_add_takes(table.pack * table.dim, table.dtype))

    def _platform(self) -> str:
        """What this mesh's programs are lowered for."""
        return next(iter(self.mesh.devices.flat)).platform

    def _segsum_kernel(self, table: SparseTable, stateful: bool) -> bool:
        """Whether this mesh's push program of ``table`` sums a combine's
        segments with ``ops/segment_sum.py`` (the rule of
        :func:`_segment_sums`).  Where ``row_add`` follows, either push
        combines by physical row, which is the width both kernels take; a
        stateful handle also combines by logical row whatever follows."""
        return (self._platform() in _SEGMENT_SUM_INTERPRET
                and (self._row_kernel(table)
                     or (stateful
                         and _row_add_takes(table.dim, table.dtype))))

    def _acc_kernel(self, table: SparseTable, batch: int) -> bool:
        """Whether this mesh's stateful push program of ``table`` at
        ``batch`` lookups a worker updates the accumulator with
        ``ops/acc_update.py`` (the rule of :func:`_update_acc`)."""
        return (self._platform() in _ACC_UPDATE_INTERPRET
                and _acc_update_takes(table.rows_per_shard,
                                      self.num_shards * batch))

    def push(self, name: str, indices, grads, handle: str = None):
        """indices: [W, n] int rows per worker; grads: [W, n, d].
        Duplicate rows (within or across workers) accumulate — the
        aggregation contract of the default server handle.

        ``handle="row_adagrad:lr,eps"`` instead applies the
        DLRM-standard row-wise Adagrad: the per-row aggregate gradient
        updates a per-row accumulator, and the row steps by
        ``-lr * G / (sqrt(acc) + eps)`` — the fused sparse analog of the
        dense engine's optimizer handles.

        Bound once, launched many times: what no two pushes of ``(name,
        handle, batch)`` differ in is a :class:`_BoundPush` that the first
        builds (:meth:`_bind_push`) and the others look up."""
        t0 = stamp()  # stage borders: see _note
        table = self._tables[name]
        idx, g = self._prep(table, indices, grads)
        batch = int(idx.shape[1])
        t1 = stamp()  # prep | select
        # The record depends on table.pack, which the orbax compat shim
        # can mutate — look it up under the lock (so the sparse stages run
        # prep, select, launch, and select has the wait for the lock).
        with self._table_mu[name]:
            b = (self._bound.get((name, handle, batch))
                 or self._bind_push(name, handle, batch))
            t2 = stamp()  # select | launch
            if b.kind is None:
                self._stores[name], token = b.prog(
                    self._stores[name], idx, g)
            else:
                if name not in self._acc:
                    self._ensure_acc(name, table)
                self._stores[name], self._acc[name], token = b.prog(
                    self._stores[name], self._acc[name], idx, g, *b.params)
                self.stateful_pushes += 1
            self.row_kernel_pushes += b.row_kernel
            self.segsum_kernel_pushes += b.segsum_kernel
            self.acc_kernel_pushes += b.acc_kernel
            self.packed_pushes += b.packed
        self._observe("push", table, batch)
        t3 = stamp()
        self._note((SPARSE_ROUTE, t3, b.slots, -1, -1))
        self._note((ENGINE_OP, t3, t2 - t1, t1 - t0, t3 - t2))
        # The token is a tiny non-donated output that becomes ready when
        # the push completes — block on it freely (the store itself is
        # donated by the next push, so it must not escape).
        return token

    def _sparse_group_program(self, op: str, tables, batches: tuple):
        """One jitted program over SEVERAL tables (one dispatch instead
        of len(tables) — the many-embedding-tables pattern of a real
        recommender step, dense analog: engine.push_pull_group)."""
        key = (op, tuple((t.name, t.pack) for t in tables), batches)
        with self._mu:
            prog = self._programs.get(key)
        if prog is not None:
            return prog

        import jax
        from jax import lax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        S = self.num_shards
        k = len(tables)
        Rs = [t.rows_per_shard for t in tables]

        store_spec = P(axis, None)
        acc_spec = P(axis)
        idx_spec = P(axis, None)
        g_spec = P(axis, None, None)

        packs = [t.pack for t in tables]
        dims = [t.dim for t in tables]

        from jax.sharding import NamedSharding

        store_fmts = tuple(
            _store_out_format(self._stores[t.name], self.mesh, axis)
            for t in tables
        )
        tok_sh = NamedSharding(self.mesh, P(axis, None))
        acc_sh = NamedSharding(self.mesh, P(axis))

        if op == "push":
            def body(*args):
                stores = args[:k]
                idxs = args[k:2 * k]
                grads = args[2 * k:]
                new = [
                    _scatter_rows(axis, S, Rs[i], packs[i], dims[i],
                                  s, idxs[i], grads[i])
                    for i, s in enumerate(stores)
                ]
                return (*new, new[0][:1, :1])

            fn = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=tuple([store_spec] * k + [idx_spec] * k
                               + [g_spec] * k),
                out_specs=tuple([store_spec] * k + [store_spec]),
                check_vma=False,
            )
            jitted = jax.jit(
                fn, donate_argnums=tuple(range(k)),
                out_shardings=(*store_fmts, tok_sh),
            )
        elif op == "push_row_adagrad":
            def body(*args):
                stores = args[:k]
                accs = args[k:2 * k]
                idxs = args[2 * k:3 * k]
                grads = args[3 * k:4 * k]
                lr, eps = args[4 * k], args[4 * k + 1]
                new_s, new_a = [], []
                for i, (s, a) in enumerate(zip(stores, accs)):
                    n2, a2 = _adagrad_sparse(
                        axis, S, Rs[i], packs[i], dims[i], s, a,
                        idxs[i], grads[i], lr, eps,
                    )
                    new_s.append(n2)
                    new_a.append(a2)
                return (*new_s, *new_a, new_s[0][:1, :1])

            fn = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=tuple([store_spec] * k + [acc_spec] * k
                               + [idx_spec] * k + [g_spec] * k
                               + [P(), P()]),
                out_specs=tuple([store_spec] * k + [acc_spec] * k
                                + [store_spec]),
                check_vma=False,
            )
            jitted = jax.jit(
                fn, donate_argnums=tuple(range(2 * k)),
                out_shardings=(*store_fmts, *([acc_sh] * k), tok_sh),
            )
        elif op == "pull":
            def body(*args):
                stores = args[:k]
                idxs = args[k:]
                return tuple(
                    _pull_rows(axis, S, s, idxs[i], pack=packs[i],
                               dim=dims[i])
                    for i, s in enumerate(stores)
                )

            fn = jax.shard_map(
                body, mesh=self.mesh,
                in_specs=tuple([store_spec] * k + [idx_spec] * k),
                out_specs=tuple([store_spec] * k),
                check_vma=False,
            )
            jitted = jax.jit(fn)
        else:
            raise ValueError(op)
        return self._keep(key, jitted)

    def _lock_tables(self, names):
        ordered = sorted(set(names))
        for n in ordered:
            self._table_mu[n].acquire()
        return ordered

    def _unlock_tables(self, ordered):
        for n in reversed(ordered):
            self._table_mu[n].release()

    def push_group(self, names, indices_list, grads_list,
                   handle: str = None):
        """Push SEVERAL tables in one dispatch; same semantics per table
        as :meth:`push` (``handle`` applies to all)."""
        log.check(len(names) == len(indices_list) == len(grads_list),
                  "group length mismatch")
        log.check(len(set(names)) == len(names),
                  "duplicate table in group (stores are donated)")
        t0 = stamp()  # stage borders: see _note
        tables = [self._tables[n] for n in names]
        prepped = [
            self._prep(t, i, g)
            for t, i, g in zip(tables, indices_list, grads_list)
        ]
        idxs = [p[0] for p in prepped]
        gs = [p[1] for p in prepped]
        batches = tuple(int(i.shape[1]) for i in idxs)
        t1 = stamp()  # prep | select
        ordered = self._lock_tables(names)
        try:
            prog = self._sparse_group_program(
                "push" if handle is None else "push_row_adagrad",
                tables, batches,
            )
            t2 = stamp()  # select | launch
            kk = len(names)
            if handle is None:
                outs = prog(*[self._stores[n] for n in names],
                            *idxs, *gs)
                for i, n in enumerate(names):
                    self._stores[n] = outs[i]
                token = outs[kk]
            else:
                _, params = self._handle_scalars(handle)
                for n, t in zip(names, tables):
                    self._ensure_acc(n, t)
                outs = prog(
                    *[self._stores[n] for n in names],
                    *[self._acc[n] for n in names],
                    *idxs, *gs, *params,
                )
                for i, n in enumerate(names):
                    self._stores[n] = outs[i]
                    self._acc[n] = outs[kk + i]
                token = outs[2 * kk]
                self.stateful_pushes += 1
            # One push, whatever it groups: counted where a kernel
            # serves any of its tables.
            self.row_kernel_pushes += any(map(self._row_kernel, tables))
            self.segsum_kernel_pushes += any(
                self._segsum_kernel(t, handle is not None) for t in tables)
            self.acc_kernel_pushes += handle is not None and any(
                map(self._acc_kernel, tables, batches))
            self.packed_pushes += any(t.pack != 1 for t in tables)
        finally:
            self._unlock_tables(ordered)
        t3 = stamp()
        # One op with one launch, whatever it groups.
        self._note((SPARSE_ROUTE, t3, self._route_slots(sum(batches)),
                    -1, -1))
        self._note((ENGINE_OP, t3, t2 - t1, t1 - t0, t3 - t2))
        for t, batch in zip(tables, batches):
            self._observe("push", t, batch)
        return token

    def pull_group(self, names, indices_list):
        """Pull SEVERAL tables in one dispatch; returns the list of
        [W, n_i, d_i] arrays in ``names`` order."""
        log.check(len(names) == len(indices_list), "group length mismatch")
        t0 = stamp()  # stage borders: see _note
        tables = [self._tables[n] for n in names]
        idxs = [self._prep(t, i)[0]
                for t, i in zip(tables, indices_list)]
        batches = tuple(int(i.shape[1]) for i in idxs)
        t1 = stamp()  # prep | select
        ordered = self._lock_tables(names)
        try:
            # Resolve table.pack under the locks (see push).
            prog = self._sparse_group_program("pull", tables, batches)
            t2 = stamp()  # select | launch
            outs = prog(*[self._stores[n] for n in names], *idxs)
            pulled = [
                o.reshape(self.num_shards, -1, t.dim)
                for o, t in zip(outs, tables)
            ]
        finally:
            self._unlock_tables(ordered)
        for t, batch in zip(tables, batches):
            self._observe("pull", t, batch)
        t3 = stamp()
        self._note((SPARSE_ROUTE, t3, self._route_slots(sum(batches)),
                    -1, -1))
        self._note((ENGINE_OP, t3, t2 - t1, t1 - t0, t3 - t2))
        return pulled

    def pull(self, name: str, indices):
        """indices: [W, n] -> [W, n, d] rows, each worker shard receiving its
        own batch."""
        t0 = stamp()  # stage borders: see _note
        table = self._tables[name]
        idx, _ = self._prep(table, indices)
        batch = int(idx.shape[1])
        t1 = stamp()  # prep | select
        with self._table_mu[name]:
            # Resolve table.pack under the lock (see push).
            prog = self._sparse_program("pull", table, batch)
            t2 = stamp()  # select | launch
            out = prog(self._stores[name], idx)  # global [W*n, d]
            pulled = out.reshape(self.num_shards, -1, table.dim)
        self._observe("pull", table, batch)
        t3 = stamp()
        self._note((SPARSE_ROUTE, t3, self._route_slots(batch), -1, -1))
        self._note((ENGINE_OP, t3, t2 - t1, t1 - t0, t3 - t2))
        return pulled

    def store_array(self, name: str):
        """A consistent snapshot of the sharded table in the LOGICAL
        shard-interleaved layout [rps*S, dim] (for checkpointing) —
        lane-packed tables are unpacked on the way out, so consumers
        never see the physical packing.  Copied under the table lock —
        see CollectiveEngine.store_array.  For a plain device-drain use
        :meth:`block` (no copy)."""
        import jax.numpy as jnp

        with self._table_mu[name]:
            t = self._tables[name]
            # Capture layout metadata WITH the snapshot so a concurrent
            # pack change (orbax compat shim) cannot desynchronize the
            # copy from its unpack.
            pack, rps = t.pack, t.rows_per_shard
            host = np.asarray(jnp.copy(self._stores[name]))
        return _unpack_host(host, rps, self.num_shards, pack, t.dim)

    def store_raw(self, name: str):
        """A consistent snapshot of the PHYSICAL sharded store (the
        lane-packed layout, matching :meth:`store_spec`) — what
        legacy-format orbax checkpoints saved and restore verbatim."""
        import jax.numpy as jnp

        with self._table_mu[name]:
            return jnp.copy(self._stores[name])

    def store_global_device(self, name: str):
        """The GLOBAL logical table ``[num_rows, dim]`` as a DEVICE
        computation (no host fetch — multi-host safe): unpack the lane
        packing and de-interleave the shard layout with pure
        reshape/transpose ops, the jnp mirror of
        :func:`_deinterleave_rows`.  This is what the fleet-size-portable
        orbax checkpoint (v2) saves: a logical array any shard count can
        restore."""
        import jax.numpy as jnp

        with self._table_mu[name]:
            t = self._tables[name]
            S, rps, pack, dim = (self.num_shards, t.rows_per_shard,
                                 t.pack, t.dim)
            num_rows = t.num_rows
            store = jnp.copy(self._stores[name])
        # Unpack ([phys*S, pack*dim] -> per-shard rows) and de-interleave
        # in one reshape/transpose chain.
        return store.reshape(S, rps, dim).transpose(1, 0, 2).reshape(
            rps * S, dim
        )[:num_rows]

    def acc_global_device(self, name: str):
        """GLOBAL logical Adagrad accumulator ``[num_rows]``, device-side
        (see :meth:`store_global_device`)."""
        import jax.numpy as jnp

        with self._table_mu[name]:
            t = self._tables[name]
            log.check(name in self._acc, f"no accumulator for {name!r}")
            S, rps = self.num_shards, t.rows_per_shard
            acc = jnp.copy(self._acc[name])
        return acc.reshape(S, rps).transpose(1, 0).reshape(-1)[:t.num_rows]

    def store_spec(self, name: str):
        """Shape/dtype/sharding of a table without copying it (restore
        targets)."""
        import jax

        with self._table_mu[name]:
            arr = self._stores[name]
            return jax.ShapeDtypeStruct(
                arr.shape, arr.dtype, sharding=arr.sharding
            )

    def block(self, name: Optional[str] = None) -> None:
        """Wait for outstanding device work without copying the table."""
        if name is not None:
            names = [name]
        else:
            with self._mu:
                names = list(self._stores)
        for n in names:
            with self._table_mu[n]:
                self._stores[n].block_until_ready()

    def set_store_array(self, name: str, value,
                        global_rows: bool = False) -> None:
        """Restore a table (checkpoint resume).  ``global_rows=True``
        accepts the fleet-size-portable GLOBAL row order ([num_rows,
        dim], the v2 checkpoint layout) and interleaves it for THIS
        engine's shard count; otherwise host arrays must already be in
        the shard-interleaved layout ``store_array`` exposes.  Sharded
        ``jax.Array``s (multi-host restores) are assigned directly."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        log.check(name in self._tables, f"table {name!r} not registered")
        table = self._tables[name]
        S = self.num_shards
        # Host arrays arrive in LOGICAL layouts (global rows or
        # interleaved — what store_array exposes) and are packed here;
        # sharded jax.Arrays (orbax same-fleet restores) carry the
        # PHYSICAL store shape.
        expected = (table.rows_per_shard * S, table.dim)
        phys_expected = (table.phys_rows * S, table.pack * table.dim)
        sharding = NamedSharding(self.mesh, P(self.axis, None))
        if global_rows and isinstance(value, jax.Array):
            # Fleet-portable DEVICE restore (orbax v2): interleave +
            # re-pack on device — the jnp mirror of _interleave_rows +
            # _pack_host, multi-host safe (no host fetch).
            import jax.numpy as jnp

            log.check_eq(tuple(value.shape), (table.num_rows, table.dim),
                         "bad global-rows restore shape")
            rps, dim, pack = table.rows_per_shard, table.dim, table.pack
            v = jnp.pad(
                value.astype(table.dtype),
                ((0, rps * S - table.num_rows), (0, 0)),
            )
            inter = v.reshape(rps, S, dim).transpose(1, 0, 2)
            phys = inter.reshape(S * table.phys_rows, pack * dim)
            placed = jax.device_put(phys, sharding)
            with self._table_mu[name]:
                self._stores[name] = placed
            return
        if global_rows and not isinstance(value, jax.Array):
            host = np.asarray(value)
            log.check_eq(tuple(host.shape), (table.num_rows, table.dim),
                         "bad global-rows restore shape")
            value = _interleave_rows(
                host, table.num_rows, table.rows_per_shard,
                S, table.dtype,
            )
        if isinstance(value, jax.Array):
            equivalent = value.sharding == sharding or (
                hasattr(value.sharding, "is_equivalent_to")
                and value.sharding.is_equivalent_to(sharding, value.ndim)
            )
            if equivalent:
                log.check_eq(tuple(value.shape), phys_expected,
                             "bad restore shape")
                with self._table_mu[name]:
                    self._stores[name] = value
                return
        host = np.asarray(value)
        unrounded_rps = -(-table.num_rows // S)
        if (tuple(host.shape) != expected
                and host.ndim == 2 and host.shape[1] == table.dim
                and host.shape[0] == unrounded_rps * S):
            # COMPAT, narrowly: a v1 checkpoint from an engine with the
            # SAME shard count whose rows_per_shard was the plain
            # ceil(num_rows/S) (pre-lane-packing rounding).  The shape
            # alone cannot distinguish other shard counts (v1 meta has
            # no num_shards), so only this exact size re-interleaves —
            # anything else still fails loud below.
            host = _interleave_rows(
                _deinterleave_rows(host, table.num_rows, unrounded_rps,
                                   S),
                table.num_rows, table.rows_per_shard, S, table.dtype,
            )
        log.check_eq(tuple(host.shape), expected, "bad restore shape")
        placed = self._place(
            _pack_host(host, table.rows_per_shard, S, table.pack,
                       table.dim),
            sharding,
        )
        with self._table_mu[name]:
            self._stores[name] = placed

    def reshard(self, mesh, axis_name: Optional[str] = None) -> None:
        """Re-lay every registered table onto a new mesh — the sparse
        half of the engine elastic tier (see CollectiveEngine.reshard
        and reshard_staged for the pair-atomicity split).

        Rows are de-interleaved to global order on the host, the
        row→shard mapping is recut for the new shard count (global row r
        lives on shard ``r % S`` — the modulo sharding that load-balances
        skewed key distributions), and programs rebuild lazily.

        Multi-process meshes work on either side; reshard is then a
        COLLECTIVE — every participating process calls it with the same
        new mesh (see CollectiveEngine.reshard)."""
        with self.reshard_staged(mesh, axis_name) as commit:
            commit()

    @contextlib.contextmanager
    def reshard_staged(self, mesh, axis_name: Optional[str] = None):
        """Stage a table recut and yield its zero-failure commit
        closure — same contract as CollectiveEngine.reshard_staged
        (everything fallible on entry, commit is assignments only,
        table locks held until exit)."""
        from .placement import (
            local_shard_count,
            mesh_is_multiprocess,
            to_host_global,
        )

        new_multiprocess = mesh_is_multiprocess(mesh)
        axis = axis_name or self.axis
        log.check(axis in mesh.axis_names,
                  f"axis {axis!r} not in new mesh")
        with self._mu:
            names = list(self._tables)
        ordered = sorted(names)
        for n in ordered:
            self._table_mu[n].acquire()
        try:
            # Sorted iteration: the multi-process snapshot is a sequence
            # of collectives — every process must issue them in the same
            # order (see CollectiveEngine.reshard).
            old_mp = self._multiprocess
            names = ordered
            snap = {}
            for n in names:
                t = self._tables[n]
                S, rps = self.num_shards, t.rows_per_shard
                host = _unpack_host(
                    to_host_global(self._stores[n], old_mp),
                    rps, S, t.pack, t.dim,
                )
                glob = _deinterleave_rows(host, t.num_rows, rps, S)
                acc_glob = None
                if n in self._acc:
                    acc_glob = _deinterleave_rows(
                        to_host_global(self._acc[n], old_mp),
                        t.num_rows, rps, S,
                    )
                snap[n] = (t, glob, acc_glob)

            # STAGE: build every new placement against the NEW mesh
            # without touching engine state — a failed recut aborts with
            # every table intact on the old mesh (crash-consistency, see
            # CollectiveEngine.reshard's staged commit).
            from jax.sharding import NamedSharding, PartitionSpec as P

            from .placement import place_host_array

            new_num_shards = mesh.shape[axis]
            row_sharding = NamedSharding(mesh, P(axis, None))
            acc_sharding = NamedSharding(mesh, P(axis))
            staged = {}
            for n in names:
                t, glob, acc_glob = snap[n]
                rps = -(-t.num_rows // new_num_shards)
                rps = -(-rps // t.pack) * t.pack
                store = place_host_array(
                    mesh,
                    _pack_host(
                        _interleave_rows(glob, t.num_rows, rps,
                                         new_num_shards, t.dtype),
                        rps, new_num_shards, t.pack, t.dim,
                    ),
                    row_sharding, new_multiprocess,
                )
                acc = None
                if acc_glob is not None:
                    acc = place_host_array(
                        mesh,
                        _interleave_rows(acc_glob, t.num_rows, rps,
                                         new_num_shards, np.float32),
                        acc_sharding, new_multiprocess,
                    )
                staged[n] = (
                    SparseTable(n, t.num_rows, t.dim, rps, t.dtype,
                                pack=t.pack),
                    store,
                    acc,
                )

            # COMMIT closure: plain assignments only — never a torn
            # table set.
            def commit() -> None:
                self.mesh = mesh
                self.axis = axis
                self.num_shards = new_num_shards
                self._multiprocess = new_multiprocess
                self._local_shard_count = (
                    local_shard_count(mesh) if new_multiprocess
                    else new_num_shards
                )
                with self._mu:
                    self._programs.clear()
                    self._bound.clear()
                    for n in names:
                        table, store, acc = staged[n]
                        self._tables[n] = table
                        self._stores[n] = store
                        if acc is not None:
                            self._acc[n] = acc

            yield commit
        finally:
            for n in reversed(ordered):
                self._table_mu[n].release()

    def table(self, name: str) -> SparseTable:
        return self._tables[name]
