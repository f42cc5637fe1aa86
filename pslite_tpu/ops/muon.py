"""Muon on a server shard: the first server handle that works on whole
matrices.

Muon (Jordan et al. 2024; as Moonshot AI's "Muon is Scalable for LLM
Training", arXiv 2502.16982, and ``MoonshotAI/Moonlight``
``examples/toy_train.py`` ``class Muon`` run it) updates a weight MATRIX by
its orthogonalised momentum::

    M  = mu*M + G
    Gn = G + mu*M                                   (Nesterov)
    O  = NS5(Gn)
    W  = W*(1 - lr*wd) - lr*0.2*sqrt(max(rows, cols)) * O

``NS5`` is five Newton-Schulz steps in bfloat16: ``X = bf16(Gn)``,
transposed if rows > cols, ``X /= (|X|_F + 1e-7)``, then five times
``A = X X^T; B = b*A + c*A A; X = a*X + B X``.  Keys that are no matrices
to it (embeddings, the output head, gains: ``KEY_ELEMENTWISE``) take AdamW.

What is fixed here and written into the benchmark's reference
(``benchmark/muon_reference.py``): M, W, the decay, the step and the scale
are f32; the operands of the fifteen products are bfloat16 (rounded to
nearest-even), the products accumulate in f32, and each of the recurrence's
three lines is rounded to bfloat16 once, after its epilogue (``A`` as it
leaves its product, ``B`` after ``b*A + c*(A A)``, ``X`` after
``a*X + (B X)``), where the published code rounds every intermediate; the
Frobenius norm is taken in f32 over the bf16 values.

The products are plain XLA: a batched ``dot_general`` a
product, one batch a :class:`MuonChunk` (keys of one ``(shorter, longer)``
side, tall ones transposed into it, at most ``MUON_CHUNK_VALUES`` values
together, so that the bf16 temporaries of a chunk, X twice, A and B, stay a
few hundred MB whatever the tree).  The momentum is kept AS THE CHUNKS ARE,
one f32 array ``[B, m, n]`` a chunk, so no pass lays it out anew;
``opt_state`` hands it out, and takes it back, as one vector in the keys'
order (:func:`momentum_vector`, :func:`momentum_chunks`).

How a key's gradient leaves the summed row ``f32[1, total]`` is not XLA's
(PR 44): the chip lays such a row out one sublane of eight to a tile, and
XLA's cut of a key is a reduce at a fifth of the HBM rate.  A key that
starts on a lane border (:func:`takes_row`) is read where it lies by a
Mosaic kernel with full vector registers: :func:`row_momentum` does a
chunk's momentum pass on it, :func:`row_vector` hands an AdamW key over as
a vector.  What decides is in the keys, not a knob; any other key keeps
XLA's cut.

The way out of the products is a kernel's too (PR 45).  Decay, step, a
tall key's transpose, the store and the pulled tree are one piece of work,
``W = W*(1 - lr*wd) - lr*0.2*sqrt(n)*O`` written where W lies and handed to
the worker, which XLA did in four passes (a fusion to a temporary, a
``dynamic_update_slice`` into a store whose tiles of 1,024 a key need not
start on, a ``copy`` of O transposed back, a cut of the whole new store for
the pulled tree).  :func:`row_apply` reads a block of O and the key's p
where it lies and writes the new values twice, in place and into the
pulled vector; :func:`row_adamw` is its element-wise sibling for the AdamW
keys, m and v in place beside p.  Which keys is :func:`takes_apply`'s to
say, per key; a bucket whose every key they write (``MuonPlan.pulls``)
pulls the kernels' own vector, any other the program's cut.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import numpy as np

NS_COEFFS = (3.4445, -4.7750, 2.0315)
NS_STEPS = 5
NS_EPS = 1e-7
# 0.2 * sqrt(max(rows, cols)): the report's match of Muon's update RMS to
# AdamW's.
RMS_MATCH = 0.2
# The most values one batched product takes (a layer's 24 expert matrices
# of 1408 x 2048, or three of 2048 x 11264).
MUON_CHUNK_VALUES = 66 * 2 ** 20


# The lanes of a vector register: a key whose gradient leaves the row
# through :func:`row_momentum` or :func:`row_vector` starts on a multiple
# of it (:func:`takes_row`).
LANES = 128
# The most values of the row a grid step of those kernels holds (1 MiB).
ROW_BLOCK_VALUES = 2 ** 18
# VMEM a call of :func:`row_momentum` may take: four streams' double
# buffers (7 MiB) and what Mosaic keeps of a block while it folds and
# transposes it.
ROW_VMEM_BYTES = 32 * 2 ** 20


class MuonChunk(NamedTuple):
    m: int                      # the shorter side
    n: int                      # the longer side
    keys: Tuple[int, ...]       # the bucket's key indices, in key order
    tall: Tuple[bool, ...]      # rows > cols: transposed into the chunk
    row: bool                   # every key's gradient is taken from the
    #                             row by :func:`row_momentum`


class MuonPlan(NamedTuple):
    """How a bucket's keys go through ``muon``: made once a bucket
    (:meth:`CollectiveEngine._muon_plan`)."""

    chunks: Tuple[MuonChunk, ...]
    muon_keys: np.ndarray       # key indices under Muon, in key order
    adamw_keys: np.ndarray      # key indices under AdamW, in key order
    mom_starts: np.ndarray      # where a Muon key lies in the momentum vector
    adamw_starts: np.ndarray    # where an AdamW key lies in m and v
    ns_flops: float             # Newton-Schulz FLOPs a step, as published
    row_keys: np.ndarray        # key indices whose gradient a kernel takes
    #                             from the row (:func:`takes_row`)
    apply_keys: np.ndarray      # key indices whose new values a kernel
    #                             writes (:func:`takes_apply`)

    @property
    def matrices(self) -> int:
        return len(self.muon_keys)

    @property
    def muon_len(self) -> int:
        return int(self.mom_starts[-1])

    @property
    def adamw_len(self) -> int:
        return int(self.adamw_starts[-1])

    @property
    def pulls(self) -> bool:
        """Every key's new values are a kernel's, so the kernels can leave
        the pulled vector whole beside the store."""
        return len(self.apply_keys) == len(self.muon_keys) + len(
            self.adamw_keys)

    @property
    def state_bytes(self) -> int:
        """4 B a Muon value + 8 B an AdamW value (the step slot apart)."""
        return 4 * self.muon_len + 8 * self.adamw_len


def ns_flops(rows: int, cols: int) -> float:
    """Five Newton-Schulz steps on one matrix, as published: ``X X^T``
    (2 m^2 n), ``A A`` (2 m^3), ``B X`` (2 m^2 n) a step."""
    m, n = min(rows, cols), max(rows, cols)
    return float(NS_STEPS * (4 * m * m * n + 2 * m ** 3))


def _block_rows(rows: int, cols: int, unit: int) -> int:
    """The rows of a ``(rows, cols)`` gradient a grid step takes: the most
    that divide ``rows``, are a multiple of ``unit`` and hold at most
    ``ROW_BLOCK_VALUES`` values; ``unit`` where none does."""
    fits = [d for d in range(unit, rows + 1, unit)
            if rows % d == 0 and d * cols <= ROW_BLOCK_VALUES]
    return max(fits, default=unit)


def _vector_tile(n: int) -> int:
    """The values of an AdamW key a grid step of :func:`row_vector`
    takes: the key whole, or whole tiles of 1,024 (a vector's on the chip)
    that divide it; 0 where neither fits a block."""
    if n <= ROW_BLOCK_VALUES:
        return n
    return max((d for d in range(1024, ROW_BLOCK_VALUES + 1, 1024)
                if n % d == 0), default=0)


def takes_row(start: int, rows: int, cols: int, elementwise: bool) -> bool:
    """Whether a kernel takes this key's gradient from the row where it
    lies, ``[start, start + rows * cols)`` of ``f32[1, total]``: it starts
    on a lane border and is whole lanes long, and a matrix is cut into
    blocks the chip's tiles allow (bfloat16 packs 16 rows to a tile, so a
    wide one has rows in sixteens and whole lanes a row; a tall one, which
    is transposed in the kernel, whole lanes both ways).  Any other key
    keeps the cut XLA makes (:func:`muon_update` ``key_grad``)."""
    if start % LANES or (rows * cols) % LANES or rows * cols == 0:
        return False
    if elementwise:
        return _vector_tile(rows * cols) > 0
    if rows > cols:
        return rows % LANES == 0 and cols % LANES == 0
    return rows % 16 == 0 and cols % LANES == 0


def takes_apply(start: int, rows: int, cols: int, elementwise: bool,
                lo: int = 0, state_len: int = 0) -> bool:
    """Whether a kernel writes this key's new values where they lie, in
    the store and in the pulled vector (:func:`row_apply`,
    :func:`row_adamw`): what :func:`takes_row` asks of its start and its
    sides, since the kernel addresses both vectors by rows of 128 values
    and walks the blocks that way walks, and of an AdamW key besides that
    its m and v, ``[lo, lo + rows * cols)`` of vectors ``[state_len]``,
    lie so too.  Any other key keeps XLA's ``dynamic_update_slice``, and a
    bucket with such a key the program's cut for its pulled tree."""
    return (takes_row(start, rows, cols, elementwise)
            and lo % LANES == 0 and state_len % LANES == 0)


def muon_plan(shapes, elementwise, chunk_values: int = MUON_CHUNK_VALUES
              ) -> MuonPlan:
    """``shapes`` ``[K, 2]`` (rows, cols a key), ``elementwise`` ``[K]``
    (the key takes AdamW).  Keys of one ``(shorter, longer)`` side share a
    group whatever their orientation; a group is cut, in key order, into
    chunks of at most ``chunk_values`` values (one key at least).  A chunk
    takes its gradients from the row where every key of it can
    (:func:`takes_row`: a chunk's momentum is one array, updated by one
    pass), an AdamW key where that key can."""
    shapes = np.asarray(shapes, np.int64).reshape(-1, 2)
    elementwise = np.asarray(elementwise, bool)
    lens = shapes[:, 0] * shapes[:, 1]
    starts = np.concatenate([[0], np.cumsum(lens)])
    ok = [takes_row(int(starts[k]), int(shapes[k, 0]), int(shapes[k, 1]),
                    bool(elementwise[k])) for k in range(len(lens))]
    muon = np.flatnonzero(~elementwise)
    adamw = np.flatnonzero(elementwise)
    groups: dict = {}
    for k in muon:
        r, c = (int(d) for d in shapes[k])
        groups.setdefault((min(r, c), max(r, c)), []).append(int(k))
    chunks = []
    for (m, n), keys in groups.items():
        per = max(1, chunk_values // (m * n))
        for i in range(0, len(keys), per):
            part = tuple(keys[i:i + per])
            chunks.append(MuonChunk(
                m, n, part,
                tuple(bool(shapes[k, 0] > shapes[k, 1]) for k in part),
                all(ok[k] for k in part)))
    row_keys = sorted([k for c in chunks if c.row for k in c.keys]
                      + [int(k) for k in adamw if ok[k]])
    adamw_starts = np.concatenate([[0], np.cumsum(lens[adamw])])
    lie = {int(k): (int(lo), int(adamw_starts[-1]))
           for k, lo in zip(adamw, adamw_starts)}
    apply_keys = [k for k in range(len(lens)) if takes_apply(
        int(starts[k]), int(shapes[k, 0]), int(shapes[k, 1]),
        bool(elementwise[k]), *lie.get(k, ()))]
    return MuonPlan(
        chunks=tuple(chunks), muon_keys=muon, adamw_keys=adamw,
        mom_starts=np.concatenate([[0], np.cumsum(lens[muon])]),
        adamw_starts=adamw_starts,
        ns_flops=float(sum(ns_flops(*shapes[k]) for k in muon)),
        row_keys=np.array(row_keys, np.int64),
        apply_keys=np.array(apply_keys, np.int64))


def state_shapes(plan: MuonPlan) -> Tuple[Tuple[int, ...], ...]:
    """The state's arrays but for the step slot: a momentum a chunk, then
    AdamW's m and v."""
    return (*((len(c.keys), c.m, c.n) for c in plan.chunks),
            (plan.adamw_len,), (plan.adamw_len,))


def momentum_vector(plan: MuonPlan, chunks, xp):
    """The chunks' momenta as one vector in the keys' order, a key's
    values row-major as its matrix lies in the store (``xp``: numpy or
    ``jax.numpy``)."""
    parts = {}
    for chunk, arr in zip(plan.chunks, chunks):
        for i, (k, tall) in enumerate(zip(chunk.keys, chunk.tall)):
            parts[k] = (arr[i].T if tall else arr[i]).reshape(-1)
    if not parts:
        return xp.zeros((0,), np.float32)
    return xp.concatenate([parts[int(k)] for k in plan.muon_keys])


def momentum_chunks(plan: MuonPlan, vector, xp):
    """The inverse of :func:`momentum_vector`."""
    where = {int(k): i for i, k in enumerate(plan.muon_keys)}
    out = []
    for chunk in plan.chunks:
        rows = []
        for k, tall in zip(chunk.keys, chunk.tall):
            lo = int(plan.mom_starts[where[k]])
            shape = (chunk.n, chunk.m) if tall else (chunk.m, chunk.n)
            mat = vector[lo:lo + chunk.m * chunk.n].reshape(shape)
            rows.append(mat.T if tall else mat)
        out.append(xp.stack(rows))
    return out


# -- a bucket over several shards: whole keys an owner ------------------------


class OwnerPlan(NamedTuple):
    """How a bucket's keys lie over ``shards`` owners, every matrix whole
    on one (:func:`owner_plan`).  A shard is laid out the same way on every
    owner: the slots of ``plan.chunks`` one behind the other (of each shape
    class as many an owner as divide evenly, wide and tall apart), then the
    room of what is left of the classes, which each owner fills with its
    own (``branches``), then the owner's stretch of the element-wise keys'
    values.  ``starts`` and ``shapes`` are a slot's, as a key's are on one
    shard; the slots of two branches lie over each other."""

    shards: int
    shard_len: int
    starts: np.ndarray          # [slots]: where a slot begins in a shard
    shapes: np.ndarray          # [slots, 2]
    plan: MuonPlan              # what every owner runs: the chunks all
    #                             have, the element-wise stretch as one key
    rest: Tuple[Tuple[int, int, int], ...]  # (B, m, n) of each momentum
    #                             array of what is left of the classes
    # A branch: (which array of ``rest``, the chunk it runs there) pairs.
    branches: Tuple[Tuple[Tuple[int, MuonChunk], ...], ...]
    branch_of: np.ndarray       # [shards]: the branch an owner takes
    # A matrix key's place, [K, 4]: its owner, its momentum array (among
    # the chunks', then ``rest``'s), its row there on the owner, its slot;
    # -1s for an element-wise key.
    where: np.ndarray
    stretch: int                # element-wise values an owner holds
    elementwise_len: int        # ... and all owners together, unpadded
    # (start in key order, start in owners' order, values), by the first.
    segments: np.ndarray
    total_len: int
    flops: np.ndarray           # [shards]: Newton-Schulz FLOPs an owner

    @property
    def padded_len(self) -> int:
        return self.shards * self.shard_len

    @property
    def matrices(self) -> int:
        return int((self.where[:, 0] >= 0).sum())

    @property
    def ns_flops(self) -> float:
        return float(self.flops.sum())

    @property
    def laid(self):
        """The runs by where they land, and the ``(start, values)`` that
        no run covers: what is laid, and what is filled with zeros."""
        by_dst = sorted(self.segments.tolist(), key=lambda e: e[1])
        gaps, at = [], 0
        for _, dst, n in by_dst + [[0, self.padded_len, 0]]:
            if dst > at:
                gaps.append((at, dst - at))
            at = dst + n
        return by_dst, gaps

    @property
    def state_bytes(self) -> int:
        """What the state holds over all owners, the step slots apart."""
        return 4 * self.shards * (
            sum(math.prod(s) for s in owner_state_shapes(self)))


def _up(n: int, unit: int) -> int:
    return -(-n // unit) * unit


def owner_plan(shapes, elementwise, shards: int,
               chunk_values: int = MUON_CHUNK_VALUES) -> OwnerPlan:
    """Deal the keys of a bucket to ``shards`` owners by whole keys.

    Of a shape class ``(shorter, longer)`` every owner gets the same
    number of wide keys and of tall ones, a run of the key order each: so
    every owner's shard is laid out alike and ONE program serves them.
    What does not divide (five ``q`` matrices over four owners) is dealt
    one key at a time, the heaviest first, to the owner with the fewest
    Newton-Schulz FLOPs so far; owners left with equal lists share a
    branch of the program, and the room a shard keeps for them is the
    fullest owner's.  The element-wise keys' values, which no handle needs
    whole, are cut into equal stretches in key order, so they level the
    owners out."""
    shapes = np.asarray(shapes, np.int64).reshape(-1, 2)
    elementwise = np.asarray(elementwise, bool)
    lens = shapes[:, 0] * shapes[:, 1]
    key_starts = np.concatenate([[0], np.cumsum(lens)])
    S = int(shards)
    groups: dict = {}
    for k in np.flatnonzero(~elementwise):
        r, c = (int(d) for d in shapes[k])
        groups.setdefault((min(r, c), max(r, c)), ([], []))[r > c].append(
            int(k))

    slot_shapes, slot_starts = [], []
    where = np.full((len(lens), 4), -1, np.int64)
    at = 0

    def slot(shape) -> int:
        nonlocal at
        at = _up(at, LANES)
        slot_starts.append(at)
        slot_shapes.append(tuple(int(d) for d in shape))
        at += int(shape[0]) * int(shape[1])
        return len(slot_starts) - 1

    # What divides: the same slots on every owner.
    flops = np.zeros(S)
    chunks, left = [], []
    for (m, n), by_side in groups.items():
        mine = [[] for _ in range(S)]        # (key, tall) an owner, by slot
        for tall, keys in enumerate(by_side):
            per = len(keys) // S
            for s in range(S):
                mine[s] += [(k, bool(tall)) for k in
                            keys[s * per:(s + 1) * per]]
            left += [((m, n), bool(tall), k) for k in keys[S * per:]]
        flops += len(mine[0]) * ns_flops(m, n)
        per = max(1, chunk_values // (m * n))
        for i in range(0, len(mine[0]), per):
            talls = tuple(t for _, t in mine[0][i:i + per])
            ids = tuple(slot((n, m) if t else (m, n)) for t in talls)
            for s in range(S):
                for j, (k, _) in enumerate(mine[s][i:i + per]):
                    where[k] = (s, len(chunks), j, ids[j])
            chunks.append((m, n, ids, talls))

    # What is left: a key at a time, the heaviest first, to the lightest.
    theirs = [[] for _ in range(S)]
    for cls, tall, k in sorted(left, key=lambda e: -ns_flops(*e[0])):
        s = int(np.argmin(flops))
        flops[s] += ns_flops(*cls)
        theirs[s].append((cls, tall, k))
    classes = [cls for cls in groups if any(e[0] == cls for e in left)]
    rest = tuple(
        (max(sum(e[0] == cls for e in theirs[s]) for s in range(S)), *cls)
        for cls in classes)
    room, signatures, branches = at, [], []
    main_slots = len(slot_starts)
    branch_of = np.zeros(S, np.int64)
    end = room
    for s in range(S):
        lists = [(a, sorted((t, k) for c, t, k in theirs[s] if c == cls))
                 for a, cls in enumerate(classes)]
        signature = tuple((a, tuple(t for t, _ in ks))
                          for a, ks in lists if ks)
        if signature not in signatures:
            signatures.append(signature)
            at, pairs = room, []
            for a, talls in signature:
                _, m, n = rest[a]
                ids = tuple(slot((n, m) if t else (m, n)) for t in talls)
                pairs.append((a, (m, n, ids, talls)))
            branches.append(pairs)
            end = max(end, at)
        b = branch_of[s] = signatures.index(signature)
        for (a, ks), (_, (_, _, ids, _)) in zip(
                [e for e in lists if e[1]], branches[b]):
            for j, (_, k) in enumerate(ks):
                where[k] = (s, len(chunks) + a, j, ids[j])

    # The element-wise values: equal stretches of their order.
    adamw = np.flatnonzero(elementwise)
    E = int(lens[adamw].sum())
    stretch, adamw_slot = 0, None
    at = _up(end, 1024)
    if E:
        stretch = -(-E // S)
        stretch = _up(stretch, LANES if stretch <= ROW_BLOCK_VALUES
                      else 2 ** 16)
        adamw_slot = slot((1, stretch))
    shard_len = _up(at, 1024)
    starts = np.array(slot_starts, np.int64)
    sshapes = np.array(slot_shapes, np.int64).reshape(-1, 2)
    flagged = np.zeros(len(starts), bool)
    if adamw_slot is not None:
        flagged[adamw_slot] = True
    ok = [takes_row(int(starts[i]), *(int(d) for d in sshapes[i]),
                    bool(flagged[i])) for i in range(len(starts))]

    def chunk(m, n, ids, talls) -> MuonChunk:
        return MuonChunk(m, n, ids, talls, all(ok[i] for i in ids))

    adamw_keys = np.array([] if adamw_slot is None else [adamw_slot],
                          np.int64)
    plan = MuonPlan(
        chunks=tuple(chunk(*c) for c in chunks),
        muon_keys=np.flatnonzero(~flagged), adamw_keys=adamw_keys,
        mom_starts=np.zeros(1, np.int64),
        adamw_starts=np.array([0, stretch] if E else [0], np.int64),
        ns_flops=float(flops.sum()),
        row_keys=np.flatnonzero(ok),
        # A left-over key's new values reach the store by XLA's
        # ``dynamic_update_slice``: the kernel that writes them back is
        # one every device enters (its interpreter walks it with all of
        # them in step), and a branch is entered by its owners alone.
        apply_keys=np.array([i for i in range(len(starts)) if (
            i < main_slots or flagged[i]) and takes_apply(
            int(starts[i]), *(int(d) for d in sshapes[i]), bool(flagged[i]),
            0, stretch)], np.int64))

    # Where each run of the key order lies in the owners' order.
    segments, e = [], 0
    for k in range(len(lens)):
        if not elementwise[k]:
            s, _, _, i = where[k]
            segments.append((int(key_starts[k]),
                             int(s * shard_len + starts[i]), int(lens[k])))
            continue
        lo, n = int(key_starts[k]), int(lens[k])
        while n:
            s, off = divmod(e, stretch)
            take = min(n, stretch - off)
            segments.append((lo, int(s * shard_len + starts[adamw_slot]
                                     + off), take))
            lo, n, e = lo + take, n - take, e + take
    merged = []
    for src, dst, n in segments:
        if n == 0:
            continue
        if merged and (merged[-1][0] + merged[-1][2] == src
                       and merged[-1][1] + merged[-1][2] == dst):
            merged[-1][2] += n
        else:
            merged.append([src, dst, n])
    return OwnerPlan(
        shards=S, shard_len=int(shard_len), starts=starts, shapes=sshapes,
        plan=plan, rest=rest,
        branches=tuple(tuple((a, chunk(*c)) for a, c in pairs)
                       for pairs in branches),
        branch_of=branch_of, where=where, stretch=int(stretch),
        elementwise_len=E,
        segments=np.array(merged, np.int64).reshape(-1, 3),
        total_len=int(key_starts[-1]), flops=flops)


def owner_state_shapes(owners: OwnerPlan) -> Tuple[Tuple[int, ...], ...]:
    """:func:`state_shapes` of one owner: a momentum a chunk, one for each
    class that left keys over (every owner keeps it, the one that owns none
    of them too), then AdamW's m and v over the owner's stretch."""
    return (*((len(c.keys), c.m, c.n) for c in owners.plan.chunks),
            *owners.rest, (owners.stretch,), (owners.stretch,))


def place(owners: OwnerPlan, values, xp):
    """``values`` ``[..., total_len]`` in key order laid into the owners'
    order ``[..., shards * shard_len]``, zeros where no key lies."""
    by_dst, gaps = owners.laid
    lead = tuple(values.shape[:-1])
    pieces = [(dst, values[..., src:src + n]) for src, dst, n in by_dst]
    pieces += [(at, xp.zeros(lead + (n,), values.dtype)) for at, n in gaps]
    return xp.concatenate(
        [piece for _, piece in sorted(pieces, key=lambda e: e[0])], axis=-1)


def unplace(owners: OwnerPlan, values, xp):
    """The inverse of :func:`place`: key order, at ``total_len``."""
    return xp.concatenate([values[..., dst:dst + n]
                           for _, dst, n in owners.segments.tolist()],
                          axis=-1)


def owner_momentum_vector(owners: OwnerPlan, arrays, xp):
    """:func:`momentum_vector` of the owners' momenta (``arrays``: a
    chunk's or a left-over class's over all owners, ``[shards * B, m,
    n]``)."""
    parts = []
    for k in np.flatnonzero(owners.where[:, 0] >= 0):
        s, a, j, i = (int(x) for x in owners.where[k])
        mat = arrays[a][s * (arrays[a].shape[0] // owners.shards) + j]
        rows, cols = owners.shapes[i]
        parts.append((mat.T if rows > cols else mat).reshape(-1))
    if not parts:
        return xp.zeros((0,), np.float32)
    return xp.concatenate(parts)


def owner_momentum_arrays(owners: OwnerPlan, vector, xp):
    """The inverse of :func:`owner_momentum_vector`; a slot no key lies in
    is zeros."""
    shapes = owner_state_shapes(owners)[:-2]
    rows = [[None] * (owners.shards * b) for b, _, _ in shapes]
    lo = 0
    for k in np.flatnonzero(owners.where[:, 0] >= 0):
        s, a, j, i = (int(x) for x in owners.where[k])
        r, c = (int(d) for d in owners.shapes[i])
        mat = vector[lo:lo + r * c].reshape(r, c)
        rows[a][s * shapes[a][0] + j] = mat.T if r > c else mat
        lo += r * c
    return [xp.stack([xp.zeros((m, n), np.float32) if x is None else x
                      for x in held])
            for held, (_, m, n) in zip(rows, shapes)]


def newton_schulz(x, steps: int = NS_STEPS):
    """``NS5`` of a batch ``[B, m, n]`` of bfloat16 matrices, m <= n."""
    import jax.numpy as jnp

    a, b, c = NS_COEFFS
    f32, bf16 = jnp.float32, jnp.bfloat16
    x32 = x.astype(f32)
    norm = jnp.sqrt(jnp.sum(x32 * x32, axis=(1, 2), keepdims=True))
    x = (x32 / (norm + NS_EPS)).astype(bf16)
    for _ in range(steps):
        xx = jnp.einsum("bik,bjk->bij", x, x,
                        preferred_element_type=f32).astype(bf16)
        poly = (b * xx.astype(f32)
                + c * jnp.einsum("bik,bkj->bij", xx, xx,
                                 preferred_element_type=f32)).astype(bf16)
        x = (a * x.astype(f32)
             + jnp.einsum("bij,bjk->bik", poly, x,
                          preferred_element_type=f32)).astype(bf16)
    return x


def _row_stretch(length: int, lane):
    """The ``BlockSpec`` of ``length`` values of the row ``f32[1, total]``
    that begin at lane ``lane(*grid indices, *prefetched)``: addressed by
    the element, since a key starts on a lane border and on no block's."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec(
        (pl.Element(1), pl.Element(length)),
        lambda *a: (0, pl.multiple_of(lane(*a) * LANES, LANES)))


def row_vector(row, start: int, n: int, *, interpret: bool):
    """``row[0, start:start + n]`` as a vector ``f32[n]``, read with full
    vector registers: the chip lays ``f32[1, n]`` out one sublane of eight
    to a tile, and XLA's own cut squeezes it at a fifth of the HBM rate."""
    import jax
    from jax.experimental import pallas as pl

    tile = _vector_tile(n)

    def kernel(g_ref, out_ref):
        out_ref[...] = g_ref[...].reshape(tile)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n,), row.dtype),
        grid=(n // tile,),
        in_specs=[_row_stretch(
            tile, lambda i: start // LANES + i * (tile // LANES))],
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        interpret=interpret,
        name="muon_row_vector",
    )(row)


def row_momentum(momentum, row, mom, chunk: MuonChunk, starts, tall: bool,
                 x=None, *, interpret: bool):
    """The momentum pass of the ``tall`` (or the other) keys of ``chunk``,
    their gradients taken from the row where they lie: ``M, X =
    momentum(M, G)``, M in place in ``mom`` ``[B, m, n]`` and the
    bfloat16 X into ``x`` (made here where none is given), every other
    key's slot of both left as it is.  Returns ``(M, X)``: the f32
    momentum first, which is how ``benchmark/muon_ops.py`` tells this pass
    from Newton-Schulz.

    A grid step holds a block of whole rows of one key's gradient, one
    stretch ``(1, rows * cols)`` of the row, and folds it to ``(rows,
    cols)`` in VMEM; a tall key's block is transposed there and lands in
    columns of its slot."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, n = chunk.m, chunk.n
    slots = [i for i, t in enumerate(chunk.tall) if t == tall]
    lanes = [int(starts[chunk.keys[i]]) // LANES for i in slots]
    rows, cols = (n, m) if tall else (m, n)
    step = _block_rows(rows, cols, LANES if tall else 16)
    block = (1, m, step) if tall else (1, step, n)

    def at(i, j, lanes_ref, slots_ref):
        return (slots_ref[i], 0, j) if tall else (slots_ref[i], j, 0)

    def kernel(lanes_ref, slots_ref, g_ref, m_ref, *refs):
        mo_ref, x_ref = refs[-2:]
        g = g_ref[...].reshape(step, cols)
        mo_ref[0], x_ref[0] = momentum(m_ref[0], g.T if tall else g)

    in_specs = [
        _row_stretch(
            step * cols, lambda i, j, lanes_ref, slots_ref:
            lanes_ref[i] + j * (step * cols // LANES)),
        pl.BlockSpec(block, at)]
    args = [row, mom]
    if x is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(x)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(mom.shape, mom.dtype),
                   jax.ShapeDtypeStruct(mom.shape, jnp.bfloat16)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(len(slots), rows // step),
            in_specs=in_specs,
            out_specs=(pl.BlockSpec(block, at), pl.BlockSpec(block, at))),
        input_output_aliases={3: 0, **({4: 1} if x is not None else {})},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ROW_VMEM_BYTES),
        interpret=interpret,
        name="muon_row_momentum",
    )(jnp.asarray(lanes, jnp.int32), jnp.asarray(slots, jnp.int32), *args)


def _window_step(t, steps: int, reads, writes, compute):
    """Grid step ``t`` of ``steps`` of a kernel that moves its windows of
    HBM itself, two slots deep: ``reads(t, slot)`` and ``writes(t, slot)``
    are the step's copies into and out of VMEM slot ``slot``,
    ``compute(slot)`` fills what is written from what was read.  A step
    starts the next step's reads before it waits for its own, and waits
    for a slot's writes when the slot comes round again, the last step
    for all that are under way (a wait names a copy of the size it is to
    take: the semaphore counts bytes)."""
    from jax import lax
    from jax.experimental import pallas as pl

    slot = lax.rem(t, 2)

    @pl.when(t == 0)
    def _():
        for copy in reads(t, slot):
            copy.start()

    @pl.when(t + 1 < steps)
    def _():
        for copy in reads(t + 1, 1 - slot):
            copy.start()

    for copy in reads(t, slot):
        copy.wait()

    @pl.when(t >= 2)
    def _():
        for copy in writes(t, slot):
            copy.wait()

    compute(slot)
    for copy in writes(t, slot):
        copy.start()

    @pl.when(t == steps - 1)
    def _():
        for s in (slot, 1 - slot)[:min(steps, 2)]:
            for copy in writes(t, s):
                copy.wait()


def _write_back(name: str, body, prefetch, blocked, spec, vectors, pulled,
                pulled_len: int, steps: int, streams: int, *,
                interpret: bool):
    """The call of a kernel that updates ``vectors`` (flat, whole lanes
    long) where they lie and, with ``pulled_len``, leaves the pulled
    vector ``f32[pulled_len]`` beside them: ``pulled`` where a call before
    this one has made it, made here (and what this call does not write
    left unset) where None.  All of them stay in HBM, seen as rows of 128
    values, the view that costs nothing (the chip tiles a vector by 1,024
    and a key may start anywhere in a tile: no block of a ``BlockSpec``
    starts there, so the kernel copies its windows itself,
    :func:`_window_step`); ``blocked`` alone comes in blocks, by ``spec``.

    ``body(t, prefetched, block, outs, bufs, sems)`` is grid step ``t`` of
    ``steps``: ``outs`` the vectors' rows and last the pulled vector's,
    ``bufs`` f32 VMEM ``[2 slots, 2 ways (in, out), streams, rows, 128]``
    of ``spec``'s rows of 128 values, ``sems`` the DMA semaphores ``[2, 2,
    streams + 1]`` beside them.  Returns the vectors, then the pulled vector
    or None."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    views = [x.reshape(-1, LANES) for x in vectors]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in views]
    if pulled is not None:
        views.append(pulled.reshape(-1, LANES))
    if pulled_len:
        out_shape.append(jax.ShapeDtypeStruct(
            (pulled_len // LANES, LANES), jnp.float32))
    fold = math.prod(spec.block_shape) // LANES

    def kernel(*refs):
        # Prefetched scalars, the block, the arguments (which the results
        # alias: the same memory), the results, the scratch.
        at = len(prefetch) + 1 + len(views)
        body(pl.program_id(0), refs[:len(prefetch)], refs[len(prefetch)],
             refs[at:at + len(out_shape)], *refs[-2:])

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shape),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(steps,),
            in_specs=[spec, *[hbm] * len(views)],
            out_specs=tuple([hbm] * len(out_shape)),
            scratch_shapes=[
                pltpu.VMEM((2, 2, streams, fold, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2, streams + 1))]),
        input_output_aliases={len(prefetch) + 1 + i: i
                              for i in range(len(views))},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=ROW_VMEM_BYTES),
        # The interpreter that knows DMAs and semaphores.
        interpret=(pltpu.InterpretParams(dma_execution_mode="eager")
                   if interpret else False),
        name=name,
    )(*prefetch, blocked, *views)
    flat = [x.reshape(-1) for x in outs]
    return (*flat[:len(vectors)], flat[-1] if pulled_len else None)


def row_apply(new_values, scale: float, o, store, pulled, chunk: MuonChunk,
              starts, tall: bool, slots, *, pulled_len: int = 0,
              interpret: bool):
    """The way out of the products for the ``tall`` (or the other) keys of
    ``chunk`` in ``slots``: ``p = new_values(p, O, scale)`` of a key
    written where it lies in ``store`` (the flat f32 store, in place) and,
    with ``pulled_len``, into the vector the program pulls
    (:func:`_write_back`).  Returns ``(store, pulled)``, the store first:
    an f32 first result is how ``benchmark/muon_ops.py`` tells this pass
    from Newton-Schulz.

    A grid step holds a block of whole rows of one key's bfloat16 O as
    :func:`row_momentum` holds the gradient's, widens it, transposes a
    tall key's in VMEM and folds it to rows of 128 values, which is how it
    addresses p."""
    import jax.numpy as jnp

    first = [int(starts[chunk.keys[i]]) // LANES for i in slots]
    return _row_apply(
        jnp.asarray(first, jnp.int32), jnp.asarray(slots, jnp.int32), o,
        store, pulled, new_values=new_values, scale=scale, tall=tall,
        pulled_len=pulled_len, interpret=interpret)


# A program calls these kernels dozens of times, and many calls differ in
# nothing but where their keys lie, which they are told in scalars: under
# ``jit`` a call whose shapes and static arguments an earlier one had is
# neither traced nor lowered again (35 calls of ``moonlight-16b-muon``'s
# step are 14 traces; on the chip's host a trace is a third of a second).
@functools.partial(jax.jit, static_argnames=(
    "new_values", "scale", "tall", "pulled_len", "interpret"))
def _row_apply(first, slots, o, store, pulled, *, new_values, scale, tall,
               pulled_len, interpret):
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, m, n = o.shape
    rows, cols = (n, m) if tall else (m, n)
    step = _block_rows(rows, cols, LANES if tall else 16)
    per, fold = rows // step, step * cols // LANES
    steps = slots.shape[0] * per

    def body(t, prefetched, o_ref, outs, bufs, sems):
        first_ref, _ = prefetched

        def window(ref, t):
            return ref.at[pl.ds(first_ref[t // per] + (t % per) * fold, fold)]

        def reads(t, slot):
            return [pltpu.make_async_copy(
                window(outs[0], t), bufs.at[slot, 0, 0], sems.at[slot, 0, 0])]

        def writes(t, slot):
            return [pltpu.make_async_copy(
                bufs.at[slot, 1, 0], window(ref, t), sems.at[slot, 1, i])
                for i, ref in enumerate(outs)]

        def compute(slot):
            o32 = o_ref[0].astype(jnp.float32)
            o32 = (o32.T if tall else o32).reshape(fold, LANES)
            bufs[slot, 1, 0] = new_values(bufs[slot, 0, 0], o32, scale)

        _window_step(t, steps, reads, writes, compute)

    def at(t, first_ref, slots_ref):
        i, j = slots_ref[t // per], t % per
        return (i, 0, j) if tall else (i, j, 0)

    return _write_back(
        "muon_row_apply", body, (first, slots), o,
        pl.BlockSpec((1, m, step) if tall else (1, step, n), at), [store],
        pulled, pulled_len, steps, 1, interpret=interpret)


def row_adamw(adamw, alpha, g, m, v, store, pulled, start: int, lo: int, *,
              pulled_len: int = 0, interpret: bool):
    """:func:`row_apply`'s element-wise sibling for one AdamW key of
    ``g.shape[0]`` values: ``p, m, v = adamw(p, m, v, g, alpha)`` with p at
    ``start`` of the store and m and v at ``lo`` of their vectors, all
    three in place, and the new p into the pulled vector as there.  ``g``
    is the key's gradient as :func:`row_vector` hands it over, ``alpha``
    the step's scalar.  Returns ``(store, m, v, pulled)``."""
    import jax.numpy as jnp

    # p and the pulled values lie at the key's start, m and v at lo.
    base = [start // LANES, lo // LANES, lo // LANES, start // LANES]
    return _row_adamw(
        jnp.reshape(alpha, (1,)), jnp.asarray(base, jnp.int32), g, m, v,
        store, pulled, adamw=adamw, pulled_len=pulled_len,
        interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("adamw", "pulled_len", "interpret"))
def _row_adamw(alpha, base, g, m, v, store, pulled, *, adamw, pulled_len,
               interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = g.shape[0]
    fold = _vector_tile(n) // LANES
    steps = n // (fold * LANES)

    def body(t, prefetched, g_ref, outs, bufs, sems):
        alpha_ref, base_ref = prefetched

        def window(i, t):
            return outs[i].at[pl.ds(base_ref[i] + t * fold, fold)]

        def reads(t, slot):
            return [pltpu.make_async_copy(
                window(i, t), bufs.at[slot, 0, i], sems.at[slot, 0, i])
                for i in range(3)]

        def writes(t, slot):
            return [pltpu.make_async_copy(
                bufs.at[slot, 1, i % 3], window(i, t), sems.at[slot, 1, i])
                for i in range(len(outs))]

        def compute(slot):
            new = adamw(bufs[slot, 0, 0], bufs[slot, 0, 1], bufs[slot, 0, 2],
                        g_ref[...], alpha_ref[0])
            for i in range(3):
                bufs[slot, 1, i] = new[i]

        _window_step(t, steps, reads, writes, compute)

    return _write_back(
        "muon_row_adamw", body, (alpha, base), g.reshape(-1, LANES),
        pl.BlockSpec((fold, LANES), lambda t, *_: (t, 0)), [store, m, v],
        pulled, pulled_len, steps, 3, interpret=interpret)


def muon_update(store, state, agg, starts, shapes, plan: MuonPlan, *,
                lr: float, mu: float, wd: float, b1: float, b2: float,
                eps: float, pulled_len: int = 0, interpret: bool = False,
                rest=None):
    """One step on the one shard that holds the bucket.  ``store`` is the
    flat f32 store, ``state`` as :func:`state_shapes` lays it out with the
    step slot last, ``agg`` the summed gradient as a row ``[1, total]``.
    Returns the new store and state and, with ``pulled_len`` (the bucket's
    ``total_len``, where ``plan.pulls``), the new parameters once more as
    a vector of their own: the pulled tree, which a cut of the store after
    the fact would read and write again.

    Every key's values are read from and written to the store where they
    lie, a chunk at a time: the barrier between two chunks keeps the next
    one's temporaries from being made before this one's are let go.  A
    key's gradient leaves the row through a kernel where the plan says it
    can (``plan.row_keys``), and its new values reach the store, and the
    pulled vector, through one (``plan.apply_keys``); ``interpret`` runs
    those kernels in the Pallas interpreter.  Any other key keeps XLA's
    cut on the way in and its ``dynamic_update_slice`` on the way out."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f32, bf16 = jnp.float32, jnp.bfloat16
    moms, (adam_m, adam_v, step_l) = state[:-3], state[-3:]
    keep = 1.0 - lr * wd
    assert plan.pulls or not pulled_len, "a key of the bucket keeps the cut"
    pulled = None

    def put(store, k: int, new_p):
        return lax.dynamic_update_slice(store, new_p, (int(starts[k]),))

    def end(k: int) -> int:
        # From the key's own shape: an owner's slots need not lie one
        # behind the other (:func:`owner_plan`).
        return int(starts[k]) + int(shapes[k][0]) * int(shapes[k][1])

    def key_values(vector, k: int):
        return lax.slice(vector, (int(starts[k]),), (end(k),))

    def key_grad(row, k: int, shape):
        # The fall-back, for a key on no lane border.  The chip lays
        # ``f32[1, n]`` out one sublane of eight to a tile, so XLA squeezes
        # the cut by a reduce at a fifth of the HBM rate and writes the key
        # out once more (18.4 ms of a 187 ms step on 568 M values,
        # PERF.md, PR 43); squeezing the whole row first is no better, a
        # pass over the gradient and a second copy of it.
        return lax.slice(row, (0, int(starts[k])),
                         (1, end(k))).reshape(shape)

    def momentum(mom, g):
        mom = mu * mom + g
        x = (g + mu * mom).astype(bf16)
        return mom, x

    def new_values(p, o, scale):
        return p * keep - scale * o

    def adamw(p, m, v, g, alpha):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        return p * keep - alpha * m / (jnp.sqrt(v) + eps), m, v

    row_keys = {int(k) for k in plan.row_keys}
    apply_keys = {int(k) for k in plan.apply_keys}

    def chunk_step(chunk, mom, store, pulled, agg):
        """One chunk's momentum pass, products and way out: its momentum,
        the store and the pulled vector after it."""
        with jax.named_scope("ps.update.muon.momentum"):
            if chunk.row:
                x = None
                for tall in sorted(set(chunk.tall)):
                    mom, x = row_momentum(momentum, agg, mom, chunk, starts,
                                          tall, x, interpret=interpret)
            else:
                grads = []
                for k, tall in zip(chunk.keys, chunk.tall):
                    g = key_grad(agg, k, tuple(int(d) for d in shapes[k]))
                    grads.append(g.T if tall else g)
                mom, x = momentum(mom, jnp.stack(grads))
        with jax.named_scope("ps.update.muon.ns"):
            o = newton_schulz(x)
        with jax.named_scope("ps.update.muon.apply"):
            scale = lr * RMS_MATCH * math.sqrt(chunk.n)
            taken = [i for i, k in enumerate(chunk.keys) if k in apply_keys]
            for tall in sorted({chunk.tall[i] for i in taken}):
                store, pulled = row_apply(
                    new_values, scale, o, store, pulled, chunk, starts, tall,
                    [i for i in taken if chunk.tall[i] == tall],
                    pulled_len=pulled_len, interpret=interpret)
            for i, (k, tall) in enumerate(zip(chunk.keys, chunk.tall)):
                if i not in taken:
                    o_k = (o[i].T if tall else o[i]).reshape(-1).astype(f32)
                    store = put(store, k, new_values(
                        key_values(store, k), o_k, scale))
        return mom, store, pulled

    new_moms = []
    for chunk, mom in zip(plan.chunks, moms):
        mom, store, pulled = chunk_step(chunk, mom, store, pulled, agg)
        new_moms.append(mom)
        store, agg = lax.optimization_barrier((store, agg))

    if rest is not None:
        # What is left of a shape class once every owner has as many: the
        # owners' lists differ, and each takes the branch that runs its
        # own (an owner does no products for a matrix it does not own).
        rest_moms = moms[len(plan.chunks):]

        def branch(pairs):
            def run(store, agg, *rest_moms):
                rest_moms = list(rest_moms)
                for at, chunk in pairs:
                    held = len(chunk.keys)
                    mom, store, _ = chunk_step(
                        chunk, rest_moms[at][:held], store, None, agg)
                    rest_moms[at] = (
                        mom if held == rest_moms[at].shape[0] else
                        lax.dynamic_update_slice(rest_moms[at], mom,
                                                 (0, 0, 0)))
                    store, agg = lax.optimization_barrier((store, agg))
                return (store, *rest_moms)

            return run

        branches, which = rest
        runs = [branch(pairs) for pairs in branches]
        if len(runs) == 1:
            store, *rest_moms = runs[0](store, agg, *rest_moms)
        else:
            store, *rest_moms = lax.switch(which, runs, store, agg,
                                           *rest_moms)
        new_moms += rest_moms

    with jax.named_scope("ps.update.muon.adamw"):
        t = step_l[0] + 1.0
        c1 = -jnp.expm1(t * math.log(b1))   # 1 - b1^t, as fused_update's
        c2 = -jnp.expm1(t * math.log(b2))
        alpha = (lr * jnp.sqrt(c2) / c1).astype(f32)
        for j, k in enumerate(plan.adamw_keys):
            k = int(k)
            lo = int(plan.adamw_starts[j])
            hi = int(plan.adamw_starts[j + 1])
            g = (row_vector(agg, int(starts[k]), hi - lo,
                            interpret=interpret) if k in row_keys
                 else key_grad(agg, k, (hi - lo,)))
            if k in apply_keys:
                store, adam_m, adam_v, pulled = row_adamw(
                    adamw, alpha, g, adam_m, adam_v, store, pulled,
                    int(starts[k]), lo, pulled_len=pulled_len,
                    interpret=interpret)
                continue
            new_p, m_k, v_k = adamw(
                key_values(store, k), lax.slice(adam_m, (lo,), (hi,)),
                lax.slice(adam_v, (lo,), (hi,)), g, alpha)
            adam_m = lax.dynamic_update_slice(adam_m, m_k, (lo,))
            adam_v = lax.dynamic_update_slice(adam_v, v_k, (lo,))
            store = put(store, k, new_p)

    new_state = (*new_moms, adam_m, adam_v, step_l + 1.0)
    if pulled is None:
        return store, new_state
    return store, new_state, pulled
