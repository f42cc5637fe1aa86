"""``ops/acc_update.py`` under the Pallas interpreter: the accumulator's
read-update-write against XLA's 1-D gather, add and scatter, bit for bit.
A CPU run proves values and which accumulators are written, never a speed;
that the kernel lowers for the chip inside the stateful push is
``test_compile_for_v5e.py``'s.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from pslite_tpu.ops import acc_update as acc_update_module  # noqa: E402
from pslite_tpu.ops.acc_update import acc_update  # noqa: E402

LANES = 128
TILE_ROWS = 8               # a tile of 1,024 accumulators: many tiles, cheap
TILE = TILE_ROWS * LANES
CHUNK = 16                  # ids a grid step
INF, NAN = np.inf, np.nan


def _xla(acc, rows, g2, n):
    """The three lines of ``_adagrad_sparse`` the kernel stands for."""
    R = acc.shape[0]
    valid = jnp.arange(rows.shape[0]) < n
    new_rows = acc[jnp.where(valid, rows, 0)] + g2
    return acc.at[jnp.where(valid, rows, R)].set(new_rows,
                                                 mode="drop"), new_rows


def _same(got, want):
    """Equal bit for bit, a NaN being any NaN."""
    got, want = np.asarray(got), np.asarray(want)
    nan = np.isnan(want)
    return (got.shape == want.shape and (np.isnan(got) == nan).all()
            and (got[~nan].view(np.int32) == want[~nan].view(np.int32)).all())


def _spread(R, n, seed):
    return np.sort(np.random.default_rng(seed).choice(R, n, replace=False))


# name: (accumulators, slots m, the touched rows: ascending and distinct,
#        accumulator entries set beforehand, g2 entries set beforehand)
CASES = {
    "no row touched": (4 * TILE, 40, [], {}, {}),
    "every slot a distinct row": (6 * TILE, 3 * CHUNK,
                                  _spread(6 * TILE, 3 * CHUNK, 1), {}, {}),
    # The last chunk is live and ends below the last tile: the walk stays
    # on it over the tiles that follow.
    "every slot a distinct row, all in the first tile": (
        4 * TILE, 3 * CHUNK, np.arange(3 * CHUNK), {}, {}),
    "every slot a distinct row, all in a middle tile": (
        5 * TILE, 3 * CHUNK, 2 * TILE + 7 + 3 * np.arange(3 * CHUNK), {}, {}),
    "every slot a distinct row, the padded last chunk partly live": (
        4 * TILE, 2 * CHUNK + 5, TILE - CHUNK + np.arange(2 * CHUNK + 5),
        {}, {}),
    "the first row and the last": (5 * TILE, 40, [0, 5 * TILE - 1], {}, {}),
    "several rows of one 128-lane chunk, and a chunk across a tile border": (
        4 * TILE, 40,
        [3, 4, 127, 128, 130, TILE - 2, TILE - 1, TILE, TILE + 1,
         2 * TILE + 64, 2 * TILE + 65], {}, {}),
    "a tile of no touched row between two that have some": (
        5 * TILE, 40, [5, 900, 3 * TILE + 1, 3 * TILE + 700, 4 * TILE + 9],
        {}, {}),
    "one tile holds more rows than a chunk": (
        3 * TILE, 5 * CHUNK,
        np.concatenate([[7], TILE + _spread(TILE, 3 * CHUNK + 5, 2),
                        [2 * TILE + 11]]), {}, {}),
    # 20,000,000 accumulators are 156,250 rows of 128: no whole tiles, and
    # no multiple of the 1,024 a 1-D array is tiled by on the chip.
    "accumulators of no whole tile, nor a multiple of 1,024": (
        3 * TILE + 5 * LANES, 50,
        np.concatenate([_spread(3 * TILE, 20, 3),
                        [3 * TILE, 3 * TILE + 5 * LANES - 1]]), {}, {}),
    "slots of no whole chunk": (4 * TILE, 3 * CHUNK + 5,
                                _spread(4 * TILE, 2 * CHUNK + 3, 4), {}, {}),
    "fewer accumulators than a tile": (3 * LANES, 20, [0, 200, 383], {}, {}),
    # Non-finite values stay in their own entry: a plain one-hot product
    # would spill ``0 * inf`` into every id of the chunk, or row of the tile.
    "inf, -inf and NaN in g2": (
        4 * TILE, 40, _spread(4 * TILE, 30, 5), {},
        {0: INF, 7: -INF, 16: NAN, 29: INF}),
    "inf, -inf and NaN in the accumulator, touched and not": (
        4 * TILE, 40, [1, 2, 3, 500, TILE + 3, 3 * TILE + 9],
        {2: INF, 4: NAN, 500: -INF, 501: INF, TILE + 3: NAN,
         2 * TILE + 1: -INF}, {}),
    "inf meets -inf, and NaN meets inf": (
        4 * TILE, 40, [10, 11, 12, 2000],
        {10: INF, 11: -INF, 12: NAN, 2000: INF},
        {0: -INF, 1: -INF, 2: INF, 3: INF}),
    "-0.0 and a NaN no row touches": (
        4 * TILE, 40, [10, 3000], {11: -0.0, 12: NAN, 2999: -0.0}, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_acc_update_is_xlas_gather_add_and_scatter_bit_for_bit(
        case, monkeypatch):
    """Every case with garbage past ``n`` in ``rows`` and ``g2`` (rows that
    are touched, rows out of range, a NaN and an ``inf``): nothing of it
    reaches the accumulator or the valid rows' results."""
    R, m, touched, acc_set, g2_set = CASES[case]
    monkeypatch.setattr(acc_update_module, "_TILE_ROWS", TILE_ROWS)
    monkeypatch.setattr(acc_update_module, "_CHUNK", CHUNK)
    touched = np.asarray(touched, np.int64)
    n = len(touched)
    assert n <= m and (np.diff(touched) > 0).all()
    rng = np.random.default_rng(R + m)
    acc = (rng.normal(size=R) ** 2).astype(np.float32)
    g2 = (rng.normal(size=m) ** 2).astype(np.float32)
    for at, value in acc_set.items():
        acc[at] = value
    for at, value in g2_set.items():
        g2[at] = value
    garbage = rng.integers(-5, R + 5, m - n)
    if n and m - n >= 4:
        garbage[:2] = touched[0], touched[-1]
        g2[n], g2[n + 1] = NAN, INF
    rows = np.concatenate([touched, garbage]).astype(np.int32)

    got_acc, got_new = jax.jit(
        lambda a, r, g, k: acc_update(a, r, g, k, interpret=True))(
            acc, rows, g2, np.int32(n))
    want_acc, want_new = jax.jit(_xla)(acc, rows, g2, np.int32(n))
    assert got_new.shape == (m,) and got_new.dtype == jnp.float32
    assert _same(got_acc, want_acc)
    assert _same(got_new[:n], want_new[:n])
    # Beside XLA's: what no id names is the buffer it was.
    untouched = np.setdiff1d(np.arange(R), touched)
    assert (np.asarray(got_acc)[untouched].view(np.int32)
            == acc[untouched].view(np.int32)).all()


def test_the_kernels_own_tile_and_chunk():
    """At the sizes the chip runs (256 rows a tile, 256 ids a chunk): a
    batch with duplicates dropped, over a ragged last tile."""
    rows, chunk = acc_update_module._TILE_ROWS, acc_update_module._CHUNK
    R, m = 3 * rows * LANES + 90 * LANES, 4 * chunk
    touched = _spread(R, 700, 6)
    rng = np.random.default_rng(7)
    acc = (rng.normal(size=R) ** 2).astype(np.float32)
    g2 = (rng.normal(size=m) ** 2).astype(np.float32)
    rows = np.concatenate([touched, np.full(m - 700, R)]).astype(np.int32)
    got_acc, got_new = jax.jit(
        lambda a, r, g, k: acc_update(a, r, g, k, interpret=True))(
            acc, rows, g2, np.int32(700))
    want_acc, want_new = jax.jit(_xla)(acc, rows, g2, np.int32(700))
    assert _same(got_acc, want_acc) and _same(got_new[:700], want_new[:700])
    assert acc_update_module.steps(R, m) == 4 + 4 - 1


# (accumulators, slots, the live ids) at the sizes the chip runs, where the
# interpreter would take minutes: the walk alone.
WALKS = {
    "the cell's size, every slot a distinct row of the lowest": (
        20_000_000, 131_072, np.arange(131_072)),
    "the cell's size, every slot distinct, a run in the middle": (
        20_000_000, 131_072, 10_000_000 + 3 * np.arange(131_072)),
    "the cell's size, every slot distinct and spread": (
        20_000_000, 131_072, _spread(20_000_000, 131_072, 8)),
    "the cell's size, half the slots dropped, ending low": (
        20_000_000, 131_072, _spread(9_000_000, 65_000, 9)),
    "the cell's size, one live id": (20_000_000, 131_072, [12_345_678]),
    "the cell's size, no live id": (20_000_000, 131_072, []),
    "the smoke's size, every slot a distinct row of the lowest": (
        1 << 20, 4_096, np.arange(4_096)),
}


@pytest.mark.parametrize("case", list(WALKS))
def test_the_walk_names_no_chunk_or_tile_past_the_arrays_and_meets_every_id(
        case):
    """The schedule the kernel's index maps read, at the kernel's own tile
    and chunk: every step's chunk and tile lie inside the arrays, a step
    moves on by one tile or one chunk, and every live id has a step that
    holds its chunk over its tile, within the steps taken."""
    R, m, touched = WALKS[case]
    K = acc_update_module._CHUNK
    tile = acc_update_module._tile_rows(R) * LANES
    T, C = -(-R // tile), m // K
    touched = np.asarray(touched, np.int64)
    n = len(touched)
    rows = np.concatenate([touched, np.full(m - n, R)]).astype(np.int32)
    ids, first, last, chunk, walk = map(np.asarray, jax.jit(
        lambda r, k: acc_update_module._walk(r, k, K, T, tile))(
            rows, np.full(1, n, np.int32)))
    assert chunk.shape == (T + C - 1,) and 1 <= walk <= len(chunk)
    assert walk == acc_update_module.steps(R, m) or n <= m - K
    tiles = np.arange(len(chunk)) - chunk
    assert chunk.min() == 0 and chunk.max() <= C - 1
    assert tiles.min() == 0 and tiles.max() <= T - 1
    moved = np.diff(chunk)
    assert ((moved == 0) | (moved == 1)).all()      # the tile moves else
    held = set(zip(chunk[:walk].tolist(), tiles[:walk].tolist()))
    want = set(zip((np.arange(n) // K).tolist(), (touched // tile).tolist()))
    assert want <= held
    live = ids != np.iinfo(np.int32).max
    assert live.sum() == n and (ids[live] == touched).all()
    chunks = -(-n // K)
    assert (first[:chunks] == touched[::K]).all()
    assert (last[:chunks] == touched[
        np.minimum(np.arange(1, chunks + 1) * K, n) - 1]).all()
    assert (last[chunks:] < 0).all()


# Tables whose rows are no multiple of 128 (MLPerf DLRM-DCNv2's own counts
# among them), each as the sparse engine keeps its accumulator: the logical
# rows first, zeros up to the next whole 128.
LOGICAL = [3, 63, 128, 1_003, 20_265, 39_060]
TOUCHED = {
    "no id": lambda rows: [],
    "every id": lambda rows: np.arange(rows),
    "the last logical id alone": lambda rows: [rows - 1],
    "a few ids, the first and the last among them": lambda rows: np.unique(
        np.concatenate([[0, rows - 1], _spread(rows, min(rows, 40), rows)])),
}


@pytest.mark.parametrize("which", list(TOUCHED))
@pytest.mark.parametrize("logical", LOGICAL)
def test_logical_accumulators_kept_in_whole_128s(logical, which):
    """The one property against numpy, at the kernel's own tile and chunk:
    ``acc[rows[i]] += g2[i]`` for ``i < n`` and nothing else, with past ``n``
    the sentinel a combine leaves (the LOGICAL row count, which lies inside
    the kept array), a NaN and an ``inf``; the tail no id names stays zero
    bit for bit."""
    kept = -(-logical // LANES) * LANES
    touched = np.asarray(TOUCHED[which](logical), np.int64)
    n = len(touched)
    m = -(-max(n, 1) // 64) * 64 + 64         # slots past ``n``, always
    rng = np.random.default_rng(logical + n)
    acc = np.zeros(kept, np.float32)
    acc[:logical] = rng.normal(size=logical) ** 2
    g2 = (rng.normal(size=m) ** 2).astype(np.float32)
    g2[n], g2[n + 1] = NAN, INF
    rows = np.concatenate([touched, np.full(m - n, logical)]).astype(np.int32)
    want = acc.copy()
    want[touched] += g2[:n]

    got_acc, got_new = jax.jit(
        lambda a, r, g, k: acc_update(a, r, g, k, interpret=True))(
            acc, rows, g2, np.int32(n))
    got_acc, got_new = np.asarray(got_acc), np.asarray(got_new)
    assert got_acc.shape == (kept,) and got_new.shape == (m,)
    assert (got_acc.view(np.int32) == want.view(np.int32)).all()
    assert (got_new[:n].view(np.int32) == want[touched].view(np.int32)).all()
    assert not got_acc[logical:].view(np.int32).any()
    # XLA's pair over the same kept array drops the sentinel too.
    pair_acc, _ = jax.jit(_xla)(acc, rows, g2, np.int32(n))
    assert (np.asarray(pair_acc).view(np.int32) == want.view(np.int32)).all()


def test_an_accumulator_of_no_whole_128s_is_padded_and_cut():
    """A caller outside the engine: any length goes through (a copy each
    way), and ``steps`` reckons it by the rows of 128 it takes."""
    acc = np.arange(1, 62, dtype=np.float32)
    rows = np.array([0, 5, 60, 61, 61, 61, 61, 61], np.int32)
    g2 = np.full(8, 0.5, np.float32)
    got_acc, got_new = acc_update(jnp.asarray(acc), rows, g2, np.int32(3),
                                  interpret=True)
    want = acc.copy()
    want[[0, 5, 60]] += 0.5
    assert got_acc.shape == (61,) and (np.asarray(got_acc) == want).all()
    assert (np.asarray(got_new)[:3] == want[[0, 5, 60]]).all()
    assert acc_update_module.steps(61, 8) == acc_update_module.steps(128, 8)
