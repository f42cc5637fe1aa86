"""``ops/row_add.py`` under the Pallas interpreter, on small tables: the
table's write by distinct row against numpy.  A CPU run proves values and
which rows are visited, never a speed; that the kernel lowers for the chip,
in place, is ``test_compile_for_v5e.py``'s.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from pslite_tpu.ops import row_add as row_add_module  # noqa: E402
from pslite_tpu.ops.row_add import row_add  # noqa: E402

BLOCK = 16      # row ids a grid step here, so that small batches span steps
ROWS = 200
WIDTH = 128     # the one width the kernel serves (a wider row: XLA's scatter)


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(row_add_module, "_BLOCK_ROWS", BLOCK)


@pytest.mark.parametrize("m, n", [
    (40, 0),        # nothing to do: the table comes back as it was
    (40, 1),
    (40, 5),        # a partial block, less than one trip of the loops
    (40, 8),        # exactly one trip
    (40, 13),       # a trip and a remainder
    (40, 16),       # exactly one block
    (40, 24),       # a block and a whole trip of the next
    (40, 37),       # several blocks and a remainder
    (40, 40),       # every slot, and a batch that is no whole block
    (48, 48),       # every slot of whole blocks
    (5, 3),         # a batch smaller than a block
])
def test_the_first_n_rows_are_added_and_no_other_is_touched(m, n):
    rng = np.random.default_rng(m * 1000 + n)
    store = rng.normal(size=(ROWS, WIDTH)).astype(np.float32)
    # Ascending and unique in the first n entries, the table's first and
    # last row among them; past n, ids in range that must not be visited.
    rows = np.sort(rng.choice(np.arange(1, ROWS - 1), size=m, replace=False))
    if n >= 2:
        rows[0], rows[n - 1] = 0, ROWS - 1
    rows[n:] = rng.integers(0, ROWS, size=m - n)
    delta = rng.normal(size=(m, WIDTH)).astype(np.float32)
    delta[n:] = np.nan
    got = np.asarray(jax.jit(
        lambda s, r, d, k: row_add(s, r, d, k, interpret=True)
    )(store, rows.astype(np.int32), delta, jnp.int32(n)))
    want = store.copy()
    want[rows[:n]] += delta[:n]                 # one f32 add a touched row
    assert np.isfinite(got).all()               # no slot past n was visited
    touched = np.zeros(ROWS, bool)
    touched[rows[:n]] = True
    assert (got[~touched] == store[~touched]).all()     # bit-unchanged
    assert (got == want).all()


# -- a lane-packed table: pack logical rows to one 128-lane physical row ------

LOGICAL = 208   # a whole number of physical rows at every packing below


def _packed_case(case, pack, rng):
    """Logical rows of a batch (the sentinel ``LOGICAL`` where another
    shard owns the slot) that the case is about."""
    if case == "row-mates in one push":
        # Every row of physical rows 0 and 3, each twice, shuffled.
        rows = np.r_[0:pack, 3 * pack:4 * pack].repeat(2)
    elif case == "an untouched row-mate":
        # One row of each physical row: its mates keep their bits.
        rows = np.arange(0, LOGICAL, pack) + rng.integers(0, pack,
                                                          LOGICAL // pack)
    else:   # duplicates and unowned slots
        rows = rng.integers(0, LOGICAL, 96)
        rows[::7] = LOGICAL
        rows[1::7] = 5
    return rng.permutation(rows).astype(np.int32)


@pytest.mark.parametrize("case", ["row-mates in one push",
                                  "an untouched row-mate",
                                  "duplicates and unowned slots"])
@pytest.mark.parametrize("dim", [8, 16, 64])
def test_a_lane_packed_tables_rows_arrive_one_entry_a_physical_row(dim, case):
    """``parallel/sparse.py`` ``_combine_phys_rows`` then the kernel, as
    both pushes of a lane-packed table call them: every gradient in its
    slot's lanes, duplicates and row-mates merged, so the kernel is given
    each physical row once (two entries of one row in a block would
    overwrite each other) and a row nothing touches keeps its bits."""
    from pslite_tpu.parallel.sparse import _combine_phys_rows

    pack = WIDTH // dim
    rng = np.random.default_rng(dim + len(case))
    local = _packed_case(case, pack, rng)
    table = rng.normal(size=(LOGICAL, dim)).astype(np.float32)
    g = rng.normal(size=(len(local), dim)).astype(np.float32)

    def push(store, local, g):
        G_seg, row_seg, valid = _combine_phys_rows(local, g, LOGICAL, pack)
        return row_seg, valid, row_add(store, row_seg, G_seg, valid.sum(),
                                       interpret=True)

    row_seg, valid, got = map(np.asarray, jax.jit(push)(
        table.reshape(LOGICAL // pack, WIDTH), local, g))
    owned = local < LOGICAL
    phys = np.unique(local[owned] // pack)
    assert (row_seg[:len(phys)] == phys).all() and valid.sum() == len(phys)
    assert (row_seg[len(phys):] == LOGICAL // pack).all()
    want = table.astype(np.float64)
    np.add.at(want, local[owned], g[owned].astype(np.float64))
    got = got.reshape(LOGICAL, dim)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    quiet = np.setdiff1d(np.arange(LOGICAL), local)
    assert len(quiet) and (got[quiet] == table[quiet]).all()
    if case == "an untouched row-mate":
        assert len(quiet) == LOGICAL - LOGICAL // pack


def test_a_row_mate_is_added_zero_whatever_the_gradient_holds():
    """Placement is a select: an infinite gradient reaches its own row
    and leaves the row beside it in the physical row as it was."""
    from pslite_tpu.parallel.sparse import _place_rows

    g = np.array([[np.inf] * 64, [1.0] * 64], np.float32)
    placed, phys = map(np.asarray, _place_rows(
        jnp.asarray(g), jnp.array([6, 9], jnp.int32), 2))
    assert (phys == [3, 4]).all()
    assert np.isinf(placed[0, :64]).all() and (placed[0, 64:] == 0).all()
    assert (placed[1, :64] == 0).all() and (placed[1, 64:] == 1).all()
