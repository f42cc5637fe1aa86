"""``ops/row_add.py`` under the Pallas interpreter, on small tables: the
table's write by distinct row against numpy.  A CPU run proves values and
which rows are visited, never a speed; that the kernel lowers for the chip,
in place, is ``test_aot_ring.py``'s.
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
