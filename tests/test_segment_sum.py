"""``ops/segment_sum.py`` under the Pallas interpreter: the combine's segment
sum over sorted rows against numpy in float64.  A CPU run proves values and
which rows are written, never a speed; that the kernel lowers for the chip
inside both pushes is ``test_compile_for_v5e.py``'s.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from benchmark.zipf import zipf_rows  # noqa: E402
from pslite_tpu.ops import segment_sum as segment_sum_module  # noqa: E402
from pslite_tpu.ops.segment_sum import segment_sum  # noqa: E402
from pslite_tpu.parallel import sparse  # noqa: E402

SMALL = 16      # slots a grid step where a case wants many steps of few rows
REAL = segment_sum_module._BLOCK
WIDTH = 128     # the one width the kernel serves (another: XLA's scatter-add)


def _segments(rows):
    """``seg`` of sorted ``rows`` as ``_combine_rows`` numbers them."""
    rows = np.asarray(rows)
    first = np.concatenate([[True], rows[1:] != rows[:-1]])
    return (np.cumsum(first) - 1).astype(np.int32)


def _runs(*lengths):
    return np.repeat(np.arange(len(lengths)), lengths)


CASES = {
    # name: (slots a grid step, sorted rows)
    "all slots distinct": (SMALL, np.arange(80)),
    "all slots equal": (SMALL, np.zeros(80, int)),
    # The hottest row's run spans several blocks, others lie inside them:
    # the cells' shape, from their own generator at a small size.
    "zipf, small blocks": (SMALL, np.sort(zipf_rows(5, (640,), 300, 0.99))),
    "zipf, the kernel's own block": (
        REAL, np.sort(zipf_rows(6, (8 * REAL,), 2000, 0.99))),
    "runs ending exactly on block borders": (
        SMALL, _runs(16, 16, 32, 5, 11, 1, 15, 48)),
    "every block a run of its own": (SMALL, _runs(*[16] * 6)),
    # What another shard owns sorts last, into one long run.
    "a sentinel tail": (SMALL, np.concatenate([np.arange(0, 60, 2),
                                               np.full(70, 10**6)])),
    "m is no multiple of the block": (SMALL, _runs(3, 1, 20, 1, 1, 17, 2)),
    "m below one block": (REAL, _runs(2, 1, 4)),
    "distinct rows pass a window's two blocks": (
        SMALL, np.concatenate([np.zeros(40, int), np.arange(1, 57)])),
}


@pytest.mark.parametrize("case", list(CASES))
def test_segment_sums_are_numpys_add_at_in_float64(case, monkeypatch):
    block, rows = CASES[case]
    monkeypatch.setattr(segment_sum_module, "_BLOCK", block)
    seg = _segments(rows)
    m, n = len(seg), int(seg[-1]) + 1
    rng = np.random.default_rng(m)
    g = rng.normal(size=(m, WIDTH)).astype(np.float32)
    got = np.asarray(jax.jit(
        lambda s, x: segment_sum(s, x, interpret=True))(seg, g))
    assert got.shape == (m, WIDTH) and got.dtype == np.float32
    want = np.zeros((m, WIDTH), np.float64)
    np.add.at(want, seg, g.astype(np.float64))
    # f32 sums in another order: an error of a few roundings of the run's
    # largest partial sum, whatever the run's length.
    longest = np.bincount(seg).max()
    tol = 2.0**-23 * np.sqrt(longest) * 8
    scale = np.maximum(np.abs(want[:n]).max(axis=1, keepdims=True), 1.0)
    assert (np.abs(got[:n] - want[:n]) / scale).max() < tol
    if longest == 1:
        assert (got[:n] == g).all()             # a row alone is itself


@pytest.mark.parametrize("block", [SMALL, REAL])
def test_a_non_finite_gradient_stays_in_its_own_row(block, monkeypatch):
    """``inf`` and NaN end where IEEE addition leaves them, as under XLA's
    scatter-add: in their own row and lane (a plain one-hot product would
    spill ``0 * inf`` into every row of the block), ``inf`` and ``-inf`` of
    one run as NaN, and every other sum as if they were not there."""
    monkeypatch.setattr(segment_sum_module, "_BLOCK", block)
    rows = np.sort(np.concatenate([
        np.zeros(block + 7, int), zipf_rows(9, (4 * block,), 50 * block, 0.99),
        [10**8, 10**8 + 1]]))
    seg = _segments(rows)
    m, n = len(seg), int(seg[-1]) + 1
    g = np.random.default_rng(3).normal(size=(m, WIDTH)).astype(np.float32)
    hot = np.flatnonzero(seg == np.bincount(seg).argmax())
    assert len(hot) > block                     # it crosses a block border
    alone = np.flatnonzero(np.bincount(seg)[seg] == 1)
    g[hot[1], 5] = np.inf                       # in a run of many blocks
    g[hot[-1], 5] = np.inf                      # and again at its far end
    g[hot[2], 6], g[hot[-2], 6] = np.inf, -np.inf       # NaN by addition
    g[alone[0], 7] = np.nan                     # rows of one slot
    g[alone[-1], 9] = -np.inf
    got = np.asarray(jax.jit(
        lambda s, x: segment_sum(s, x, interpret=True))(seg, g))[:n]
    scatter = np.asarray(jnp.zeros((m, WIDTH)).at[seg].add(g))[:n]
    want = np.zeros((m, WIDTH), np.float64)
    with np.errstate(invalid="ignore"):
        np.add.at(want, seg, g.astype(np.float64))
    want = want[:n]
    bad = ~np.isfinite(want)
    assert sorted(map(tuple, np.argwhere(bad))) == sorted(
        [(seg[hot[0]], 5), (seg[hot[0]], 6), (seg[alone[0]], 7),
         (seg[alone[-1]], 9)])
    assert (np.isnan(got) == np.isnan(want)).all()
    assert (np.isnan(scatter) == np.isnan(want)).all()
    assert (got[bad & ~np.isnan(want)] == want[bad & ~np.isnan(want)]).all()
    np.testing.assert_allclose(got[~bad], want[~bad], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["zipf duplicates", "all distinct",
                                  "unowned slots sort last"])
def test_combine_rows_through_the_kernel_is_combine_rows_through_xla(
        case, monkeypatch):
    """``_combine_rows`` with the CPU named among the segment sum's
    platforms (interpreted) against the same call through XLA's
    scatter-add: the distinct rows and ``valid`` are equal, and every valid
    row's sum differs by f32 rounding of the run's length at most."""
    m, R = 3 * REAL + 40, 500
    rng = np.random.default_rng(len(case))
    local = {"zipf duplicates": lambda: zipf_rows(11, (m,), R, 0.99),
             "all distinct": lambda: rng.permutation(m) % (4 * R),
             "unowned slots sort last": lambda: np.where(
                 rng.random(m) < 0.3, R, zipf_rows(12, (m,), R, 0.99)),
             }[case]().astype(np.int32)
    if case == "all distinct":
        R = 4 * R
    g = rng.normal(size=(m, WIDTH)).astype(np.float32)
    combine = lambda: [np.asarray(x) for x in jax.jit(
        lambda l, x: sparse._combine_rows(l, x, R))(local, g)]
    G_xla, rows_xla, valid_xla = combine()
    traced = []
    real = segment_sum_module.segment_sum
    monkeypatch.setattr(
        segment_sum_module, "segment_sum",
        lambda seg, sg, **kw: traced.append(sg.shape) or real(seg, sg, **kw))
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)
    G, rows, valid = combine()
    assert set(traced) == {(m, WIDTH)}          # in the program
    assert (rows == rows_xla).all() and (valid == valid_xla).all()
    assert valid.sum() == len(np.unique(local[local < R]))
    copies = np.bincount(local, minlength=R + 1)[rows[valid]]
    err = np.abs(G[valid] - G_xla[valid]).max(axis=1)
    scale = np.maximum(np.abs(G_xla[valid]).max(axis=1), 1.0)
    assert (err / scale <= 2.0**-23 * 4 * np.sqrt(copies)).all()
    assert (G[valid][copies == 1] == G_xla[valid][copies == 1]).all()


def test_other_widths_and_dtypes_keep_xlas_scatter_add(monkeypatch):
    """The rule is the rows' own shape: 128 f32 lanes take the kernel, a
    64-wide (a lane-packed table's first combine under ``row_adagrad``),
    a 256-wide and a bf16 batch keep the line as it was, bit for bit."""
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)
    called = []
    monkeypatch.setattr(
        segment_sum_module, "segment_sum",
        lambda seg, sg, **kw: called.append(sg.shape) or jnp.zeros_like(sg))
    seg = _segments(np.sort(zipf_rows(2, (96,), 30, 0.99)))
    for width, dtype in ((64, jnp.float32), (256, jnp.float32),
                         (128, jnp.bfloat16)):
        g = jnp.asarray(np.random.default_rng(width).normal(
            size=(96, width)), dtype)
        got = jax.jit(sparse._segment_sums)(seg, g)
        assert (got == jnp.zeros_like(g).at[seg].add(g)).all()
    assert not called
    jax.jit(sparse._segment_sums)(seg, jnp.zeros((96, 128), jnp.float32))
    assert set(called) == {(96, 128)}
