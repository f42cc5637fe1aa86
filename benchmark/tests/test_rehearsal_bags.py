"""The cell kind ``dlrm-dcnv2-multihot.bags`` brings, end to end at a tiny
size without the chip: ``cells/tiny-sparse-bags.json`` under
``cells/tiny-zipf-bags.json`` (named by no entry of ``workloads``), six
128-wide tables of 3 to 4,001 rows with bags of 8, 1, 3, 2, 27 and 5 ids
under ``drivers/sparse_bags_pull_push.py``: a step is one
``KVWorker.pull_sparse_group(pool="sum")`` and one ``push_sparse_group(handle,
pool="sum")`` under ``row_adagrad``.  On the CPU a push's write is XLA's
scatter; with the CPU named among the platforms of the three sparse kernels
(interpreted) it is what the chip runs.  Both read ``correct``; the bf16
control fails every limit; a pool that leaves a slot out and a push that
brings a bag's gradient to its first row alone come out ``correct: false``;
a program whose calls take no ``pool`` ends where the driver is loaded, with
a plain message.
"""

import time

import numpy as np
import pytest

import harness
import tiny

tiny.KINDS["bags"] = ("tiny-sparse-bags.json", "tiny-zipf-bags.json")
BAGS, HS, DIM = 64, [8, 1, 3, 2, 27, 5], 128
TABLES = len(HS)


def _run(seed=7, seconds=0.3, **kw):
    return harness.run_cell(tiny.cell("bags"), seed, seconds, False,
                            time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture()
def engines(monkeypatch):
    """Every ``SparseEngine`` a run pushes a group through, to read its
    counters after the run has shut its cluster down; a one-table call
    would be another path than the cell's."""
    from pslite_tpu.parallel.sparse import SparseEngine

    seen, real = [], SparseEngine.push_group

    def push_group(self, *a, **kw):
        if self not in seen:
            seen.append(self)
        return real(self, *a, **kw)

    def one_table(self, *a, **kw):
        raise AssertionError("a one-table op in the many-tables cell")

    monkeypatch.setattr(SparseEngine, "push_group", push_group)
    monkeypatch.setattr(SparseEngine, "push", one_table)
    monkeypatch.setattr(SparseEngine, "pull", one_table)
    return seen


@pytest.fixture()
def kernels_on_cpu(monkeypatch):
    from pslite_tpu.parallel import sparse

    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)
    monkeypatch.setitem(sparse._ACC_UPDATE_INTERPRET, "cpu", True)


def _check(ok, result, out, engines, by_kernel):
    assert ok and result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert "0 compilations in the window" in out
    for number in ("engine_byte_counters_gap", "hot_bag_copies_spread",
                   "hot_bag_copies_missing", "nonfinite_in_pulled_rows"):
        assert f"compare {number}: 0.0" in out, number
    for number in ("first3_err", "final_err", "acc_err"):
        assert f"compare {number}: " in out, number
    (eng,) = engines
    # Every step pushed once, as ONE op over the six tables under the handle;
    # a row a BAG crosses the API, whatever the bag holds.
    pushes = eng.push_bytes // (4 * TABLES * BAGS * DIM * 4)
    assert pushes >= result["attempted"] + 4
    assert eng.push_bytes == pushes * 4 * TABLES * BAGS * DIM * 4
    assert eng.stateful_pushes == pushes and eng.packed_pushes == 0
    assert eng.row_kernel_pushes == (pushes if by_kernel else 0)
    assert eng.segsum_kernel_pushes == (pushes if by_kernel else 0)
    assert eng.acc_kernel_pushes == (pushes if by_kernel else 0)
    tiny.check_metrics(result, "end_to_end",
                       {"goodput", "step_p50", "step_p95", "setup_s"})


def test_the_bags_cell_end_to_end_on_four_devices(engines, capsys):
    ok, result = _run(seed=2**31 + 5)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=False)


def test_the_bags_cell_through_the_kernels_as_the_chip_runs_it(
        engines, kernels_on_cpu, capsys):
    ok, result = _run(seed=2**31 + 6, seconds=0.1)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=True)


def test_the_pool_counter_reads_the_cells_bags_on_a_cpu_run(capsys):
    """``--trace 1`` on the CPU: no device plane, so the four trace readers
    are silent; the program's counter reads 46 / 6 lookups a bag where the
    window holds whole slots of the clock, and two ops a step."""
    ok, result = harness.run_cell(
        tiny.cell("bags"), 13, 4.6, True, time.perf_counter(),
        require_tpu=False)
    assert ok and result["correct"]
    got = tiny.check_metrics(result, "per_layer",
                             {"bag_lookups_per_bag", "sparse_tables_per_op",
                              "ops_per_step", "compiles_in_window"})
    assert not {"bag_pull_ms", "bag_pull_roofline", "bag_combine_ms",
                "bag_write_ms"} & got
    assert result["metrics"]["bag_lookups_per_bag"]["value"] \
        == pytest.approx(sum(HS) / TABLES)
    assert result["metrics"]["sparse_tables_per_op"]["value"] == 6.0
    # (The window cuts its border steps by time: 1.9993 .. 2.0004.)
    assert result["metrics"]["ops_per_step"]["value"] == pytest.approx(
        2.0, abs=0.01)
    assert result["metrics"]["compiles_in_window"]["value"] == 0.0


def test_the_control_fails_every_limit_of_the_bags_cell(capsys):
    _run(seed=11, control="bf16")
    out = capsys.readouterr().out
    for number in ("first3_err", "final_err", "acc_err"):
        line = next(l for l in out.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in out.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def _not_correct(capsys, number):
    ok, result = _run(seed=5, seconds=0.1)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    lines = [l for l in out.splitlines()
             if l.startswith("compare ") and "NOT CORRECT" in l]
    assert any(l.startswith(f"compare {number}") for l in lines), out


def test_a_pool_that_leaves_a_slot_out_is_not_correct(monkeypatch, capsys):
    """The sum over all but a bag's last slot (the pool adds ``h`` slabs
    ``[B, d]``, a slot each): every pooled row of a table whose bags hold
    more than one id is short of a row."""
    import jax.numpy as jnp

    real = jnp.sum

    def short(x, *a, **kw):
        if x.ndim == 3 and kw.get("axis") == 0 and x.shape[0] > 1:
            x = x[:-1]
        return real(x, *a, **kw)

    monkeypatch.setattr(jnp, "sum", short)
    _not_correct(capsys, "first3_err")


def test_a_gradient_brought_to_a_bags_first_row_alone_is_not_correct(
        monkeypatch, capsys):
    """A push that brings a bag's gradient, once a slot, to the row of the
    bag's FIRST id: the other slots' rows miss it, the first has too much."""
    import jax.numpy as jnp

    from pslite_tpu.parallel import sparse

    real = sparse._push_slots

    def first_row(axis, S, R, idx_l, *rest):
        if idx_l.ndim == 3:
            idx_l = jnp.broadcast_to(idx_l[:, :, :1], idx_l.shape)
        return real(axis, S, R, idx_l, *rest)

    monkeypatch.setattr(sparse, "_push_slots", first_row)
    _not_correct(capsys, "final_err")


def test_the_bags_generator_is_a_fixed_function_of_the_first_id():
    bag_ids = harness._load(tiny.cell("bags").search, "drivers",
                            "sparse_bags_pull_push").bag_ids
    first = np.array([[0, 5, 0, 7], [5, 0, 3, 3]])
    bags = bag_ids(2**31 + 9, 4, first, 27, 1600)
    assert bags.shape == (2, 4, 27) and bags.dtype == np.int32
    assert (bags[..., 0] == first).all()
    assert (0 <= bags).all() and (bags < 1600).all()
    # The same first id brings the same bag, wherever it comes again.
    assert (bags[0, 0] == bags[0, 2]).all() and (bags[0, 0] == bags[1, 1]).all()
    assert (bags[0, 1] == bags[1, 0]).all() and (bags[1, 2] == bags[1, 3]).all()
    assert (bags[0, 0] != bags[0, 1]).any()
    # Another table, another seed: other bags; a bag of one id is its id.
    assert (bag_ids(2**31 + 9, 5, first, 27, 1600) != bags).any()
    assert (bag_ids(2**31 + 8, 4, first, 27, 1600) != bags).any()
    assert (bag_ids(1, 0, first, 1, 10) == first[..., None]).all()
    # Uniform over the table's rows: 40,000 draws over 10 rows.
    many = bag_ids(3, 1, np.arange(400), 101, 10)[:, 1:]
    counts = np.bincount(many.reshape(-1), minlength=10)
    assert counts.min() > 3600 and counts.max() < 4400
    # A table smaller than a bag: ids repeat inside it.
    small = bag_ids(3, 0, np.array([0, 1, 2]), 8, 3)
    assert all(len(set(b)) < 8 for b in small)


def test_a_program_without_pool_ends_where_the_driver_is_loaded(monkeypatch):
    """The parent of the PR that brought ``pool``: the driver's file says
    what is missing as it is loaded, before anything boots."""
    import pslite_tpu as ps

    def pull_sparse_group(self, names, indices_list, outs=None,
                          callback=None):
        raise AssertionError("called")

    monkeypatch.setattr(ps.KVWorker, "pull_sparse_group", pull_sparse_group)
    path = harness._find(tiny.cell("bags").search, "drivers",
                         "sparse_bags_pull_push", (".py",))
    monkeypatch.delitem(harness._modules, path, raising=False)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="takes no pool"):
        _run(seed=3)
    assert time.perf_counter() - t0 < 30
    monkeypatch.delitem(harness._modules, path, raising=False)
