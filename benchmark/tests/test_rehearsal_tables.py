"""The cell kind ``dlrm-terabyte-26tables.zipf`` brings, end to end at a tiny
size without the chip: ``cells/tiny-sparse-tables.json`` under
``cells/tiny-zipf-tables.json`` (named by no entry of ``workloads``), six
64-wide tables of 3 to 4,001 rows, each kept two rows to a 128-lane physical
row, under ``drivers/sparse_tables_pull_push.py``: a step is one
``KVWorker.pull_sparse_group`` and one ``push_sparse_group``.  On the CPU a
push is XLA's scatter; with the CPU named among the platforms of
``ops/row_add.py`` and ``ops/segment_sum.py`` (interpreted) it is what the
chip runs.  Both read ``correct``; the bf16 control fails; a merge broken
underneath comes out ``correct: false``; a program without the grouped calls
ends in the driver's ``__init__`` with a plain message.
"""

import time

import pytest

import harness
import tiny

tiny.KINDS["tables"] = ("tiny-sparse-tables.json", "tiny-zipf-tables.json")
TABLES, LOOKUPS, DIM = 6, 64, 64


def _run(seed=7, seconds=0.3, **kw):
    return harness.run_cell(tiny.cell("tables"), seed, seconds, False,
                            time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture()
def engines(monkeypatch):
    """Every ``SparseEngine`` a run pushes a group through, to read its
    counters after the run has shut its cluster down; a one-table push
    would be another path than the cell's."""
    from pslite_tpu.parallel.sparse import SparseEngine

    seen, real = [], SparseEngine.push_group

    def push_group(self, *a, **kw):
        if self not in seen:
            seen.append(self)
        return real(self, *a, **kw)

    def push(self, *a, **kw):
        raise AssertionError("a one-table push in the many-tables cell")

    monkeypatch.setattr(SparseEngine, "push_group", push_group)
    monkeypatch.setattr(SparseEngine, "push", push)
    return seen


@pytest.fixture()
def kernels_on_cpu(monkeypatch):
    from pslite_tpu.parallel import sparse

    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)
    monkeypatch.setitem(sparse._SEGMENT_SUM_INTERPRET, "cpu", True)


def _check(ok, result, out, engines, by_kernel):
    assert ok and result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert "0 compilations in the window" in out
    for number in ("engine_byte_counters_gap", "hot_row_copies_spread",
                   "hot_row_copies_missing", "nonfinite_in_pulled_rows"):
        assert f"compare {number}: 0.0" in out, number
    (eng,) = engines
    assert [eng.table(f"emb{i:02d}").pack for i in range(TABLES)] \
        == [2] * TABLES
    # Every step pushed once, as ONE op over the six tables, into
    # lane-packed tables, and by the kernels exactly where the program is
    # lowered for their platform.
    pushes = eng.push_bytes // (4 * TABLES * LOOKUPS * DIM * 4)
    assert pushes >= result["attempted"] + 4
    assert eng.packed_pushes == pushes
    assert eng.row_kernel_pushes == (pushes if by_kernel else 0)
    assert eng.segsum_kernel_pushes == (pushes if by_kernel else 0)
    assert eng.stateful_pushes == 0
    tiny.check_metrics(result, "end_to_end",
                       {"goodput", "step_p50", "step_p95", "setup_s"})


def test_the_many_tables_cell_end_to_end_on_four_devices(engines, capsys):
    ok, result = _run(seed=2**31 + 5)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=False)


def test_the_many_tables_cell_through_the_kernels_as_the_chip_runs_it(
        engines, kernels_on_cpu, capsys):
    ok, result = _run(seed=2**31 + 6, seconds=0.1)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=True)


def test_the_group_counter_reads_the_cells_tables_on_a_cpu_run(capsys):
    """``--trace 1`` on the CPU: no device plane, so the three trace
    readers are silent; the program's counter reads 6.0 tables an op where
    the window holds whole slots of the clock, and two ops a step."""
    ok, result = harness.run_cell(
        tiny.cell("tables"), 13, 4.6, True, time.perf_counter(),
        require_tpu=False)
    assert ok and result["correct"]
    got = tiny.check_metrics(result, "per_layer",
                             {"sparse_tables_per_op", "ops_per_step",
                              "compiles_in_window"})
    assert not {"tables_combine_ms", "tables_write_ms",
                "sparse_device_ops_per_step"} & got
    assert result["metrics"]["sparse_tables_per_op"]["value"] == 6.0
    assert result["metrics"]["ops_per_step"]["value"] == pytest.approx(2.0)
    assert result["metrics"]["compiles_in_window"]["value"] == 0.0


def test_the_control_fails_on_the_many_tables_cell(capsys):
    _run(seed=11, control="bf16")
    out = capsys.readouterr().out
    for number in ("first3_err", "final_err"):
        line = next(l for l in out.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in out.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def test_a_broken_merge_of_row_mates_is_not_correct(kernels_on_cpu,
                                                    monkeypatch, capsys):
    """The combine by logical row alone: two mates of one physical row
    reach the kernel as two entries, and one write overwrites the other.
    The three-row table has two physical rows and every batch holds both
    mates of each."""
    from pslite_tpu.parallel import sparse

    def combine(local, g, R, pack):
        G_seg, row_seg, valid = sparse._combine_rows(local, g, R)
        placed, phys = sparse._place_rows(G_seg, row_seg, pack)
        return placed, phys, valid

    monkeypatch.setattr(sparse, "_combine_phys_rows", combine)
    ok, result = _run(seed=5, seconds=0.1)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith("compare ") and "NOT CORRECT" in l
               for l in out.splitlines()), out


def test_a_program_without_the_grouped_calls_ends_in_the_drivers_init(
        monkeypatch):
    """The parent of the PR that brought the calls: the driver says what
    is missing before any table is registered, and the harness shuts the
    cluster down and lets the error out."""
    import pslite_tpu as ps
    from pslite_tpu.parallel.sparse import SparseEngine

    monkeypatch.delattr(ps.KVWorker, "pull_sparse_group")
    monkeypatch.delattr(ps.KVWorker, "push_sparse_group")

    def register(self, *a, **kw):
        raise AssertionError("a table was registered")

    monkeypatch.setattr(SparseEngine, "register_sparse", register)
    t0 = time.perf_counter()
    with pytest.raises(RuntimeError, match="no pull_sparse_group / "
                                           "push_sparse_group"):
        _run(seed=3)
    assert time.perf_counter() - t0 < 30
