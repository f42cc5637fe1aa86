"""The cell kind ``dlrm-terabyte-emb64.zipf`` brings, end to end at a tiny
size without the chip: ``tiny-sparse`` at 64 lanes (``cells/tiny-sparse-64.json``,
named by no entry of ``workloads``), a table kept two rows to a 128-lane
physical row, under the sparse driver as it stands.  On the CPU the push is
XLA's scatter; with the CPU named among the platforms of ``ops/row_add.py``
(interpreted) it is what the chip runs: the rows placed in their slot's
lanes, combined by physical row, written by the kernel.  Both read
``correct``; the bf16 control fails; a placement or a merge broken underneath
comes out ``correct: false``.
"""

import os
import subprocess
import sys
import time

import pytest

import harness
import tiny
from conftest import BENCH, HERE, ROOT

tiny.KINDS["packed"] = ("tiny-sparse-64.json", "tiny-zipf.json")


def _run(seed=7, seconds=0.3, **kw):
    return harness.run_cell(tiny.cell("packed"), seed, seconds, False,
                            time.perf_counter(), require_tpu=False, **kw)


@pytest.fixture()
def engines(monkeypatch):
    """Every ``SparseEngine`` a run pushes through, to read its counters
    after the run has shut its cluster down."""
    from pslite_tpu.parallel.sparse import SparseEngine

    seen, real = [], SparseEngine.push

    def push(self, *a, **kw):
        if self not in seen:
            seen.append(self)
        return real(self, *a, **kw)

    monkeypatch.setattr(SparseEngine, "push", push)
    return seen


@pytest.fixture()
def kernel_on_cpu(monkeypatch):
    from pslite_tpu.parallel import sparse

    monkeypatch.setitem(sparse._ROW_ADD_INTERPRET, "cpu", True)


def _check(ok, result, out, engines, by_kernel):
    assert ok and result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    assert "0 compilations in the window" in out
    assert "compare engine_byte_counters_gap: 0.0" in out
    assert "compare hot_row_copies_spread: 0.0" in out
    (eng,) = engines
    table = eng.table("emb")
    assert (table.dim, table.pack) == (64, 2)
    # Every step pushed once, every push into the lane-packed table, and
    # by the kernel exactly where the program is lowered for its platform.
    pushes = eng.push_bytes // (4 * 256 * 64 * 4)
    assert pushes >= result["attempted"] + 4
    assert eng.packed_pushes == pushes
    assert eng.row_kernel_pushes == (pushes if by_kernel else 0)
    assert eng.stateful_pushes == 0


def test_the_packed_cell_end_to_end_on_four_devices(engines, capsys):
    ok, result = _run(seed=2**31 + 5)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=False)


def test_the_packed_cell_through_the_kernel_as_the_chip_runs_it(
        engines, kernel_on_cpu, capsys):
    ok, result = _run(seed=2**31 + 6)
    _check(ok, result, capsys.readouterr().out, engines, by_kernel=True)


def test_the_control_fails_on_the_packed_cell(capsys):
    _run(seed=11, control="bf16")
    out = capsys.readouterr().out
    for number in ("first3_err", "final_err"):
        line = next(l for l in out.splitlines()
                    if l.startswith(f"control[bf16] {number}"))
        assert "fails, as it must" in line
        sound = next(l for l in out.splitlines()
                     if l.startswith(f"compare {number}"))
        assert float(line.split()[2]) > 30 * float(sound.split()[2])


def _every_row_in_slot_0(monkeypatch):
    """Placement broken: every gradient lands in the first row's lanes of
    its physical row, so odd rows' gradients go to their even mates."""
    from pslite_tpu.parallel import sparse

    real = sparse._place_rows
    monkeypatch.setattr(
        sparse, "_place_rows",
        lambda g, rows, pack: real(g, rows - rows % pack, pack))


def _no_merge_of_row_mates(monkeypatch):
    """The combine by logical row alone: two mates of one physical row
    reach the kernel as two entries, and one write overwrites the other."""
    from pslite_tpu.parallel import sparse

    def combine(local, g, R, pack):
        G_seg, row_seg, valid = sparse._combine_rows(local, g, R)
        placed, phys = sparse._place_rows(G_seg, row_seg, pack)
        return placed, phys, valid

    monkeypatch.setattr(sparse, "_combine_phys_rows", combine)


@pytest.mark.parametrize("breaker", [_every_row_in_slot_0,
                                     _no_merge_of_row_mates])
def test_a_broken_packed_push_is_not_correct(breaker, kernel_on_cpu,
                                             monkeypatch, capsys):
    breaker(monkeypatch)
    ok, result = _run(seed=5)
    out = capsys.readouterr().out
    assert not ok and result["correct"] is False
    assert any(l.startswith("compare ") and "NOT CORRECT" in l
               for l in out.splitlines()), out


def test_the_packed_cell_on_one_device_in_a_child_process():
    code = (
        "import json, os, sys, time\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=1'\n"
        "os.environ['JAX_ENABLE_COMPILATION_CACHE'] = 'false'\n"
        f"sys.path[:0] = [{BENCH!r}, {ROOT!r}, {HERE!r}]\n"
        "import harness, tiny\n"
        "tiny.KINDS['packed'] = ('tiny-sparse-64.json', 'tiny-zipf.json')\n"
        "from pslite_tpu.parallel import sparse\n"
        "sparse._ROW_ADD_INTERPRET['cpu'] = True\n"
        "ok, r = harness.run_cell(tiny.cell('packed', chips=1), 3, 0.2, False,"
        " time.perf_counter(), require_tpu=False)\n"
        "assert ok and r['device']['count'] == 1, r\n"
        "print('ONE_DEVICE_OK')\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "PS_LOOPBACK_NS"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, env=env)
    assert "ONE_DEVICE_OK" in out.stdout, out.stderr[-3000:]
