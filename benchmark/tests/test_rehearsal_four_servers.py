"""The cell kind ``dlrm-criteo-emb.zipf.4chip`` brings, end to end at a tiny
size without the chip: ``tiny-sparse`` (``cells/tiny-sparse.json``: four
shards, four workers; it needed no file of its own) under the sparse driver
as it stands, on four virtual CPU devices, in a traced run long enough for
the stage clock to hold whole slots: the counter's reader reads 4.0 slots a
lookup (every shard is sent every worker's batch), the two trace readers
read nothing (a CPU trace has no TPU plane), and the comparison holds the
hottest row's copies bit-equal over the four workers' rows.
"""

import time

import harness
from tiny import cell, check_metrics


def test_four_workers_four_shards_and_the_slots_counter(capsys):
    ok, result = harness.run_cell(cell("sparse"), 2**31 + 47, 3.6, True,
                                  time.perf_counter(), require_tpu=False)
    out = capsys.readouterr().out
    assert ok and result["correct"] and result["failed"] == 0
    assert result["device"]["count"] == 4
    for line in ("compare hot_row_copies_spread: 0.0",
                 "compare hot_row_copies_missing: 0.0",
                 "compare engine_byte_counters_gap: 0.0"):
        assert line in out, line
    # 2 * W * lookups * dim * 4 bytes a step: four workers' batches.
    assert f"{2 * 4 * 256 * 128 * 4:,} payload bytes a step" in out
    got = check_metrics(result, "per_layer", {"sparse_slots_per_lookup"})
    assert result["metrics"]["sparse_slots_per_lookup"]["value"] == 4.0
    assert not {"sparse_route_ms", "sparse_route_ici_share"} & got
