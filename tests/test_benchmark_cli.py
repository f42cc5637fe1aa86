"""Benchmark CLI (test_benchmark.cc parity) + distributed options."""

import os
import subprocess
import sys

import pytest


def test_benchmark_cli_over_launcher():
    proc = subprocess.run(
        [
            sys.executable, "-m", "pslite_tpu.tracker.local",
            "-n", "1", "-s", "2", "--",
            sys.executable, "-m", "pslite_tpu.benchmark",
            "--len", "16384", "--repeat", "4", "--mode", "push_then_pull",
        ],
        capture_output=True,
        timeout=240,
        cwd="/root/repo",
    )
    out = proc.stdout.decode()
    assert proc.returncode == 0, proc.stderr.decode()[-1500:]
    assert "push:" in out and "pull:" in out and "Gbps" in out
    assert "CHECK_OK" in out


def test_distributed_options_from_env():
    from pslite_tpu.environment import Environment
    from pslite_tpu.parallel.distributed import (
        distributed_options,
        init_distributed,
    )

    env = Environment({
        "DMLC_PS_ROOT_URI": "10.0.0.1",
        "DMLC_PS_ROOT_PORT": "9090",
        "DMLC_NUM_WORKER": "4",
        "DMLC_RANK": "2",
    })
    opts = distributed_options(env)
    assert opts == {
        "coordinator_address": "10.0.0.1:9091",
        "num_processes": 4,
        "process_id": 2,
    }
    # Single-process: no-op.
    assert init_distributed(Environment({"DMLC_NUM_WORKER": "1"})) is None

    from pslite_tpu.utils.logging import CheckError

    with pytest.raises(CheckError):
        distributed_options(Environment({
            "DMLC_PS_ROOT_URI": "h", "DMLC_NUM_WORKER": "4",
        }))  # missing DMLC_RANK


def test_benchmark_cli_recv_buffer_mode():
    """ENABLE_RECV_BUFFER=1 (test_benchmark.cc:268-320): registered
    buffers on both sides over the shm van, in-place deliveries counted
    and non-zero."""
    import re

    env = dict(os.environ, ENABLE_RECV_BUFFER="1")
    proc = subprocess.run(
        [
            sys.executable, "-m", "pslite_tpu.tracker.local",
            "-n", "1", "-s", "1", "--van", "shm", "--",
            sys.executable, "-m", "pslite_tpu.benchmark",
            "--len", "16384", "--repeat", "4", "--mode", "push_then_pull",
        ],
        capture_output=True,
        timeout=240,
        env=env,
        cwd="/root/repo",
    )
    out = proc.stdout.decode()
    assert proc.returncode == 0, proc.stderr.decode()[-1500:]
    assert "CHECK_OK" in out
    hits = {
        m.group(1): int(m.group(2))
        for m in re.finditer(r"(\w*RECV_BUFFER_HITS) (\d+)", out)
    }
    assert hits.get("RECV_BUFFER_HITS", 0) > 0, out[-1200:]
    assert hits.get("SERVER_RECV_BUFFER_HITS", 0) > 0, out[-1200:]
