"""Elastic reshard across OS processes (VERDICT r02 #4).

Two jax.distributed processes shrink and grow the engine's kv axis live
— the deployment shape the reference's recovery path serves
(van.cc:266-332), on the collective data plane."""

import os
import subprocess
import sys

from pslite_tpu.utils.network import get_available_port


def test_reshard_across_two_processes():
    port = get_available_port()
    child = os.path.join(os.path.dirname(__file__), "reshard_child.py")
    procs = []
    for rank in range(2):
        env = dict(
            os.environ,
            RESHARD_RANK=str(rank),
            RESHARD_COORD=f"127.0.0.1:{port}",
        )
        # The child pins its own platform/device-count env before jax
        # import; scrub any inherited conftest pin.
        env.pop("XLA_FLAGS", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, child],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
        )
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode())
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"reshard child failed:\n{out}"
    assert sum("RESHARD_OK" in o for o in outs) == 2, outs
