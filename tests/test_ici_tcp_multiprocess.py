"""IciTcpVan: collective data plane over the TCP control plane, across
real OS processes — the fabric_van pattern (fabric_van.h:123-127) with
jax.distributed supplying the cross-process device mesh.

2 worker processes x 4 virtual CPU devices each = one global 8-device
mesh; a dense push_pull must aggregate across both processes and match
the host model (the PS aggregation contract of kv_app.h:430-452).
"""

import os
import subprocess
import sys

import pytest

from pslite_tpu.utils.network import get_available_port


@pytest.mark.parametrize("van,extra", [
    ("ici_tcp", {}),
    # Same-host co-located flavor: bootstrap + message fallback ride
    # /dev/shm (segments + ring pipes), collectives ride the global mesh.
    ("ici_shm", {"PS_SHM_RING": "1"}),
])
def test_ici_two_process_push_pull(van, extra):
    port = get_available_port()
    child = os.path.join(os.path.dirname(__file__), "ici_tcp_child.py")
    base_env = dict(
        os.environ,
        DMLC_NUM_WORKER="2",
        DMLC_NUM_SERVER="1",
        DMLC_PS_ROOT_URI="127.0.0.1",
        DMLC_PS_ROOT_PORT=str(port),
        DMLC_NODE_HOST="127.0.0.1",
        PS_VAN_TYPE=van,
        PS_ICI_MULTIHOST="1",
        PS_VERBOSE="1",
        **extra,
    )
    # The children pin their own platform; scrub any inherited forcing.
    for var in ("JAX_PLATFORMS", "XLA_FLAGS"):
        base_env.pop(var, None)
    roles = [("scheduler", None), ("server", None), ("worker", 0),
             ("worker", 1)]
    procs = []
    for role, rank in roles:
        env = dict(base_env, DMLC_ROLE=role)
        if rank is not None:
            env["DMLC_RANK"] = str(rank)
        procs.append(
            subprocess.Popen(
                [sys.executable, child],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
            )
        )
    outputs = []
    for p in procs:
        try:
            # 1-CPU host: 4 interpreter startups serialize, plus the
            # cross-process shard_map compile; be generous.
            out, _ = p.communicate(timeout=420)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outputs.append(out.decode())
    for p, out in zip(procs, outputs):
        assert p.returncode == 0, f"child failed:\n{out}"
    worker_outs = [o for o in outputs if "WORKER_OK 24.0" in o]
    assert len(worker_outs) == 2, f"expected 2 worker OKs, got: {outputs}"
    if extra.get("PS_SHM_RING"):
        # The ring pipes must actually engage — a native-core fallback
        # would pass this test on plain sockets, masking pipe regressions.
        assert not any("staying on sockets" in o for o in outputs), outputs


def test_init_distributed_idempotent(monkeypatch):
    """A process hosting several worker instances (groups/JOINT) must
    join jax.distributed once; later calls are no-ops."""
    import jax

    from pslite_tpu.environment import Environment
    from pslite_tpu.parallel import distributed

    env = Environment({
        "DMLC_NUM_WORKER": "2",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": "12345",
        "DMLC_RANK": "0",
    })
    calls = []
    # Restore module lease state after the test (monkeypatch teardown).
    monkeypatch.setattr(distributed, "_leases", 0)
    monkeypatch.setattr(distributed, "_opts", None)
    monkeypatch.setattr(distributed, "_owned", False)
    monkeypatch.setattr(jax.distributed, "is_initialized", lambda: True)
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: calls.append(kw),
    )
    assert distributed.init_distributed(env) is None
    assert calls == []

    # acquire() on an externally-owned runtime takes a lease but release()
    # must never shut that runtime down.
    shutdowns = []
    monkeypatch.setattr(jax.distributed, "shutdown",
                        lambda: shutdowns.append(1))
    assert distributed.acquire(env) is True
    distributed.release()
    assert shutdowns == []


def test_acquire_release_owned_lifecycle(monkeypatch):
    """Owned path: acquire initializes once; two leases; the runtime is
    shut down exactly once, on the LAST release.  Mismatched cluster
    options are refused."""
    import jax
    import pytest

    from pslite_tpu.environment import Environment
    from pslite_tpu.parallel import distributed
    from pslite_tpu.utils import logging as log

    env = Environment({
        "DMLC_NUM_WORKER": "2",
        "DMLC_PS_ROOT_URI": "127.0.0.1",
        "DMLC_PS_ROOT_PORT": "12345",
        "DMLC_RANK": "0",
    })
    monkeypatch.setattr(distributed, "_leases", 0)
    monkeypatch.setattr(distributed, "_opts", None)
    monkeypatch.setattr(distributed, "_owned", False)
    state = {"init": 0, "shutdown": 0, "up": False}
    monkeypatch.setattr(jax.distributed, "is_initialized",
                        lambda: state["up"])

    def fake_init(**kw):
        state["init"] += 1
        state["up"] = True

    def fake_shutdown():
        state["shutdown"] += 1
        state["up"] = False

    monkeypatch.setattr(jax.distributed, "initialize", fake_init)
    monkeypatch.setattr(jax.distributed, "shutdown", fake_shutdown)

    assert distributed.acquire(env) is True   # initializes
    assert distributed.acquire(env) is True   # reuses (same opts)
    assert state["init"] == 1

    # A different cluster description must be refused while leased.
    env_other = Environment({
        "DMLC_NUM_WORKER": "2",
        "DMLC_PS_ROOT_URI": "10.0.0.9",
        "DMLC_PS_ROOT_PORT": "999",
        "DMLC_RANK": "0",
    })
    with pytest.raises(log.CheckError, match="mismatched"):
        distributed.acquire(env_other)

    distributed.release()
    assert state["shutdown"] == 0  # sibling lease still active
    distributed.release()
    assert state["shutdown"] == 1  # last owned lease out
    distributed.release()          # extra release is a no-op
    assert state["shutdown"] == 1

    # Single-process configs never touch the distributed runtime.
    env1 = Environment({"DMLC_NUM_WORKER": "1"})
    monkeypatch.setattr(jax.distributed, "is_initialized",
                        lambda: (_ for _ in ()).throw(AssertionError))
    assert distributed.init_distributed(env1) is None
