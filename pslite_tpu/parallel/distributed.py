"""Multi-host bootstrap: jax.distributed from the PS environment.

The reference scales multi-host through its scheduler rendezvous; on TPU
pods the equivalent is ``jax.distributed.initialize`` building one global
mesh across hosts, with XLA collectives riding ICI within a slice and DCN
across slices.  This module derives the coordinator/process topology from
the same DMLC_* variables the PS control plane uses, so one launcher
config drives both planes:

- coordinator = ``DMLC_PS_ROOT_URI : DMLC_PS_ROOT_PORT + 1`` (the port
  next to the scheduler),
- num_processes = worker count (each host is one worker / one JOINT
  process),
- process_id = ``DMLC_RANK``.

Single-process use (tests, one chip) never needs this.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from .. import environment
from ..utils import logging as log

_mu = threading.Lock()
_leases = 0
_opts: Optional[Dict[str, object]] = None
_owned = False  # True only when THIS module performed the initialize


def distributed_options(env=None) -> Dict[str, object]:
    """Pure computation of jax.distributed.initialize kwargs from env."""
    env = env or environment.get()
    uri = env.find("DMLC_PS_ROOT_URI")
    log.check(uri is not None, "DMLC_PS_ROOT_URI not set")
    port = env.find_int("DMLC_PS_ROOT_PORT", 0) + 1
    num = env.find_int("DMLC_NUM_WORKER", 0)
    log.check(num > 0, "DMLC_NUM_WORKER not set")
    rank = env.find_int("DMLC_RANK", -1)
    log.check(0 <= rank < num,
              "DMLC_RANK must be set per host for multi-host meshes")
    return {
        "coordinator_address": f"{uri}:{port}",
        "num_processes": num,
        "process_id": rank,
    }


def _initialize_or_unwind(opts) -> None:
    """jax.distributed.initialize with half-init cleanup: jax assigns its
    global client BEFORE connecting, so a connect failure (coordinator
    unreachable) would leave ``jax.distributed.is_initialized()`` true on
    a never-connected runtime and poison every later acquire.  Unwind on
    failure so retries re-initialize."""
    import jax

    try:
        jax.distributed.initialize(**opts)
    except Exception:
        try:
            jax.distributed.shutdown()
        except Exception:  # best-effort: leave no half-open client
            pass
        raise


def acquire(env=None) -> bool:
    """Join the jax.distributed runtime (once per process) and take a
    lease on it.  Several worker instances per process (instance groups /
    JOINT role) each acquire; the runtime shuts down when the LAST lease
    is released — never under a sibling still using the global mesh, and
    never at all when someone else (the user's own
    ``jax.distributed.initialize`` call) owns the runtime.

    Returns True when a lease was taken (multi-process config), False
    for single-process configs (nothing to release).
    """
    global _leases, _opts, _owned
    import jax

    env = env or environment.get()
    if env.find_int("DMLC_NUM_WORKER", 1) <= 1:
        return False

    with _mu:
        if not jax.distributed.is_initialized():
            opts = distributed_options(env)
            _initialize_or_unwind(opts)
            # Recorded only after a successful initialize.
            _opts = opts
            _owned = True
            log.info(f"jax.distributed initialized: {opts}")
        elif _opts is not None:
            # Reusing the runtime this process already joined: the caller
            # must describe the SAME cluster, or its collectives would
            # silently run over the wrong process set.
            want = distributed_options(env)
            log.check(
                want == _opts,
                f"jax.distributed already initialized with {_opts}; "
                f"refusing mismatched options {want}",
            )
        else:
            log.info("jax.distributed externally initialized; reusing "
                     "(shutdown stays with its owner)")
        _leases += 1
    return True


def release() -> None:
    """Release one lease; shuts the runtime down when none remain AND
    this module performed the initialize (an externally-owned runtime is
    never torn down from here)."""
    global _leases, _opts, _owned
    import jax

    with _mu:
        if _leases == 0:
            return
        _leases -= 1
        if _leases > 0 or not _owned:
            return
        _opts = None
        _owned = False
        try:
            jax.distributed.shutdown()
        except Exception as exc:  # best-effort: interpreter teardown
            log.vlog(1, f"jax.distributed.shutdown: {exc!r}")


def init_distributed(env=None) -> Optional[Dict[str, object]]:
    """Back-compat initialize-once (NO lease accounting — callers of this
    wrapper own any shutdown themselves).  Prefer acquire()/release().
    Returns the options used when this call initialized, else None."""
    global _opts
    import jax

    env = env or environment.get()
    if env.find_int("DMLC_NUM_WORKER", 1) <= 1:
        return None

    with _mu:
        if jax.distributed.is_initialized():
            return None
        opts = distributed_options(env)
        _initialize_or_unwind(opts)
        _opts = opts  # mismatch guard for later acquire()s; not owned
        return opts


def global_mesh(axis_name: str = "kv"):
    """1-D mesh over every device of every process (call after
    init_distributed on multi-host)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()), (axis_name,))
