"""Boot and shutdown of the system under test, in this process.

Copied from ``chip_smoke.py::_Smoke.boot`` (not imported: the yardstick
lives under ``benchmark/``): scheduler + one joint (server and worker) node,
each through ``ps.start_ps`` on its own thread, over ``PS_VAN_TYPE=ici``.
The van builds its engines over every local device, so W = the cell's chips.
"""

from __future__ import annotations

import threading


class Cluster:
    """The booted system: ``kv`` is the ``KVWorker`` all traffic goes
    through, ``sparse`` the van's sparse engine (tables are registered
    there; ``KVWorker`` has no sparse registration call)."""

    def __init__(self, server_handle: str):
        import pslite_tpu as ps

        env = ps.environment.Environment({
            "PS_VAN_TYPE": "ici",
            "PS_ICI_SERVER_HANDLE": server_handle,
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1",
            "DMLC_PS_ROOT_URI": "benchmark",
            "DMLC_PS_ROOT_PORT": "1",
        })
        errors: list = []

        def start(role: str) -> None:
            try:
                ps.start_ps(role=role, env=env)
            except BaseException as exc:  # re-raised on this thread below
                errors.append(exc)

        threads = [
            threading.Thread(target=start, args=(role,), daemon=True,
                             name=f"start-{role}")
            for role in ("scheduler", "joint")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        self._ps = ps
        self.server = ps.KVServer(0)
        self.server.set_request_handle(ps.KVServerDefaultHandle())
        self.kv = ps.KVWorker(0, 0)
        if self.kv.engine is None:
            raise RuntimeError("the ici van built no collective engine")
        self.engine = self.kv.engine
        self.sparse = ps.postoffice(ps.Role.WORKER).van.sparse_engine

    def shutdown(self) -> None:
        self._ps.finalize()
        self.server.stop()
