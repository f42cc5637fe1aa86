"""IciVan — the flagship TPU transport: XLA collectives over the ICI mesh.

The reference's BASELINE north star: an ``XlaVan/IciVan`` alongside
zmq/rdma/fabric/ucx that maps ``KVWorker::ZPush/ZPull`` and KVServer
aggregation onto reduce-scatter + all-gather over the device mesh, with the
PS roles as logical shards of one SPMD program rather than RDMA endpoints.

Split of planes (mirroring FabricVan nesting a ZMQVan for bootstrap,
fabric_van.h:123-127):

- **Control plane**: a pluggable message transport.  :class:`IciVan`
  nests the in-process loopback (single-process clusters, tests);
  :class:`IciTcpVan` nests the real socket van, so separate OS processes
  bootstrap through the scheduler exactly like the reference's
  fabric/ucx vans ride their nested ZMQ control plane.
- **Data plane**: a :class:`CollectiveEngine` + :class:`SparseEngine` on
  the mesh.  ``KVWorker`` detects the engine and routes registered dense
  buckets and sparse tables through jitted collectives; unregistered
  traffic falls back to the message path, preserving the full KV contract
  (the "sync collective vs async per-message" duality of SURVEY §7).

The message fallback path inherits the control plane's per-peer send
lanes (van.py, docs/send_lanes.md) unchanged: unregistered fan-out to S
server shards overlaps across peers even while the registered traffic
rides collectives — relevant on ``IciTcpVan``/``IciShmVan``, where the
message path crosses real sockets/segments.

Multi-process meshes (``PS_ICI_MULTIHOST=1``): each worker process joins
``jax.distributed`` (coordinator derived from the same DMLC_* variables
the control plane uses — parallel/distributed.py) and the engines are
built over the GLOBAL mesh spanning every process's devices, so a dense
push is one cross-process reduce-scatter riding ICI/DCN.  Worker
processes must then drive registered buckets in SPMD lockstep (same
ops, same order), which is the same contract XLA imposes on any
multi-host program; per-message asynchrony stays on the control plane.
"""

from __future__ import annotations

from ..utils import logging as log
from .loopback_van import LoopbackVan
from .shm_van import ShmVan
from .tcp_van import TcpVan


class _IciDataPlane:
    """Engine management shared by every ICI van flavor (mixin)."""

    def __init__(self, postoffice):
        super().__init__(postoffice)
        self.engine = None
        self.sparse_engine = None
        self._mesh = None
        self._dist_lease = False

    def set_mesh(self, mesh) -> None:
        """Install a specific mesh before start() (tests, multi-host)."""
        self._mesh = mesh

    def _multihost(self) -> bool:
        return self.env.find_int("PS_ICI_MULTIHOST", 0) == 1

    def _make_mesh(self):
        if self._mesh is not None:
            return self._mesh
        if self._multihost():
            # Join the global jax.distributed runtime before first backend
            # use; every worker process contributes its local devices to
            # one global mesh (the DCN/ICI-spanning deployment).  Lease-
            # counted: with several worker instances per process the
            # runtime survives until the LAST instance stops.
            from ..parallel import distributed

            self._dist_lease = distributed.acquire(self.env)
            return distributed.global_mesh()
        return None  # CollectiveEngine defaults to the local-device mesh

    def start(self, customer_id: int) -> None:
        for var, served in (("PS_ICI_IMPL", ("", "xla")),
                            ("PS_ICI_COMPRESS", ("",))):
            log.check((self.env.find(var) or "") in served,
                      f"{var} selected the ring kernel, which was removed "
                      f"at PR 46 (XLA's collectives carry every dense "
                      f"push_pull): unset it")
        super().start(customer_id)
        # Only worker instances drive the SPMD data plane; scheduler/server
        # instances keep the control-plane role (barriers, bookkeeping, and
        # the async message fallback path).
        if self.engine is None and self.po.is_worker:
            from ..parallel.engine import CollectiveEngine
            from ..parallel.sparse import SparseEngine
            from ..utils import compile_cache
            from ..utils.profiling import stage_clock

            compile_cache.enable_compile_cache()
            handle = self.env.find("PS_ICI_SERVER_HANDLE", "sum")
            self.engine = CollectiveEngine(
                mesh=self._make_mesh(), server_handle=handle,
            )
            self.sparse_engine = SparseEngine(
                self.engine.mesh, self.engine.axis,
            )
            # The engine path's host stages and cache counters, beside
            # the message plane's instruments (docs/observability.md).
            metrics = self.po.metrics
            stage_clock().export(metrics)
            self.engine.export(metrics)
            self.sparse_engine.export(metrics)
            counts = compile_cache.cache_counts
            metrics.gauge("compile_cache.hits", fn=lambda: counts[0])
            metrics.gauge("compile_cache.misses", fn=lambda: counts[1])

    def reshard_engines(self, mesh, customer_id: int = 0) -> None:
        """Cluster-coordinated elastic recut — the roster-level trigger
        over the engine-level :meth:`CollectiveEngine.reshard`.

        EVERY worker instance of the cluster must call this with the
        same new mesh (the app's scale decision, e.g. after the
        launcher grows/shrinks the fleet).  The surrounding
        WORKER_GROUP barriers quiesce the data plane: no registered
        dense/sparse op can be in flight anywhere when the collective
        snapshot/rebuild runs, and no process resumes pushing until
        every process finished the recut — the elastic analog of the
        reference re-admitting recovered nodes under a barriered
        roster update (van.cc:266-332).

        CRASH SEMANTICS (a peer may die at any moment,
        tests/test_reshard_crash.py; barrier timeout via
        ``PS_RESHARD_TMO_S``, default 900, 0 = wait forever):

        - death BEFORE the entry barrier: survivors time out at the
          entry barrier and abort with their engines UNTOUCHED on the
          old mesh (nothing has run yet).
        - failure DURING the recut (including a peer death surfacing as
          a collective error): BOTH engines stage first and only then
          commit (reshard_staged), gated by a COMMIT BARRIER between
          staging and commit — a process whose staging failed never
          joins it, so its peers time out, abort their staged state,
          and the WHOLE CLUSTER stays together on the old mesh (no
          cross-process mesh divergence).  Stores are never torn and
          the engine pair never diverges.  (A peer dying INSIDE a
          jax.distributed collective is bounded by jax's own collective
          timeout; the resulting error takes this same abort path.)
        - death AFTER the recut, before the resume barrier: the
          collective phase completed, so every SURVIVOR holds the same
          committed new-mesh state; the resume-barrier timeout raises
          to report the cluster degraded.  Recovery (keepalive restart
          + rejoin) re-admits the dead rank; further barriers must wait
          for it (see Postoffice.barrier's timeout caveat).
        """
        import os

        from ..base import WORKER_GROUP

        log.check(self.engine is not None,
                  "reshard_engines: no engine (worker-only, after start)")
        # Validate the cheap deterministic invariants BEFORE the first
        # barrier: a worker failing these would otherwise wedge every
        # peer at the resume barrier instead of raising visibly.
        kv_axes = (
            self.engine.axis if isinstance(self.engine.axis, tuple)
            else (self.engine.axis,)
        )
        for a in kv_axes:
            log.check(a in mesh.axis_names,
                      f"kv axis {a!r} not in new mesh")
        if self.engine.worker_axis is not None:
            log.check(self.engine.worker_axis in mesh.axis_names,
                      f"worker axis {self.engine.worker_axis!r} not in "
                      f"new mesh")
        tmo = float(os.environ.get("PS_RESHARD_TMO_S", "900")) or None
        self.po.barrier(customer_id, WORKER_GROUP, timeout_s=tmo)
        done = False
        try:
            # Stage BOTH engines (everything fallible, including the
            # multi-process collectives), pass the COMMIT BARRIER (so a
            # peer whose staging failed aborts the whole cluster — its
            # absence times the barrier out inside the with-blocks,
            # which then unwind WITHOUT committing), then commit both.
            staged = False
            with self.engine.reshard_staged(mesh) as commit_dense, \
                    self.sparse_engine.reshard_staged(mesh) as commit_sp:
                staged = True
                try:
                    self.po.barrier(customer_id, WORKER_GROUP,
                                    timeout_s=tmo)
                except log.CheckError:
                    raise log.CheckError(
                        "a peer failed to stage the recut (commit "
                        "barrier timeout) — aborted together on the "
                        "old mesh"
                    ) from None
                commit_dense()
                commit_sp()
            done = True
        finally:
            # A process whose STAGING failed goes SILENT: barrier rounds
            # are anonymous counts, so issuing any further request would
            # land in the same round as the survivors' commit barrier
            # and release it — committing them onto the new mesh while
            # this process aborts (cross-process divergence).  Peers
            # detect the silence by timeout at the commit barrier and
            # abort together; they then time out at THIS resume barrier
            # too, where the commit-abort error (done=False) wins.
            if staged:
                try:
                    self.po.barrier(customer_id, WORKER_GROUP,
                                    timeout_s=tmo)
                except Exception:  # noqa: BLE001 - degraded report
                    if done:
                        raise log.CheckError(
                            "reshard completed on this process but a "
                            "peer did not reach the resume barrier — "
                            "cluster degraded; recover the dead rank "
                            "before further collective ops"
                        ) from None
                    # Recut already aborted: the commit-barrier error
                    # propagating from the try block wins.

    def stop_transport(self) -> None:
        super().stop_transport()
        if self._dist_lease:
            self._dist_lease = False
            from ..parallel import distributed

            distributed.release()

    # NOTE: no register_recv_buffer here.  Donated HBM buffers make
    # delivery-in-place the default on the collective path (SURVEY §5
    # "RegisterRecvBuffer ⇒ donated HBM"), and kv_app treats an absent
    # van hook as exactly that no-op — while a mixin no-op would shadow
    # ShmVan's REAL transport hook in IciShmVan's MRO and silently
    # disable in-place push delivery on its message path.


class IciVan(_IciDataPlane, LoopbackVan):
    """Collective data plane over the in-process loopback control plane."""


class IciTcpVan(_IciDataPlane, TcpVan):
    """Collective data plane over the real socket control plane — the
    fabric_van pattern (fabric_van.h:123-127): scheduler bootstrap, rank
    assignment, barriers, heartbeats, and the message fallback path all
    ride TCP between OS processes, while registered dense/sparse traffic
    rides jitted XLA collectives over the (optionally multi-process)
    device mesh."""


class IciShmVan(_IciDataPlane, ShmVan):
    """Collective data plane over the same-host shm control plane:
    multi-process single-host deployments (the reference's co-located
    BYTEPS_ENABLE_IPC topology) bootstrap through /dev/shm segments
    (+ optional PS_SHM_RING pipes) while registered traffic rides the
    collectives — the IPC analog of the fabric_van nesting."""
