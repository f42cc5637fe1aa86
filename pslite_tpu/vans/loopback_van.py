"""Loopback van — in-process transport for unit tests.

This is the "fake backend" tier the reference fork dropped (SURVEY §4): a
whole cluster (scheduler + servers + workers, including instance groups) runs
inside one process, with every message round-tripped through the real wire
format (``wire.pack_frame``/``unpack``) so serialization is exercised on every
test.  The scheduler bootstrap, rank assignment, barriers, heartbeats and
recovery all run for real — only the sockets are replaced by queues.

Endpoints register in a process-global registry keyed by
``(namespace, host, port)``; the namespace (``PS_LOOPBACK_NS``) isolates
concurrently running test clusters.
"""

from __future__ import annotations

import struct
import threading
import time
from typing import Dict, Optional, Tuple

from ..base import SCHEDULER_ID
from ..message import Message, Node
from ..utils import logging as log
from ..utils.queues import PriorityRecvQueue, ThreadsafeQueue
from .. import wire
from .chunking import RECV_DRAIN_LAST, recv_cost, recv_priority, recv_tenant
from .van import Van

_registry_mu = threading.Lock()
_registry: Dict[Tuple[str, str, int], "LoopbackVan"] = {}
_port_counter = [20000]
_SCHEDULER_BIND_WAIT_S = 10.0


def reset_registry() -> None:
    """Drop all registered endpoints (test teardown helper)."""
    with _registry_mu:
        _registry.clear()


class LoopbackVan(Van):
    def __init__(self, postoffice):
        super().__init__(postoffice)
        self._ns = self.env.find("PS_LOOPBACK_NS", "default")
        # The queue holds packed blobs, so the receive-priority level is
        # computed by the SENDER (which still has the Message) and
        # pushed alongside — same discipline as the socket vans
        # (docs/chunking.md), same PS_RECV_PRIORITY opt-out.
        self._prio_recv = bool(self.env.find_int("PS_RECV_PRIORITY", 1))
        self._queue = (
            PriorityRecvQueue(lambda _b: 0,
                              weights=self._tenant_weights)
            if self._prio_recv else ThreadsafeQueue()
        )
        self._peers: Dict[int, Tuple[str, int]] = {}
        self._bound_key: Optional[Tuple[str, str, int]] = None

    def bind_transport(self, node: Node, max_retry: int) -> int:
        port = node.port
        with _registry_mu:
            if port == 0:
                _port_counter[0] += 1
                port = _port_counter[0]
            key = (self._ns, node.hostname, port)
            log.check(key not in _registry, f"loopback addr in use: {key}")
            _registry[key] = self
            self._bound_key = key
        return port

    def connect_transport(self, node: Node) -> None:
        if node.id >= 0:
            self._peers[node.id] = (node.hostname, node.port)
        if node.id == SCHEDULER_ID and not self.po.is_scheduler:
            # A socket van's connect retries until the scheduler
            # listens; here the scheduler is another thread of this
            # process (``start_ps`` once per role), so give it the same
            # grace to bind before the ADD_NODE that follows is sent.
            key = (self._ns, node.hostname, node.port)
            deadline = time.monotonic() + _SCHEDULER_BIND_WAIT_S
            while key not in _registry and time.monotonic() < deadline:
                time.sleep(0.005)

    def _resolve(self, recver: int) -> "LoopbackVan":
        if recver == self.my_node.id:
            return self
        addr = self._peers.get(recver)
        log.check(addr is not None, f"loopback: unknown recver {recver}")
        with _registry_mu:
            van = _registry.get((self._ns, addr[0], addr[1]))
        log.check(van is not None, f"loopback: no endpoint at {addr}")
        return van

    def send_msg(self, msg: Message) -> int:
        # Thread-safe without any van-level locking: per-peer send lanes
        # may call this concurrently for different recvers, and the
        # registry lookup + queue push are each internally locked.  The
        # one-pass join also serializes the payload HERE (dispatch
        # time), so the zero-copy contract matches the socket vans:
        # callers must not mutate buffers until wait(ts).
        target = self._resolve(msg.meta.recver)
        chunks = wire.pack_frame(msg)
        blob = b"".join(chunks)  # join accepts memoryviews: one copy
        if target._prio_recv:
            # The queue holds packed blobs: priority AND the tenant/
            # cost (docs/qos.md) are computed sender-side while the
            # Message is still in hand.
            target._queue.push(blob, priority=recv_priority(msg),
                               tenant=recv_tenant(msg),
                               cost=recv_cost(msg))
        else:
            target._queue.push(blob)
        return len(blob)

    def recv_msg(self) -> Optional[Message]:
        blob = self._queue.wait_and_pop()
        if blob is None:
            return None
        meta_len, n_data = wire.unpack_frame_header(blob[: wire.FRAME_HEADER_SIZE])
        off = wire.FRAME_HEADER_SIZE
        lens = struct.unpack_from(f"<{n_data}Q", blob, off)
        off += 8 * n_data
        meta = wire.unpack_meta(blob[off : off + meta_len])
        off += meta_len
        bufs = []
        for ln in lens:
            bufs.append(blob[off : off + ln])
            off += ln
        return wire.rebuild_message(meta, bufs)

    def stop_transport(self) -> None:
        if self._prio_recv:
            self._queue.push(None, priority=RECV_DRAIN_LAST)
        else:
            self._queue.push(None)
        if self._bound_key is not None:
            with _registry_mu:
                _registry.pop(self._bound_key, None)
            self._bound_key = None
