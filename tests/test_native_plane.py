"""Native zero-copy data plane (docs/native_core.md).

Frame-level parity: the frames the C++ sender lanes put on the wire
must be BYTE-IDENTICAL to ``wire.pack_frame`` over ``split_message``'s
chunks — that is what lets mixed native/non-native clusters
interoperate (ISSUE 6 acceptance).  Captured off a raw accepted socket
so nothing but the lane's own encoder touches the bytes.

Also: the mixed-cluster storm (native worker <-> PS_NATIVE=0 servers,
bit-exact vs all-Python), the ABI-stamp freshness assert, and the
stale-.so rejection guard (compiles a wrong-stamp library when a C++
toolchain is present; SKIPS otherwise).
"""

import copy
import os
import re
import shutil
import socket
import subprocess
import sys

import numpy as np
import pytest

from pslite_tpu import wire
from pslite_tpu.message import OPT_COMPRESS_INT8, Message
from pslite_tpu.sarray import SArray
from pslite_tpu.vans import native as native_mod
from pslite_tpu.vans.chunking import native_descriptor, split_message

from helpers import LoopbackCluster

_PEER = 77


def _require_native():
    if native_mod.load() is None:
        pytest.skip("native core unavailable (make native)")


def _msg(segs, push=True, option=0, trace=0, sender=9, recver=_PEER,
         timestamp=3):
    msg = Message()
    m = msg.meta
    m.sender, m.recver = sender, recver
    m.request = True
    m.push = push
    m.app_id = 0
    m.timestamp = timestamp
    m.option = option
    m.trace = trace
    for a in segs:
        msg.add_data(SArray(a))
    return msg


def _variants():
    """(name, message, chunk_bytes) — every encoder feature the parity
    contract covers: plain, empty-vals, int8 options, trace extension
    tails, the chunk extension (chunked transfer), and the EXT_CODEC
    tail of the quantized transport tier (docs/compression.md) —
    monolithic AND re-chunked, where EXT_CHUNK must stay the meta's
    trailing bytes with the codec ext intact ahead of it."""
    from pslite_tpu.message import CodecInfo
    from pslite_tpu.ops import codecs

    rng = np.random.default_rng(7)
    keys = np.arange(16, dtype=np.uint64)
    vals = rng.normal(size=16 * 256).astype(np.float32)
    big_vals = rng.normal(size=16 * 2048).astype(np.float32)
    codec = codecs.get_codec("int8")
    codes, scales, flags = codec.encode(big_vals)
    cmsg = _msg([keys, np.ascontiguousarray(codes), scales],
                trace=0x77AA)
    cmsg.meta.codec = CodecInfo(codec=codec.wire_id,
                                raw_len=big_vals.nbytes,
                                block=codec.block, flags=flags)
    cmsg2 = _msg([keys, np.ascontiguousarray(codes), scales])
    cmsg2.meta.codec = cmsg.meta.codec
    out = [
        ("plain_push", _msg([keys, vals]), 0),
        ("empty_vals", _msg([keys, np.empty(0, np.float32)]), 0),
        ("int8_options",
         _msg([keys, (rng.normal(size=512) * 10).astype(np.int8),
               rng.normal(size=16).astype(np.float32)],
              option=OPT_COMPRESS_INT8, trace=0xABCDEF), 0),
        ("traced_chunked", _msg([keys, vals], trace=0x1234), 4096),
        ("chunked_with_lens",
         _msg([keys, vals, np.full(16, 256, np.int32)]), 4096),
        ("codec_ext_mono", cmsg, 0),
        ("codec_ext_chunked", cmsg2, 8192),
    ]
    return out


def _python_wire_bytes(msg, chunk_bytes, xfer_id, sid_start):
    """What the pure-Python path puts on the wire for this message:
    split_message's chunks (or the monolithic frame), each pack_framed
    with the sid the (in-order) lane would stamp at dispatch."""
    chunks = (split_message(copy.deepcopy(msg), chunk_bytes, xfer_id)
              if chunk_bytes > 0 else None)
    if chunks is None:
        chunks = [copy.deepcopy(msg)]
    blob = bytearray()
    for i, c in enumerate(chunks):
        c.meta.sid = sid_start + i
        for part in wire.pack_frame(c):
            blob += bytes(part)
    return bytes(blob), len(chunks)


def _recv_exact(conn, n):
    buf = bytearray()
    conn.settimeout(10.0)
    while len(buf) < n:
        got = conn.recv(min(1 << 20, n - len(buf)))
        if not got:
            break
        buf += got
    return bytes(buf)


def test_native_frames_byte_identical_to_python():
    """Acceptance: for every encoder variant, the native sender lane's
    byte stream equals the Python encoder's exactly — including the
    chunk split boundaries, per-chunk sids, lens tables, and the
    trace/chunk extension tails."""
    _require_native()
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port = srv.getsockname()[1]
    nt = native_mod.NativeTransport()
    try:
        nt.connect(_PEER, "127.0.0.1", port)
        conn, _ = srv.accept()
        try:
            sid = 0
            for name, msg, chunk_bytes in _variants():
                xfer_id = 1000 + sid
                expected, n_chunks = _python_wire_bytes(
                    msg, chunk_bytes, xfer_id, sid)
                desc = native_descriptor(msg, chunk_bytes, iter([xfer_id]))
                assert desc.n_chunks == n_chunks, name
                assert desc.wire_bytes == len(expected), name
                nt.send_enqueue(_PEER, 0, desc.meta_buf, desc.arrs,
                                desc.chunk_bytes, desc.ext_off)
                assert nt.send_flush(10000)
                got = _recv_exact(conn, len(expected))
                assert got == expected, (
                    f"{name}: native frame bytes differ from pack_frame"
                )
                done = nt.send_reap(_PEER)
                assert [st for _, st in done] == [0]
                sid += n_chunks
        finally:
            conn.close()
    finally:
        nt.stop()
        nt.destroy()
        srv.close()


def test_native_descriptor_wire_bytes_accounting():
    """desc.wire_bytes must equal the summed pack_frame byte counts —
    it feeds van.send_bytes and the sent-bytes counters at reap."""
    for name, msg, chunk_bytes in _variants():
        expected, n_chunks = _python_wire_bytes(msg, chunk_bytes, 55, 0)
        desc = native_descriptor(msg, chunk_bytes, iter([55]))
        assert desc.wire_bytes == len(expected), name
        assert desc.n_chunks == n_chunks, name


# -- mixed-cluster interop ---------------------------------------------------


def _tcp_storm(env_extra=None, per_node_env=None, seed=42):
    """Deterministic mixed storm over a REAL in-process tcp cluster;
    returns the final pulled state (same shape as test_chunking's
    loopback _storm, but through the socket transports the native data
    plane actually drives)."""
    from pslite_tpu.kv.kv_app import KVServer, KVServerDefaultHandle, KVWorker

    base = {"PS_CHUNK_BYTES": "8192"}
    base.update(env_extra or {})
    cl = LoopbackCluster(num_workers=1, num_servers=2, van_type="tcp",
                         env_extra=base, per_node_env=per_node_env)
    cl.start()
    servers = []
    for po in cl.servers:
        s = KVServer(0, postoffice=po)
        s.set_request_handle(KVServerDefaultHandle())
        servers.append(s)
    w = KVWorker(0, 0, postoffice=cl.workers[0])
    span = (1 << 64) // 8
    big_keys = (np.arange(8, dtype=np.uint64) * span + 1).astype(np.uint64)
    small_keys = (np.arange(8, dtype=np.uint64) * span + 2).astype(np.uint64)
    rng = np.random.default_rng(seed)
    big = rng.normal(size=8 * 4096).astype(np.float32)
    small = rng.normal(size=8 * 16).astype(np.float32)
    for i in range(6):
        ts1 = w.push(big_keys, big)
        ts2 = w.push(small_keys, small, priority=1)
        w.wait(ts1)
        w.wait(ts2)
        if i % 2:
            w.wait(w.push(big_keys, big, compress="int8"))
    out_b = np.zeros_like(big)
    out_s = np.zeros_like(small)
    w.wait(w.pull(big_keys, out_b))
    w.wait(w.pull(small_keys, out_s))
    w.stop()
    for s in servers:
        s.stop()
    cl.finalize()
    return out_b, out_s


def test_mixed_cluster_storm_bit_exact():
    """Acceptance: a native worker pushing to PS_NATIVE=0 servers (and
    the scheduler) produces stores BIT-EXACT with an all-Python
    cluster — frames from either encoder decode identically."""
    _require_native()
    py_only = {k: {"PS_NATIVE": "0"}
               for k in ("scheduler", "server0", "server1")}
    mixed = _tcp_storm(per_node_env=py_only)
    allpy = _tcp_storm(env_extra={"PS_NATIVE": "0"})
    np.testing.assert_array_equal(mixed[0], allpy[0])
    np.testing.assert_array_equal(mixed[1], allpy[1])


def test_native_cluster_storm_bit_exact():
    """All-native cluster vs all-Python: same stores, both directions
    of every link exercising the native lanes + express recv."""
    _require_native()
    native = _tcp_storm()
    allpy = _tcp_storm(env_extra={"PS_NATIVE": "0"})
    np.testing.assert_array_equal(native[0], allpy[0])
    np.testing.assert_array_equal(native[1], allpy[1])


def test_native_reassembly_storm_bit_exact():
    """PS_NATIVE_REASSEMBLY=1 with 2 rails: chunk payloads direct-read
    into the core's SHARED transfer table (one transfer's stripes land
    on different per-stream receive pumps and scatter into one buffer)
    and each transfer reaches Python as ONE complete frame
    (finalize_native_transfer) — stores bit-exact vs all-Python,
    int8 + priority traffic included."""
    _require_native()
    reasm = _tcp_storm(env_extra={"PS_NATIVE_REASSEMBLY": "1",
                                  "PS_NATIVE_RAILS": "2"})
    allpy = _tcp_storm(env_extra={"PS_NATIVE": "0"})
    np.testing.assert_array_equal(reasm[0], allpy[0])
    np.testing.assert_array_equal(reasm[1], allpy[1])


# -- stale-.so guard (satellite: version-stamped library) --------------------


def test_abi_stamp_matches():
    """The checked-in/built .so must carry native.py's ABI_VERSION —
    load() would have rejected it otherwise, so reaching a loaded lib
    and re-reading the stamp asserts the build is fresh."""
    _require_native()
    lib = native_mod.load()
    assert lib.psl_abi_version() == native_mod.ABI_VERSION


def _cxx():
    return shutil.which(os.environ.get("CXX", "g++"))


_CPP_DIR = os.path.join(os.path.dirname(native_mod.__file__),
                        "..", "..", "cpp")


def test_core_builds_where_a_toolchain_exists(monkeypatch):
    """conftest builds the core best-effort and says nothing when that
    fails; the native legs then skip, also silently.  Here the failure is
    loud: with ``make`` and a compiler on the machine the library must
    exist (a build that failed is run again for the compiler's words)
    and load.  SKIPS only without a C++ toolchain."""
    if _cxx() is None or shutil.which("make") is None:
        pytest.skip("no C++ toolchain")
    so = os.path.join(_CPP_DIR, "libpslite_core.so")
    if not os.path.exists(so):
        r = subprocess.run(["make", "-C", _CPP_DIR], capture_output=True,
                           text=True, timeout=300)
        tail = "\n".join((r.stdout + r.stderr).splitlines()[-15:])
        assert r.returncode == 0 and os.path.exists(so), (
            f"cpp/libpslite_core.so does not build here:\n{tail}")
        return  # this process may hold load()'s negative result
    monkeypatch.delenv("PS_NATIVE", raising=False)
    assert native_mod.load() is not None, (
        "cpp/libpslite_core.so exists and does not load: `make native`")


def test_stale_so_rejected(tmp_path, monkeypatch):
    """A library whose compiled-in stamp mismatches ABI_VERSION must be
    rejected at load() (loudly, not per-symbol) so every van falls back
    to pure Python together.  SKIPS without a C++ toolchain."""
    cxx = _cxx()
    if cxx is None:
        pytest.skip("no C++ toolchain")
    text = open(os.path.join(_CPP_DIR, "pslite_core.cc")).read()
    stale_text, n = re.subn(r"kAbiVersion = \d+", "kAbiVersion = 9999",
                            text, count=1)
    assert n == 1
    stale_src = tmp_path / "stale_core.cc"
    stale_src.write_text(stale_text)
    stale_so = tmp_path / "libstale_core.so"
    try:
        subprocess.run(
            [cxx, "-O0", "-std=c++17", "-fPIC", "-shared", "-pthread",
             "-o", str(stale_so), str(stale_src)],
            check=True, capture_output=True, timeout=300,
        )
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired):
        pytest.skip("toolchain cannot build the core here")
    # load() in a SUBPROCESS: dlopen caching and the module-level _lib
    # cache in this process must not see the stale candidate.
    code = (
        "from pslite_tpu.vans import native\n"
        f"native._LIB_PATHS = [{str(stale_so)!r}]\n"
        "assert native.load() is None, 'stale .so was accepted'\n"
        "print('REJECTED')\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
        env={**os.environ,
             "PYTHONPATH": os.pathsep.join(sys.path)},
    )
    assert r.returncode == 0, r.stderr
    assert "REJECTED" in r.stdout
    assert "ABI stamp 9999" in (r.stderr + r.stdout)
