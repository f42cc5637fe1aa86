#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process, which owns every local chip, drives the main path once through
the entry points a user calls — ``ps.start_ps`` / ``KVServer`` /
``KVWorker`` / ``ps.finalize`` with ``PS_VAN_TYPE=ici`` — at the full width
of the configurations the repo supports (BASELINE.json configs 4 and 5 and
the README's own snippet), and checks every result against a numpy
reference:

- ``resnet50``: all 25,557,032 f32 parameters of the ResNet-50 gradient
  trace as its 36 buckets, host-origin ``[W, total]`` gradients,
  ``push_pull`` + ``wait`` under the fused ``sgd_momentum`` server handle;
- ``readme``: 40 keys x 256,000 f32 in one bucket, ``push_pull``, then a
  separate ``push`` and ``pull``;
- ``sparse``: a 2^20 x 64 embedding table, Zipf indices,
  ``push_sparse`` / ``pull_sparse``, then one push under the stateful
  server handle ``row_adagrad`` and one plain sum on tables of their own,
  2^20 x 128 and 2^20 x 64 (physical rows of 128 f32 lanes, the 64-wide
  lane-packed two to one: on the chip all are summed by distinct row with
  the ``ops/segment_sum.py`` kernel and written by ``ops/row_add.py``'s);
  over several chips the exchange is routed by owner, and under the sum and
  under ``row_adagrad`` alike one batch that fits its buckets and one whose
  ids all have one owner, which falls back to the gathered body, stays exact
  and is counted by ``engine.sparse.route.overflow``; last the three tables
  in one ``push_sparse_group`` and one ``pull_sparse_group``, whose program
  gives one array a width, each entry against its table's ``pull_sparse``;
- ``message_path``: an unregistered key, which the collective path cannot
  take, answered by the ``KVServer`` handler.

It has no CPU mode: without a TPU it exits non-zero before any work.  Every
phase has a deadline; a phase that fails or hangs ends the run non-zero
with its name.  Wall times are printed as set-up information only (the
first step of a phase compiles, the later ones do not) — a measurement is
the benchmark's business, not this script's.

The last line of standard output is the verdict::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``tests/test_chip_smoke.py`` drives :func:`run_smoke` at a tiny size on the
virtual CPU mesh, kernels interpreted.
"""

from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, List, Sequence, Tuple

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

LR, MOMENTUM = 0.01, 0.9
SERVER_HANDLE = f"sgd_momentum:{LR},{MOMENTUM}"
LAMB = dict(lr=0.01, b1=0.9, b2=0.999, eps=1e-6, wd=0.01)
LAMB_HANDLE = "lamb:" + ",".join(str(v) for v in LAMB.values())
SEED = 20260926


def _resnet50_buckets() -> Tuple[Tuple[str, int], ...]:
    from pslite_tpu.models.resnet_trace import make_buckets

    return tuple(make_buckets())


@dataclass(frozen=True)
class Sizes:
    """What the smoke moves.  The defaults are the full width."""

    dense_buckets: Sequence[Tuple[str, int]] = field(
        default_factory=_resnet50_buckets
    )
    steps: int = 3
    readme_keys: int = 40
    readme_val_len: int = 256_000
    emb_rows: int = 1 << 20
    emb_dim: int = 64
    emb_batch: int = 4096
    # The table pushed under the stateful handle, then summed into: rows of
    # 128 lanes, which on the chip a push writes through ops/row_add.py.
    emb_opt_dim: int = 128
    # A third table under the handle whose rows are no multiple of 128 on
    # any mesh: its accumulator is kept in whole 128s a shard and takes
    # ops/acc_update.py's pass as the others do.
    emb_odd_rows: int = 1_000_003


class PhaseFailed(RuntimeError):
    """A phase raised; ``__cause__`` is what it raised."""


def check(ok, what) -> None:
    """An ``assert`` that ``python -O`` cannot remove."""
    if not ok:
        raise AssertionError(what)


def _expired(name: str, seconds: float) -> None:
    """A hung device call cannot be interrupted from Python: say which
    phase it was and leave."""
    print(f"chip_smoke: FAILED phase={name}: no end after {seconds:.0f} s",
          file=sys.stderr, flush=True)
    sys.stdout.flush()
    os._exit(3)


@contextlib.contextmanager
def deadline(name: str, seconds: float,
             expired: Callable[[str, float], None] = _expired):
    timer = threading.Timer(seconds, expired, args=(name, seconds))
    timer.daemon = True
    timer.start()
    try:
        yield
    finally:
        timer.cancel()


class _LambRecurrence:
    """LAMB with bias correction in float64, key by key (``starts``,
    ``flags`` as the engine has them): what the ``lamb`` and ``mixed``
    phases hold the store to."""

    def __init__(self, init, starts, flags):
        from pslite_tpu.parallel.engine import KEY_NO_ADAPT, KEY_NO_DECAY

        self.p = np.asarray(init, np.float64).copy()
        self.m, self.v = np.zeros_like(self.p), np.zeros_like(self.p)
        self.starts, self.t = starts, 0
        self.no_decay = [bool(f & KEY_NO_DECAY) for f in flags]
        self.no_adapt = [bool(f & KEY_NO_ADAPT) for f in flags]

    def step(self, g) -> list:
        """Apply ``g`` (``[W, total]``, summed over W); the keys' trust
        ratios."""
        lr, b1, b2, eps, wd = LAMB.values()
        self.t += 1
        t, p = self.t, self.p
        gs = np.asarray(g, np.float64).sum(axis=0)
        m = self.m = b1 * self.m + (1 - b1) * gs
        v = self.v = b2 * self.v + (1 - b2) * gs * gs
        ratios = []
        for k in range(len(self.starts) - 1):
            sl = slice(self.starts[k], self.starts[k + 1])
            decay = 0.0 if self.no_decay[k] else wd
            u = ((m[sl] / (1 - b1 ** t))
                 / (np.sqrt(v[sl] / (1 - b2 ** t)) + eps) + decay * p[sl])
            pn, un = np.linalg.norm(p[sl]), np.linalg.norm(u)
            r = pn / un if not self.no_adapt[k] and pn > 0 and un > 0 else 1.0
            ratios.append(r)
            p[sl] -= lr * r * u
        return ratios


def _bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), as float64."""
    bits = np.asarray(x, np.float32).view(np.uint32)
    bits = (bits + np.uint32(0x7FFF) + ((bits >> np.uint32(16)) & np.uint32(1))
            ) & np.uint32(0xFFFF0000)
    return bits.view(np.float32).astype(np.float64)


def _muon_matrix_step(p, mom, g, lr, mu, wd):
    """One Muon step of one matrix in float64 outside the products, whose
    operands are rounded to bfloat16 (``ops/muon.py`` has the recurrence):
    returns the new parameters and momentum."""
    a, b, c = 3.4445, -4.7750, 2.0315
    mom = mu * mom + g
    x = _bf16(g + mu * mom)
    tall = x.shape[0] > x.shape[1]
    x = x.T if tall else x
    x = _bf16(x / (float(np.float32(np.sqrt(np.sum(x * x)))) + 1e-7))
    for _ in range(5):
        xx = _bf16(x @ x.T)
        x = _bf16(a * x + _bf16(b * xx + c * (xx @ xx)) @ x)
    o = x.T if tall else x
    return p * (1 - lr * wd) - lr * 0.2 * np.sqrt(max(p.shape)) * o, mom


def _momentum_reference(agg_steps, n: int) -> np.ndarray:
    """The store after the ``sgd_momentum`` recurrence over the summed
    gradients ``agg_steps`` (float32, like the kernel)."""
    store = np.zeros(n, np.float32)
    mom = np.zeros(n, np.float32)
    for agg in agg_steps:
        mom = np.float32(MOMENTUM) * mom + agg
        store = store - np.float32(LR) * mom
    return store


class _Smoke:
    def __init__(self, mesh, sizes: Sizes):
        self.mesh = mesh
        self.sizes = sizes
        self.n_dev = int(mesh.devices.size)
        self.on_tpu = next(iter(mesh.devices.flat)).platform == "tpu"
        self.kv = None
        self.server = None

    def phases(self) -> List[Tuple[str, float, Callable[[], None]]]:
        return [
            ("boot", 60, self.boot),
            ("resnet50", 400, self.resnet50),
            ("readme", 150, self.readme),
            ("lamb", 150, self.lamb),
            ("mixed", 150, self.mixed),
            ("muon", 150, self.muon),
            ("sparse", 200, self.sparse),
            ("message_path", 30, self.message_path),
            ("shutdown", 30, self.shutdown),
        ]

    # -- boot / shutdown -----------------------------------------------------

    def boot(self) -> None:
        """Scheduler + one joint (server and worker) node in this process,
        each through ``ps.start_ps``, over the in-process ICI van."""
        import pslite_tpu as ps

        env = ps.environment.Environment({
            "PS_VAN_TYPE": "ici",
            "PS_ICI_SERVER_HANDLE": SERVER_HANDLE,
            "DMLC_NUM_WORKER": "1",
            "DMLC_NUM_SERVER": "1",
            "DMLC_PS_ROOT_URI": "chip_smoke",
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
        self.server = ps.KVServer(0)
        self.server.set_request_handle(ps.KVServerDefaultHandle())
        self.kv = ps.KVWorker(0, 0)
        eng = self.kv.engine
        check(eng is not None, "the ici van built no collective engine")
        got = [d.id for d in eng.mesh.devices.flat]
        want = [d.id for d in self.mesh.devices.flat]
        check(got == want, f"engine mesh {got} is not the given mesh {want}")
        print(f"  engine: {self.n_dev} device(s), worker sum W = "
              f"{eng.num_workers}, kernels "
              f"{'interpreted' if eng._interpret else 'compiled (Mosaic)'}")
        check(eng._interpret == (not self.on_tpu),
              "kernels interpreted on a TPU mesh, or compiled off one")

    def shutdown(self) -> None:
        import pslite_tpu as ps

        ps.finalize()
        self.server.stop()

    # -- dense: ResNet-50 trace ---------------------------------------------

    def resnet50(self) -> None:
        kv, eng = self.kv, self.kv.engine
        W = eng.num_workers
        buckets = list(self.sizes.dense_buckets)
        rng = np.random.default_rng(SEED)
        keys, grads, outs = [], [], []
        for i, (name, n) in enumerate(buckets):
            # One distinct key per bucket: the worker routes a request to
            # its bucket by (count, first key, last key).
            k = np.array([1000 + i], dtype=np.uint64)
            kv.register_dense(name, k, n)
            keys.append(k)
            grads.append(rng.standard_normal((W, n), dtype=np.float32))
            outs.append(np.zeros(n, np.float32))
        if self.on_tpu:
            self._assert_fused_kernel_in_program(buckets[0][0])

        walls = []
        for step in range(self.sizes.steps):
            t0 = time.perf_counter()
            scale = np.float32(step + 1)
            stamps = [
                kv.push_pull(k, g * scale, out)
                for k, g, out in zip(keys, grads, outs)
            ]
            for ts in stamps:
                kv.wait(ts)
            walls.append(time.perf_counter() - t0)

        for (name, n), g, out in zip(buckets, grads, outs):
            agg = g.sum(axis=0, dtype=np.float32)
            want = _momentum_reference(
                [agg * np.float32(s + 1) for s in range(self.sizes.steps)], n
            )
            check(out.shape == (n,) and np.isfinite(out).all(),
                  f"{name}: not finite")
            np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6,
                                       err_msg=name)
        if self.n_dev > 1:
            for name, _ in buckets:
                self._assert_sharded(name)
        total = sum(n for _, n in buckets)
        print(f"  {len(buckets)} buckets, {total:,} f32 parameters, "
              f"{self.sizes.steps} steps agree with the momentum "
              f"recurrence at W = {W}")
        print("  set-up: first step (compiles) "
              f"{walls[0]:.2f} s; later steps "
              + ", ".join(f"{w:.2f} s" for w in walls[1:]))

    def _assert_fused_kernel_in_program(self, name: str) -> None:
        """The fused optimizer must be a Mosaic kernel in the program the
        chip runs, not an interpreted stand-in."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        eng = self.kv.engine
        bucket = eng.bucket(name)
        store = eng.store_spec(name)
        grads = jax.ShapeDtypeStruct(
            (eng.num_workers, bucket.padded_len), store.dtype,
            sharding=NamedSharding(eng.mesh, P(eng.axis, None)),
        )
        prog = eng._program("push_pull_st", bucket.padded_len,
                            bucket.dtype, SERVER_HANDLE)
        text = prog.lower(store, store, grads).as_text()
        check("tpu_custom_call" in text,
              "push_pull_st lowered for a TPU mesh holds no tpu_custom_call: "
              "the fused optimizer kernel is not in the program")

    def _assert_sharded(self, name: str) -> None:
        """Every device holds 1/N of the store and of each optimizer
        slot."""
        eng = self.kv.engine
        want_devices = {d.id for d in self.mesh.devices.flat}
        kind, slots = eng.opt_state(name)
        check(kind == "sgd_momentum", f"{name}: optimizer state {kind!r}")
        for what, arr in (("store", eng.store_array(name)),
                          *((f"slot {i}", s) for i, s in enumerate(slots))):
            shards = arr.addressable_shards
            per_dev = arr.shape[0] // self.n_dev
            check({s.device.id for s in shards} == want_devices,
                  f"{name} {what}: not on every device")
            check(all(s.data.shape == (per_dev,) for s in shards),
                  f"{name} {what}: shards {[s.data.shape for s in shards]}, "
                  f"want 1/{self.n_dev} = {per_dev} each")

    # -- dense: the README's snippet ----------------------------------------

    def readme(self) -> None:
        kv = self.kv
        W = kv.engine.num_workers
        sz = self.sizes
        keys = np.arange(sz.readme_keys, dtype=np.uint64)
        total = sz.readme_keys * sz.readme_val_len
        kv.register_dense("grads", keys, val_len=sz.readme_val_len)
        grads = np.ones(total, np.float32)  # every worker's gradient
        outs = np.zeros_like(grads)
        agg = np.full(1, W, np.float32)
        walls = []
        for step in range(sz.steps):
            t0 = time.perf_counter()
            kv.wait(kv.push_pull(keys, grads, outs))
            walls.append(time.perf_counter() - t0)
            want = _momentum_reference([agg] * (step + 1), 1)[0]
            np.testing.assert_allclose(outs, want, rtol=1e-5,
                                       err_msg=f"push_pull {step}")
        kv.wait(kv.push(keys, grads))
        pulled = np.zeros_like(grads)
        kv.wait(kv.pull(keys, pulled))
        want = _momentum_reference([agg] * (sz.steps + 1), 1)[0]
        np.testing.assert_allclose(pulled, want, rtol=1e-5,
                                   err_msg="push then pull")
        if self.n_dev > 1:
            self._assert_sharded("grads")
        print(f"  {sz.readme_keys} keys x {sz.readme_val_len:,} f32 in one "
              f"bucket: {sz.steps} push_pull, then push and pull, agree")
        print("  set-up: first push_pull (compiles) "
              f"{walls[0]:.2f} s; later "
              + ", ".join(f"{w:.2f} s" for w in walls[1:]))

    # -- dense: keys of their own lengths under LAMB -------------------------

    def lamb(self) -> None:
        """Two steps of ``lamb`` on a bucket registered with ``lens``: keys
        of 3 and 2 values, one on no lane border, one that crosses every
        shard's border (so that on more than one chip the norms' ``psum``
        crosses chips), one left out of decay and adaptation; against the
        recurrence in float64: the pulled values, and m and v.  On a TPU
        one key more, between those: too long for VMEM to hold
        (``fused_update.LAMB_HELD_TILES``), 130 tiles over however many
        shards; on one chip it takes two passes, and with it the two short
        keys in its last tile, while the keys before it and one more
        behind take one.  Then, on one chip, a short key and a long one
        that share tile 0, so that every tile is walked and no key held:
        the two passes, and m and v whole after them."""
        from pslite_tpu.ops.fused_update import LAMB_HELD_TILES, LAMB_TILE
        from pslite_tpu.parallel.engine import KEY_NO_ADAPT, KEY_NO_DECAY

        eng = self.kv.engine
        one_shard = eng.num_shards == 1
        lens = [3, 30522, LAMB_TILE * self.n_dev + 77, 1000, 2]
        flags = [0, 0, 0, KEY_NO_DECAY | KEY_NO_ADAPT, 0]
        # (The interpreter would take minutes over the long key.)
        long = (LAMB_HELD_TILES + 1) * LAMB_TILE + 5 if self.on_tpu else 0
        two_pass = 0
        if long:
            lens.insert(3, long)
            flags.insert(3, 0)
            if one_shard:
                lens.append(LAMB_TILE + 5)
                flags.append(0)
                two_pass = long + 1000 + 2
        total = sum(lens)
        ratios = self._lamb_steps("lamb_tree", 5000, lens, flags, 2)
        check(eng.lamb_one_pass == (total - two_pass if one_shard else 0),
              f"one pass over {eng.lamb_one_pass:,} of {total:,} values")
        print(f"  {len(lens)} keys of {', '.join(f'{n:,}' for n in lens)} "
              f"values ({eng.bucket('lamb_tree').padded_len:,} padded) over "
              f"{self.n_dev} device(s): 2 steps under {LAMB_HANDLE} agree, "
              f"{eng.lamb_one_pass:,} values in one pass; trust ratios "
              + ", ".join(f"{r:.3f}" for r in ratios))
        if long and one_shard:
            self._lamb_steps("lamb_pair", 5100, [300, long], [0, 0], 1)
            check(eng.lamb_one_pass == 0, "a key in walked tiles alone is "
                  f"held: one pass over {eng.lamb_one_pass:,} values")
            print(f"  2 keys of 300, {long:,} values in one tile 0: the two "
                  f"passes, m and v whole")

    def _lamb_steps(self, name: str, key0: int, lens, flags,
                    steps: int) -> list:
        """Register ``name`` with ``lens`` and ``flags``, take ``steps``
        steps of ``push_pull`` under LAMB and hold the pulled values, the
        store, m and v to the float64 recurrence; the last step's trust
        ratios."""
        import jax.numpy as jnp

        kv, eng = self.kv, self.kv.engine
        W = eng.num_workers
        lens, flags = np.array(lens), np.array(flags)
        keys = np.arange(key0, key0 + len(lens), dtype=np.uint64)
        starts = np.concatenate([[0], np.cumsum(lens)])
        total = int(lens.sum())
        rng = np.random.default_rng(SEED)
        init = (0.02 * rng.standard_normal(total)).astype(np.float32)
        kv.register_dense(name, keys, lens=lens, flags=flags, init=init)
        check(kv._engine_route(keys, 0, lens) == name,
              "a call with the registered lens is the engine's")
        ref = _LambRecurrence(init, starts, flags)
        before, pulls_before = eng.lamb_updates, eng.kernel_pulls
        for t in range(1, steps + 1):
            g = rng.standard_normal((W, total)).astype(np.float32)
            # Where W gradients cancel to within eps, u = gs / (|gs| + eps)
            # turns on the last bits of the sum, and the f32 sum of the
            # chips parts from the recurrence's float64 sum by more than
            # the tolerance: 4 of 8.7 M did.  No sum lies there.
            g[0, np.abs(g.sum(axis=0, dtype=np.float64)) < 1e-3] += 0.5
            # Host-origin, then device-origin: at the keys' own length.
            sent = g if t == 1 else jnp.asarray(g)
            pulled = np.asarray(eng.push_pull(name, sent, LAMB_HANDLE))
            ratios = ref.step(g)
            np.testing.assert_allclose(pulled, ref.p, atol=2e-6,
                                       err_msg=f"{name} step {t}")
        check(eng.lamb_updates - before == steps, "every op ran under LAMB")
        # One shard holds the bucket whole: the kernel wrote the pulled
        # values itself; over several they are the gathered shards, cut.
        np.testing.assert_array_equal(
            pulled, np.asarray(eng.store_array(name))[:total],
            err_msg="the pulled values are the store's")
        check(eng.kernel_pulls - pulls_before
              == (steps if eng.num_shards == 1 else 0),
              "the kernel's pulled values on one shard and there alone")
        _, (m, v, _) = eng.opt_state(name)
        for what, got, want in (("m", m, ref.m), ("v", v, ref.v)):
            got = np.asarray(got)
            np.testing.assert_allclose(got[:total], want, rtol=1e-5,
                                       atol=1e-6, err_msg=f"{name}: {what}")
            check(not got[total:].any(), f"{name}: {what} of the padding")
        return ratios

    def mixed(self) -> None:
        """Two steps of ``lamb`` on a bucket whose job's dtype (bfloat16)
        is narrower than its store's (float32), the keys the ``lamb``
        phase's: the f32 store against the float64 recurrence fed the bf16
        gradients widened, the pulled bf16 values bit-equal to the store's
        rounding (from ``lamb_apply`` itself on one chip, from the rounded
        shards gathered on several), m and v f32, ``pull`` alone, and the
        refusals: a gradient of another dtype, a stateless handle."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from pslite_tpu.ops.fused_update import LAMB_TILE
        from pslite_tpu.parallel.engine import KEY_NO_ADAPT, KEY_NO_DECAY
        from pslite_tpu.utils import logging as log

        eng = self.kv.engine
        W = eng.num_workers
        bf16 = np.dtype(jnp.bfloat16)
        lens = np.array([3, 30522, LAMB_TILE * self.n_dev + 77, 1000, 2])
        flags = np.array([0, 0, 0, KEY_NO_DECAY | KEY_NO_ADAPT, 0])
        keys = np.arange(6000, 6000 + len(lens), dtype=np.uint64)
        starts = np.concatenate([[0], np.cumsum(lens)])
        total = int(lens.sum())
        rng = np.random.default_rng(SEED + 7)
        init = (0.02 * rng.standard_normal(total)).astype(np.float32)
        bucket = self.kv.register_dense(
            "mixed_tree", keys, lens=lens, flags=flags, init=init,
            dtype=jnp.float32, job_dtype=jnp.bfloat16)
        check(bucket.mixed and bucket.nbytes == 2 * total,
              "the bucket counts the job's bytes")
        ref = _LambRecurrence(init, starts, flags)
        narrow, pulls = eng.narrow_ops, eng.kernel_pulls

        def held(pulled, what):
            check(pulled.dtype == bf16 and pulled.shape == (total,), what)
            store = np.asarray(eng.store_array("mixed_tree"))[:total]
            np.testing.assert_array_equal(
                np.asarray(pulled).view(np.uint16),
                store.astype(bf16).view(np.uint16),
                err_msg=f"{what}: pulled is not the store rounded")
            return store

        for t in (1, 2):
            g = rng.standard_normal((W, total)).astype(bf16)
            # Host-origin rows, then the same rows on the device.
            sent = g if t == 1 else jax.device_put(
                g, NamedSharding(eng.mesh, P(eng.axis)))
            pulled = eng.push_pull("mixed_tree", sent, LAMB_HANDLE)
            ratios = ref.step(g.astype(np.float32))
            store = held(pulled, f"mixed step {t}")
            np.testing.assert_allclose(store, ref.p, atol=2e-6,
                                       err_msg=f"mixed step {t}")
        held(eng.pull("mixed_tree"), "pull alone")
        kind, (m, v, slot) = eng.opt_state("mixed_tree")
        check(m.dtype == v.dtype == jnp.float32, "m and v stay f32")
        check(eng.narrow_ops - narrow == 3, "three ops counted as narrow")
        check(eng.kernel_pulls - pulls == (2 if eng.num_shards == 1 else 0),
              "the kernel's own bf16 pulled values on one shard")
        for what, call in (
                ("an f32 device gradient", lambda: eng.push_pull(
                    "mixed_tree", jnp.zeros((W, total), jnp.float32),
                    LAMB_HANDLE)),
                ("a stateless handle", lambda: eng.push_pull(
                    "mixed_tree", g, f"sgd:{LR}"))):
            try:
                call()
            except log.CheckError as exc:
                check("'mixed_tree'" in str(exc), f"{what}: no bucket named")
            else:
                check(False, f"{what} was not refused")
        print(f"  {len(lens)} keys ({total:,} values) pushed and pulled in "
              f"bfloat16 over an f32 store on {self.n_dev} device(s): 2 "
              f"steps agree, pulled == rounded store bit for bit, ratios "
              + ", ".join(f"{r:.3f}" for r in ratios))

    # -- sparse plane ---------------------------------------------------------

    def muon(self) -> None:
        """Two steps of ``muon`` on two buckets registered with ``lens``
        and ``shapes``.  ``muon_tree``: wide, tall and square matrices, two
        of one shape (one batched product), a 64-row router and an AdamW
        key on no lane border between them, and in front of them (PR 44) a
        gain of 512 values, a wide key 512 values off a tile of 1,024 and a
        tall one of its side, each of two grid steps: those three leave the
        gradient row through ``ops/muon.py``'s kernels
        (``eng.muon_row_keys``), the others by XLA's cut; the new values
        of the three matrices on lane borders are written by
        ``row_apply`` (``eng.muon_apply_keys``), and since a key of the
        bucket is not, the pulled tree stays the program's cut.
        ``muon_apply`` (PR 45): every key a kernel's both ways, a wide and
        a tall matrix 512 values off a tile and one of each on a tile
        border, two grid steps each, AdamW keys whose m and v lie on a
        tile border and 512 off it, one of two grid steps: there the
        pulled tree is the kernels' own vector (``eng.kernel_pulls``) and
        must equal the store bit for bit.  Against the recurrence in
        float64 with the products' operands rounded to bfloat16: what the
        chip does with bf16 products, with a kernel's blocks (the
        interpreter loads an output block before the kernel runs; the chip
        does not) and with copies a kernel starts in one grid step and
        waits for in another, which tier-1 cannot see.  Two bfloat16
        computations of one recurrence differ by roundings that flip, so a
        matrix is held to 0.3 of one step's root mean square in any
        element and 0.05 in the root mean square (the chip reads 0.146 and
        0.018, a Newton-Schulz step left out 0.8); an AdamW key and the
        momentum to f32 rounding.  On more than one chip the bucket is
        sharded on its keys' borders (PR 56): every matrix whole on one
        owner, the W rows summed to the owners, the pulled tree the gather
        laid back into key order and equal to ``pull``'s bit for bit."""
        import jax.numpy as jnp

        from pslite_tpu.parallel.engine import KEY_ELEMENTWISE

        kv, eng = self.kv, self.kv.engine
        W = eng.num_workers
        lr, mu, wd, b1, b2, eps = 1e-3, 0.95, 0.1, 0.9, 0.95, 1e-8
        handle = f"muon:{lr},{mu},{wd},{b1},{b2},{eps}"
        # name, shapes, AdamW, keys through the row's kernels, keys a
        # kernel writes back.
        trees = [
            ("muon_tree",
             [(1, 512), (512, 1024), (1024, 512), (192, 512), (1, 333),
              (512, 192), (256, 256), (192, 512), (64, 2048)],
             [True, False, False, False, True, False, False, False, False],
             3, 3),
            ("muon_apply",
             [(1, 512), (512, 1024), (1024, 512), (1, 1536), (512, 1024),
              (1024, 512), (1, 524288)],
             [True, False, False, True, False, False, True], 7, 7),
        ]
        updates = 0
        for name, shapes, adamw, row_keys, apply_keys in trees:
            shapes, adamw = np.array(shapes), np.array(adamw)
            lens = shapes[:, 0] * shapes[:, 1]
            keys = np.arange(5200 + 100 * updates,
                             5200 + 100 * updates + len(lens),
                             dtype=np.uint64)
            starts = np.concatenate([[0], np.cumsum(lens)])
            total = int(lens.sum())
            rng = np.random.default_rng(SEED + updates)
            init = (0.02 * rng.standard_normal(total)).astype(np.float32)
            kv.register_dense(name, keys, lens=lens, shapes=shapes,
                              flags=np.where(adamw, KEY_ELEMENTWISE, 0),
                              init=init)
            # Over several shards the bucket lies by its owner plan (every
            # matrix whole on one shard) and the pulled tree is the gather,
            # laid back into key order: no kernel's own vector.
            one_shard = eng.num_shards == 1
            whole = apply_keys == len(lens) and one_shard
            p = [init[starts[k]:starts[k + 1]].astype(np.float64).reshape(
                shapes[k]) for k in range(len(lens))]
            mom = [np.zeros_like(x) for x in p]
            v = [np.zeros_like(x) for x in p]
            worst = [0.0, 0.0]
            pulls = eng.kernel_pulls
            for t in (1, 2):
                g = rng.standard_normal((W, total)).astype(np.float32)
                # As in ``lamb``: no sum of the W rows lies where AdamW's
                # m / (sqrt(v) + eps) turns on the f32 sum's last bits.
                g[0, np.abs(g.sum(axis=0, dtype=np.float64)) < 1e-3] += 0.5
                sent = g if t == 1 else jnp.asarray(g)
                pulled = np.asarray(eng.push_pull(name, sent, handle))
                gs = g.astype(np.float64).sum(axis=0)
                for k in range(len(lens)):
                    gk = gs[starts[k]:starts[k + 1]].reshape(shapes[k])
                    got = pulled[starts[k]:starts[k + 1]].reshape(shapes[k])
                    if adamw[k]:
                        mom[k] = b1 * mom[k] + (1 - b1) * gk
                        v[k] = b2 * v[k] + (1 - b2) * gk * gk
                        p[k] = (p[k] * (1 - lr * wd)
                                - lr * np.sqrt(1 - b2 ** t) / (1 - b1 ** t)
                                * mom[k] / (np.sqrt(v[k]) + eps))
                        np.testing.assert_allclose(
                            got, p[k], atol=2e-7,
                            err_msg=f"{name}: adamw key {k} step {t}")
                        continue
                    was = p[k]
                    p[k], mom[k] = _muon_matrix_step(p[k], mom[k], gk, lr,
                                                     mu, wd)
                    step = np.sqrt(np.mean((p[k] - was) ** 2))
                    diff = np.abs(got - p[k])
                    worst = [max(worst[0], diff.max() / step),
                             max(worst[1],
                                 np.sqrt(np.mean(diff ** 2)) / step)]
                    check(diff.max() < 0.3 * step
                          and np.sqrt(np.mean(diff ** 2)) < 0.05 * step,
                          f"{name} key {k} {tuple(shapes[k])} step {t}: off "
                          f"by {diff.max() / step:.3f} of a step at worst, "
                          f"{np.sqrt(np.mean(diff ** 2)) / step:.4f} rms")
                if whole or not one_shard:
                    check(np.array_equal(
                        pulled, np.asarray(eng.pull(name))[:total]),
                        f"{name} step {t}: the kernels' pulled vector is "
                        f"the store's values bit for bit")
            updates += 2
            check(eng.muon_updates == updates
                  and eng.muon_matrices == int((~adamw).sum()),
                  "every op ran under Muon")
            owned = eng.bucket(name).owned
            check((owned is None) == one_shard
                  and eng.muon_owners == min(eng.num_shards,
                                             int((~adamw).sum())),
                  f"{name}: laid by {eng.muon_owners} owners over "
                  f"{eng.num_shards} shards")
            if not one_shard:
                # A slot starts on a lane border whatever the key order,
                # and a key left over (all of them, where the owners
                # outnumber a shape class) leaves through no kernel: the
                # plan's own counts, a key by its slot.
                row_keys, apply_keys = (eng.muon_row_keys,
                                        eng.muon_apply_keys)
                check(owned.padded_len == eng.bucket(name).padded_len
                      and row_keys >= 1 and apply_keys >= 1,
                      f"{name}: the owners' layout")
            check(eng.muon_row_keys == row_keys and starts[1] % 1024 == 512,
                  f"{name}: {row_keys} keys leave the row through a kernel, "
                  f"not {eng.muon_row_keys}")
            check(eng.muon_apply_keys == apply_keys
                  and eng.kernel_pulls - pulls == 2 * whole,
                  f"{name}: {apply_keys} keys are written back by a kernel, "
                  f"not {eng.muon_apply_keys}; the pulled tree the kernels' "
                  f"{eng.kernel_pulls - pulls} times of 2")
            _, (m_got, am_got, av_got, slot) = eng.opt_state(name)
            np.testing.assert_allclose(
                np.asarray(m_got), np.concatenate(
                    [mom[k].reshape(-1) for k in range(len(lens))
                     if not adamw[k]]), atol=5e-6, err_msg="the momentum")
            for got, want, what in ((am_got, mom, "m"), (av_got, v, "v")):
                np.testing.assert_allclose(
                    np.asarray(got), np.concatenate(
                        [want[k].reshape(-1) for k in range(len(lens))
                         if adamw[k]]), rtol=1e-5,
                    # (The f32 sum of W rows, in another order.)
                    atol=1e-7 * max(1, W / 2),
                    err_msg=f"{name}: AdamW's {what}")
            check(float(np.asarray(slot)[0]) == 2.0, "the step slot")
            least = 4 * int(lens[~adamw].sum()) + 8 * int(lens[adamw].sum())
            check(eng.opt_state_nbytes(name)
                  == (least + 4 if one_shard
                      else owned.state_bytes + 4 * eng.num_shards) >= least,
                  "the state at its own size")
            print(f"  {name}: {len(lens)} keys "
                  f"({', '.join(f'{r}x{c}' for r, c in shapes)}): 2 steps "
                  f"under {handle} agree, at worst {worst[0]:.3f} of a step "
                  f"in an element, {worst[1]:.4f} rms; muon_row_keys "
                  f"{eng.muon_row_keys}, muon_apply_keys "
                  f"{eng.muon_apply_keys}, pulled by the kernels "
                  f"{eng.kernel_pulls - pulls} of 2; {eng.muon_owners} "
                  f"owners over {eng.num_shards} shards")

    @staticmethod
    def _fits(se, idx) -> bool:
        """Whether the exchange routed by owner holds this batch: no worker
        sends one shard more slots than a bucket has (``parallel/sparse.py``
        ``_capacity``).  An op on a batch that does not runs the gathered
        body in the same program, and ``engine.sparse.route.overflow``
        counts it.  Always on one shard, where nothing is routed."""
        W, n = idx.shape
        if not se._routed(n):
            return True
        fullest = max(np.bincount(row % W, minlength=W).max() for row in idx)
        return fullest <= se._route_slots(n) // W

    @staticmethod
    def _even_batch(rng, rows, W, batch):
        """Zipf duplicates with every owner sent the same share by every
        worker (row ``r`` is shard ``r % W``'s): the batch that fits the
        routed exchange's buckets at any size."""
        base = (rng.zipf(1.2, size=(W, batch)) - 1) % (rows // W)
        owner = (np.arange(batch) + np.arange(W)[:, None]) % W
        return (base * W + owner).astype(np.int32)

    def sparse(self) -> None:
        import pslite_tpu as ps
        from pslite_tpu.models.embedding import skewed_indices

        kv = self.kv
        sz = self.sizes
        se = ps.postoffice(ps.Role.WORKER).van.sparse_engine
        W = se.num_shards
        se.register_sparse("emb", sz.emb_rows, sz.emb_dim)
        ref = np.zeros((sz.emb_rows, sz.emb_dim), np.float32)
        rng = np.random.default_rng(SEED + 1)
        walls = []
        # Ops whose batch the routed exchange does not hold, as the host
        # counts them; the engine's count is read where a step is checked.
        over0, fell_back = se.route_overflows(), 0
        for rnd in range(2):
            idx = skewed_indices(sz.emb_rows, W, sz.emb_batch,
                                 seed=SEED + rnd)
            grads = rng.standard_normal(
                (W, sz.emb_batch, sz.emb_dim), dtype=np.float32
            )
            out = np.zeros_like(grads)
            t0 = time.perf_counter()
            kv.wait(kv.push_sparse("emb", idx, grads))
            kv.wait(kv.pull_sparse("emb", idx, out=out))
            walls.append(time.perf_counter() - t0)
            fell_back += 2 * (not self._fits(se, idx))
            np.add.at(ref, idx.reshape(-1),
                      grads.reshape(-1, sz.emb_dim))
            check(np.isfinite(out).all(), "pulled rows not finite")
            np.testing.assert_allclose(out, ref[idx], rtol=1e-4, atol=1e-3,
                                       err_msg=f"round {rnd}")
            # The Zipf head: every worker pulled row 0, and every copy of
            # it is the one aggregated row.
            hot = idx == 0
            check(hot.any(axis=1).all(), "a worker never drew the hot row")
            hot_rows = out[hot]
            check((hot_rows == hot_rows[0]).all(),
                  "the hot row differs between workers")
        check(se.route_overflows() == over0 + fell_back,
              f"overflow count {se.route_overflows() - over0} after two "
              f"Zipf rounds of which {fell_back // 2} do not fit")
        # The exchange's two bodies under the sum, whatever the Zipf rounds
        # ran: a batch every bucket holds, and one whose ids all lie on one
        # shard (the last), which must fall back, lose no slot and be
        # counted, push and pull.
        even = self._even_batch(rng, sz.emb_rows, W, sz.emb_batch)
        last = (even // W) * W + (W - 1)
        for batch, falls in ((even, False), (last, se._routed(sz.emb_batch))):
            check(self._fits(se, batch) == (not falls), "a batch's fit")
            grads = rng.standard_normal(
                (W, sz.emb_batch, sz.emb_dim), dtype=np.float32)
            out = np.zeros_like(grads)
            kv.wait(kv.push_sparse("emb", batch, grads))
            kv.wait(kv.pull_sparse("emb", batch, out=out))
            fell_back += 2 * falls
            np.add.at(ref, batch.reshape(-1), grads.reshape(-1, sz.emb_dim))
            np.testing.assert_allclose(
                out, ref[batch], rtol=1e-4, atol=1e-3,
                err_msg="a batch of one owner" if falls else "an even batch")
            check(se.route_overflows() == over0 + fell_back,
                  f"overflow count {se.route_overflows() - over0}, "
                  f"{fell_back} ops fell back")
        if W > 1:
            print(f"  routed by owner over {W} shards, "
                  f"{se._route_slots(sz.emb_batch)} slots a shard an op "
                  f"where gathered {W * sz.emb_batch}: an even batch routed, "
                  f"a batch of one owner fell back and is exact; "
                  f"engine.sparse.route.overflow {fell_back}")
        if self.n_dev > 1:
            t = se.table("emb")
            shards = se.store_raw("emb").addressable_shards
            check(len(shards) == self.n_dev and all(
                s.data.shape == (t.phys_rows, t.pack * t.dim) for s in shards
            ), f"table shards {[s.data.shape for s in shards]}")
        print(f"  {sz.emb_rows:,} x {sz.emb_dim} table, batch "
              f"{sz.emb_batch} per worker, {int(hot.sum())} pulls of the "
              f"hot row: push_sparse / pull_sparse agree")
        print(f"  set-up: first round (compiles) {walls[0]:.2f} s; "
              f"second {walls[1]:.2f} s")
        # A 64-wide table is lane-packed two rows to a 128-lane physical
        # row: on the chip both rounds were summed by physical row with
        # ops/segment_sum.py and written by ops/row_add.py.
        t = se.table("emb")
        kernels = 4 * (self.on_tpu and t.pack * t.dim == 128)
        check((se.row_kernel_pushes, se.segsum_kernel_pushes,
               se.packed_pushes) == (kernels, kernels, 4 * (t.pack != 1)),
              f"row kernel / segment sum kernel / packed pushes "
              f"{se.row_kernel_pushes} / {se.segsum_kernel_pushes} / "
              f"{se.packed_pushes} after four rounds")
        # Once under the stateful server handle and once more under the
        # sum, on tables of their own: 128 lanes, and 64 (lane-packed).  The
        # even batch, so that over several shards the routed body runs
        # under the handle; its third push is the one that falls back.
        for name, dim in (("emb_opt", sz.emb_opt_dim), ("emb_opt64", 64)):
            self._sparse_under_handle(se, name, dim, even, rng)
        self._sparse_under_handle(
            se, "emb_odd", sz.emb_opt_dim,
            self._even_batch(rng, sz.emb_odd_rows, W, sz.emb_batch), rng,
            rows=sz.emb_odd_rows)
        self._sparse_grouped(se, even, rng)

    def _sparse_grouped(self, se, idx, rng) -> None:
        """The three tables in ONE grouped push and one grouped pull
        (``push_sparse_group`` / ``pull_sparse_group``): the pull's program
        gives one array a width (two here: the two 64-wide tables' rows
        side by side), and every entry cut from it is the one-table
        ``pull_sparse`` of the same ids, bit for bit."""
        kv, sz = self.kv, self.sizes
        names = ["emb", "emb_opt", "emb_opt64"]
        dims = [se.table(n).dim for n in names]
        grads = [rng.standard_normal((se.num_shards, sz.emb_batch, d),
                                     dtype=np.float32) for d in dims]
        kv.wait(kv.push_sparse_group(names, [idx] * 3, grads))
        ts = kv.pull_sparse_group(names, [idx] * 3)
        kv.wait(ts)
        pulled = kv.get_pulled(ts)
        classes = list(dict.fromkeys(dims))
        check([a.shape[1:] for a in pulled.arrays]
              == [(sz.emb_batch * dims.count(d), d) for d in classes],
              f"a grouped pull's results {[a.shape for a in pulled.arrays]}")
        for name, rows in zip(names, pulled):
            one = kv.pull_sparse(name, idx)
            kv.wait(one)
            check((np.asarray(rows).view(np.uint32)
                   == np.asarray(kv.get_pulled(one)).view(np.uint32)).all(),
                  f"a grouped pull's rows of {name} differ from pull_sparse")
        check(np.abs(np.asarray(pulled[1])).max() > 0, "nothing was pushed")
        print(f"  one grouped push and one grouped pull of {names}: "
              f"{len(pulled.arrays)} results for {len(names)} tables, every "
              f"entry equal to its table's pull_sparse")

    def _sparse_under_handle(self, se, name, dim, idx, rng,
                             rows=None) -> None:
        """From the zero state one push of row-wise Adagrad leaves
        -lr * G / (sqrt(mean(G**2)) + eps) in every touched row; a plain
        sum into the same table then adds G; a second push under the handle
        meets the accumulators the first left (on the chip
        ops/acc_update.py reads, steps and writes them; on several chips
        each its own shard's).  ``rows``: the table's, ``emb_rows`` unless
        given; whatever they are, a shard keeps its accumulators in whole
        128s and the tail behind its rows stays zero."""
        kv, sz = self.kv, self.sizes
        W = se.num_shards
        lr, eps = 0.05, 1e-8
        table_rows = rows or sz.emb_rows
        table = se.register_sparse(name, table_rows, dim)
        handle = f"row_adagrad:{lr},{eps}"
        before = (se.row_kernel_pushes, se.packed_pushes,
                  se.segsum_kernel_pushes, se.acc_kernel_pushes,
                  se.acc_kernel_tables)
        check(self._fits(se, idx), "the batch under the handle fits")
        overflows = se.route_overflows()
        grads = rng.standard_normal((W, sz.emb_batch, dim), dtype=np.float32)
        out = np.zeros_like(grads)
        t0 = time.perf_counter()
        kv.wait(kv.push_sparse(name, idx, grads, handle))
        kv.wait(kv.pull_sparse(name, idx, out=out))
        wall = time.perf_counter() - t0
        rows, inverse = np.unique(idx.reshape(-1), return_inverse=True)
        G = np.zeros((len(rows), dim), np.float64)
        np.add.at(G, inverse, grads.reshape(-1, dim))
        want = -lr * G / (np.sqrt(np.mean(G ** 2, axis=1))[:, None] + eps)
        np.testing.assert_allclose(
            out.reshape(-1, dim), want[inverse], rtol=1e-4,
            atol=1e-6, err_msg=f"row_adagrad through push_sparse, {name}")
        acc = np.asarray(se.acc_global_device(name))
        check(np.count_nonzero(acc) == len(rows),
              "accumulator rows touched != rows pushed")
        # The kernels take physical rows of 128 f32 lanes, packed or not:
        # where one writes the table, the other summed the duplicates (a
        # lane-packed table's where they are merged by physical row).
        kernel = se.row_kernel_pushes == before[0] + 1
        check(kernel == (self.on_tpu and table.pack * dim == 128),
              f"table written by the row kernel: {kernel}")
        check(se.segsum_kernel_pushes == before[2] + kernel,
              f"segment sum kernel pushes {se.segsum_kernel_pushes}")
        # The accumulator is by logical row whatever the table's packing:
        # at these sizes every push lowered for a TPU takes its kernel.
        check(se.acc_kernel_pushes == before[3] + self.on_tpu,
              f"accumulator kernel pushes {se.acc_kernel_pushes}")
        print(f"  one push under {handle} through "
              f"push_sparse, {table_rows:,} x {dim} (pack {table.pack}), "
              f"duplicates summed by "
              f"{'ops/segment_sum.py' if kernel else 'XLA scatter-add'}, "
              f"the table written by "
              f"{'ops/row_add.py' if kernel else 'XLA scatter'}: "
              f"{len(rows):,} distinct rows and their accumulators agree "
              f"({wall:.2f} s, compiles)")
        # And the plain sum into the same table: where the kernel takes the
        # rows, a push with no handle is written by distinct row too.
        kv.wait(kv.push_sparse(name, idx, grads))
        kv.wait(kv.pull_sparse(name, idx, out=out))
        np.testing.assert_allclose(
            out.reshape(-1, dim), (want + G)[inverse], rtol=1e-4,
            atol=1e-5, err_msg=f"the sum after row_adagrad, {name}")
        check((se.row_kernel_pushes == before[0] + 2) == kernel,
              f"row kernel pushes {se.row_kernel_pushes} after the sum")
        check(se.segsum_kernel_pushes == before[2] + 2 * kernel,
              f"segment sum kernel pushes {se.segsum_kernel_pushes} after "
              f"the sum")
        check(se.packed_pushes == before[1] + 2 * (table.pack != 1),
              f"packed pushes {se.packed_pushes} after the sum")
        print(f"  one push with no handle into the same table, written by "
              f"{'ops/row_add.py' if kernel else 'XLA scatter'}: agrees")
        # A second step of the recurrence, in float64: the accumulators the
        # first push left are read, stepped and written back.
        grads = rng.standard_normal((W, sz.emb_batch, dim), dtype=np.float32)
        kv.wait(kv.push_sparse(name, idx, grads, handle))
        kv.wait(kv.pull_sparse(name, idx, out=out))
        G2 = np.zeros_like(G)
        np.add.at(G2, inverse, grads.reshape(-1, dim))
        acc_want = np.mean(G ** 2, axis=1) + np.mean(G2 ** 2, axis=1)
        want = want + G - lr * G2 / (np.sqrt(acc_want)[:, None] + eps)
        np.testing.assert_allclose(
            out.reshape(-1, dim), want[inverse], rtol=1e-4, atol=1e-5,
            err_msg=f"a second push under row_adagrad, {name}")
        acc = np.asarray(se.acc_global_device(name))
        np.testing.assert_allclose(
            acc[rows], acc_want, rtol=1e-5,
            err_msg=f"accumulators after two pushes, {name}")
        check(np.count_nonzero(acc) == len(rows),
              "accumulator rows touched != rows pushed, second push")
        check(se.acc_kernel_pushes == before[3] + 2 * self.on_tpu,
              f"accumulator kernel pushes {se.acc_kernel_pushes} after the "
              f"second push")
        check(se.route_overflows() == overflows,
              "a batch that fits was counted as an overflow")
        print(f"  a second push under {handle}: rows and accumulators "
              f"follow the float64 recurrence, the accumulator updated by "
              f"{'ops/acc_update.py' if self.on_tpu else 'XLA gather + scatter'}")
        # A third, every slot a distinct row of the first shard's lowest
        # rows (row r is shard r % W's row r // W): no slot is dropped, so
        # the kernel's last chunk of ids is live, and it ends in the
        # accumulator's first tile with every other tile still to pass.
        low = (W * np.arange(W * sz.emb_batch, dtype=idx.dtype)).reshape(
            W, sz.emb_batch)
        kv.wait(kv.pull_sparse(name, low, out=out))
        grads = rng.standard_normal((W, sz.emb_batch, dim), dtype=np.float32)
        G3 = grads.reshape(-1, dim).astype(np.float64)
        acc_want = acc.astype(np.float64)
        acc_want[low.reshape(-1)] += np.mean(G3 ** 2, axis=1)
        want = out.reshape(-1, dim) - lr * G3 / (
            np.sqrt(acc_want[low.reshape(-1)])[:, None] + eps)
        kv.wait(kv.push_sparse(name, low, grads, handle))
        kv.wait(kv.pull_sparse(name, low, out=out))
        np.testing.assert_allclose(
            out.reshape(-1, dim), want, rtol=1e-4, atol=1e-5,
            err_msg=f"a push of distinct low rows under row_adagrad, {name}")
        after = np.asarray(se.acc_global_device(name))
        np.testing.assert_allclose(
            after[low.reshape(-1)], acc_want[low.reshape(-1)], rtol=1e-5,
            err_msg=f"accumulators of the distinct low rows, {name}")
        quiet = np.ones(len(acc), bool)
        quiet[low.reshape(-1)] = False
        check((after[quiet] == acc[quiet]).all(),
              "an accumulator no row of the third push names has changed")
        check(se.acc_kernel_pushes == before[3] + 3 * self.on_tpu,
              f"accumulator kernel pushes {se.acc_kernel_pushes} after the "
              f"third push")
        check(se.acc_kernel_tables == before[4] + 3 * self.on_tpu,
              f"tables whose accumulator the kernel updated "
              f"{se.acc_kernel_tables} after the third push")
        kept = np.asarray(se._acc[name]).view(np.uint32).reshape(W, -1)
        check(kept.shape[1] == table.acc_rows
              and table.acc_rows == -(-table.rows_per_shard // 128) * 128,
              f"a shard keeps {kept.shape[1]:,} accumulators for "
              f"{table.rows_per_shard:,} rows")
        check(not kept[:, table.rows_per_shard:].any(),
              "the tail behind a shard's accumulators is not zero")
        # All of one owner: over several shards the two pulls and the push
        # ran the gathered body, the fallback under the handle.
        fell_back = 3 * (not self._fits(se, low))
        check(fell_back == 3 * se._routed(sz.emb_batch), "the low rows' fit")
        check(se.route_overflows() == overflows + fell_back,
              f"overflow count {se.route_overflows() - overflows} after "
              f"the third push, {fell_back} ops fell back")
        print(f"  a third, {low.size:,} distinct rows of the first shard's "
              f"lowest: rows and accumulators follow, every other "
              f"accumulator is as it was, a shard's "
              f"{table.rows_per_shard:,} kept in {table.acc_rows:,}"
              + (f"; all of one owner, its {fell_back} ops fell back to the "
                 f"gathered body and were counted" if fell_back else ""))

    # -- message path ---------------------------------------------------------

    def message_path(self) -> None:
        kv = self.kv
        keys = np.array([7777], dtype=np.uint64)
        check(kv._engine_route(keys) is None, "key 7777 is registered")
        vals = np.arange(32, dtype=np.float32)
        kv.wait(kv.push(keys, vals))
        out = np.zeros_like(vals)
        kv.wait(kv.pull(keys, out))
        np.testing.assert_array_equal(out, vals)
        print("  unregistered key 7777 answered by the KVServer handler")


def run_smoke(mesh, sizes: Sizes,
              expired: Callable[[str, float], None] = _expired) -> None:
    """Drive every phase on ``mesh`` (every local device — what the ICI
    van gives its engine).  Raises :class:`PhaseFailed` naming the first
    phase that raised; a phase that outlives its deadline calls
    ``expired(name, seconds)``."""
    for name, seconds, phase in _Smoke(mesh, sizes).phases():
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        with deadline(name, seconds, expired):
            try:
                phase()
            except Exception as exc:
                raise PhaseFailed(name) from exc
        print(f"phase {name}: ok ({time.perf_counter() - t0:.1f} s)",
              flush=True)


def main() -> int:
    t_start = time.perf_counter()
    with deadline("device", 120):
        import jax

        devices = jax.devices()
    d0 = devices[0]
    print(f"platform: {d0.platform}")
    print(f"device_kind: {d0.device_kind}")
    print(f"devices: {len(devices)}")
    print(f"jax: {jax.__version__}", flush=True)
    if d0.platform != "tpu":
        print("chip_smoke: JAX found no TPU — this script has no CPU mode "
              "(tests/test_chip_smoke.py is the CPU drive)",
              file=sys.stderr)
        return 2

    from pslite_tpu.parallel.mesh import default_mesh
    from pslite_tpu.utils.compile_cache import (cache_counts,
                                                enable_compile_cache)

    cache_dir = enable_compile_cache()
    try:
        with deadline("run", 1150):
            run_smoke(default_mesh(), Sizes())
    except PhaseFailed as failed:
        traceback.print_exception(failed.__cause__)
        print(f"chip_smoke: FAILED phase={failed}", file=sys.stderr,
              flush=True)
        sys.stdout.flush()
        # Not sys.exit: a thread still inside a failed device call must
        # not hold the interpreter open.
        os._exit(1)
    print(f"set-up: compile cache {cache_dir}: {cache_counts[0]} hits, "
          f"{cache_counts[1]} misses; whole run "
          f"{time.perf_counter() - t_start:.1f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
