"""``dense_push_pull``: the closed-loop driver of a gradient tree cut into
buckets.

A traffic file names its driver (``"driver": "dense_push_pull"``) and gives
its parameters; the configuration file gives the sizes.  A driver owns the
cell's inputs (made on the device from ``--seed``), one ``step`` that the
set-up, the check and the measured window all call, and the comparison with
the plain reference.  All traffic goes through ``KVWorker``.

One client, closed loop: step t+1 is issued when every pulled array of step
t is ready — a synchronous training job.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from buckets import expand_tensors, make_buckets
from driver_base import CHECKED_STEPS, Comparison, _Driver
from least_bytes import dense_adam_step
from reference import (AdamReference, Rounding, parse_adam_handle,
                       scaled_error)


class DenseDriver(_Driver):
    """``device-buckets``: every bucket's ``[W, padded]`` gradient lives on
    the device, as a TPU job's backward pass leaves it; a step is one
    ``push_pull`` per bucket, then a ``wait`` on each."""

    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        self.kv = cluster.kv
        self.eng = cluster.engine
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.W = int(self.eng.num_workers)
        tensors = expand_tensors(config["tensors"])
        self.params_total = sum(n for _, n in tensors)
        want = config.get("parameters")
        if want is not None and want != self.params_total:
            raise ValueError(
                f"tensor list sums to {self.params_total:,}, the "
                f"configuration states {want:,}")
        self.sizes = make_buckets([n for _, n in tensors],
                                  int(traffic["bucket_elements"]))
        self.adam = parse_adam_handle(config["server_handle"])
        self.limits = config["limits"]
        self.keys: List[np.ndarray] = []
        self.names: List[str] = []
        self.grads: list = []
        self.params: list = [None] * len(self.sizes)
        self.steps_done = 0
        self.sampled: List[int] = []
        self._check_grads: Dict[int, list] = {}
        self._check_pulled: Dict[int, list] = {}

    # -- set-up --------------------------------------------------------------

    @property
    def payload_bytes_per_step(self) -> int:
        """One worker's push plus its pull (how ``tests/test_benchmark.cc``
        of the reference counts goodput)."""
        return 2 * 4 * self.params_total

    def least_bytes(self) -> Dict[str, float]:
        return dense_adam_step(self.params_total, self.W)

    def counters(self) -> Tuple[int, int]:
        return int(self.eng.push_bytes), int(self.eng.pull_bytes)

    def expected_counters(self, steps: int) -> Tuple[int, int]:
        return 4 * self.params_total * steps, 4 * self.params_total * steps

    def _generator(self) -> Callable:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.eng.mesh, P(self.eng.axis, None))
        # The seed is an argument, not a constant of the program: a program
        # that holds the seed is compiled anew for every seed.
        words = np.random.SeedSequence(self.seed).generate_state(
            2, np.uint32)
        programs: Dict[int, Callable] = {}

        def gen(index: int, padded: int):
            prog = programs.get(padded)
            if prog is None:
                prog = jax.jit(
                    lambda w, i: self._rows(w, i, padded),
                    out_shardings=sharding)
                programs[padded] = prog
            return prog(words, np.uint32(index))

        return gen

    def _rows(self, words, index, padded: int):
        """One bucket's gradient ``[W, padded]``: a direction in (-1, 1)
        per element, times a factor in (0.5, 1.5) of each worker's own.
        The rows all differ, and in every element they agree in sign, as
        the gradients of data-parallel replicas of one model mostly do.

        Independent rows would not do: in a few of 8 M compared elements
        their f32 sum cancels to ~1e-7, Adam's ``m / (sqrt(v) + eps)`` is
        then ill-conditioned, and the comparison reads the conditioning
        (0.02 and 0.16 of a learning rate on two seeds, four chips, my
        chip run, PR 23) instead of the program.

        The values are an integer hash of (seed, bucket, worker, element),
        one fused pass: ``jax.random`` took 12 ms a bucket on the chip (6 s
        of every run's set-up on one chip), and a pure function of the
        indices is the same under any sharding."""
        import jax.numpy as jnp
        from jax import lax

        def mix(x):  # lowbias32 (Wellons): a 32-bit avalanche in 5 ops
            x = x ^ (x >> 16)
            x = x * jnp.uint32(0x7FEB352D)
            x = x ^ (x >> 15)
            x = x * jnp.uint32(0x846CA68B)
            return x ^ (x >> 16)

        def unit(bits):  # 24 bits -> the open interval (0, 1)
            return ((bits >> 8).astype(jnp.float32) + 0.5) * (2.0 ** -24)

        e = lax.broadcasted_iota(jnp.uint32, (1, padded), 1)
        w = lax.broadcasted_iota(jnp.uint32, (self.W, 1), 0)
        base = 2.0 * unit(mix(e ^ mix(words[0] + index))) - 1.0
        scale = 0.5 + unit(mix((e + w * jnp.uint32(0x9E3779B9))
                               ^ mix(words[1] + index)))
        return base * scale

    def setup(self) -> Dict[str, float]:
        """Register every bucket (zero stores: the first push initialises,
        as on a ps-lite server) and make every gradient on the device.
        Returns the seconds of each part, for the set-up line."""
        import jax

        gen = self._generator()
        n_buckets = len(self.sizes)
        t0 = time.perf_counter()
        for i, n in enumerate(self.sizes):
            name = f"b{i}"
            key = np.array([1000 + i], dtype=np.uint64)
            self.kv.register_dense(name, key, n)
            self.names.append(name)
            self.keys.append(key)
        t1 = time.perf_counter()
        for i, name in enumerate(self.names):
            self.grads.append(gen(i, self.eng.bucket(name).padded_len))
        self.sampled = self._sample()
        # Steps 1..3 push gradients of their own in the sampled buckets,
        # so that m and v mix three directions and the parameters depend
        # on the gradients' sizes, not only their signs.
        for b in self.sampled:
            padded = self.eng.bucket(self.names[b]).padded_len
            self._check_grads[b] = [
                gen(n_buckets * (s + 1) + b, padded)
                for s in range(CHECKED_STEPS)
            ]
            self._check_pulled[b] = []
        jax.block_until_ready(self.grads)
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    def _sample(self) -> List[int]:
        """The first bucket, the last, one partial, and five drawn."""
        n = len(self.sizes)
        limit = int(self.traffic["bucket_elements"])
        want = int(self.traffic.get("sampled_buckets", 8))
        chosen = [0, n - 1]
        partial = [i for i in range(1, n - 1) if self.sizes[i] < limit]
        if partial:
            chosen.append(partial[len(partial) // 2])
        rest = [i for i in range(n) if i not in chosen]
        rng = np.random.default_rng(self.seed)
        rng.shuffle(rest)
        chosen.extend(rest[: max(0, want - len(chosen))])
        return sorted(set(chosen))

    # -- the step ------------------------------------------------------------

    def step(self, grads: Optional[Sequence] = None
             ) -> Tuple[float, float, float]:
        """Issue every bucket, then wait for every bucket.  Returns the
        clock at the first issue, after the last issue and after the last
        wait."""
        kv, params = self.kv, self.params
        grads = self.grads if grads is None else grads
        stamps = []
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            for i, (key, g) in enumerate(zip(self.keys, grads)):
                ts = kv.push_pull(key, g, None)
                # At once: KVWorker keeps only its last 8 device results.
                params[i] = kv.get_pulled(ts)
                stamps.append(ts)
        t1 = time.perf_counter()
        with self._span("bench_wait"):
            for ts in stamps:
                kv.wait(ts)
        t2 = time.perf_counter()
        self.steps_done += 1
        return t0, t1, t2

    def checked_steps(self) -> None:
        """The first three steps from the fresh (zero) state, through the
        window's own ``step``.  They also compile and warm every shape."""
        for s in range(CHECKED_STEPS):
            grads = list(self.grads)
            for b in self.sampled:
                grads[b] = self._check_grads[b][s]
            self.step(grads)
            for b in self.sampled:
                self._check_pulled[b].append(self.params[b])

    # -- the comparison ------------------------------------------------------

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        """Run after the window.  With ``rounding`` the numbers are the
        control's: the reference in lower precision, put in the program's
        place."""
        lim = self.limits
        lr = self.adam["lr"]
        rng = np.random.default_rng(self.seed + 1)
        slice_len = int(self.traffic.get("followed_elements", 65536))
        first3 = final = 0.0
        slot_gap = bad_shards = nonfinite = 0.0
        for b in self.sampled:
            n = self.sizes[b]
            name = self.names[b]
            ref = AdamReference(n, **self.adam)
            ctl = (AdamReference(n, **self.adam, rounding=rounding)
                   if rounding is not None else None)
            for s in range(CHECKED_STEPS):
                g = np.asarray(self._check_grads[b][s])[:, :n]
                want = ref.step(g)
                got = (ctl.step(g) if ctl is not None
                       else np.asarray(self._check_pulled[b][s]))
                first3 = max(first3, scaled_error(got, want, lr))
            # Every later step pushed the window's gradient: follow a
            # seeded slice of the bucket through all of them.
            lo = int(rng.integers(0, max(1, n - slice_len + 1)))
            sl = slice(lo, min(n, lo + slice_len))
            # Summed over W once, in float64: the same sum every step.
            g = np.asarray(self.grads[b])[:, sl].astype(np.float64).sum(
                axis=0, keepdims=True)
            for r in (ref, ctl):
                if r is not None:
                    r.p, r.m, r.v = r.p[sl], r.m[sl], r.v[sl]
                    for _ in range(self.steps_done - CHECKED_STEPS):
                        r.step(g)
            got = (ctl.p if ctl is not None
                   else np.asarray(self.params[b])[sl])
            final = max(final, scaled_error(got, ref.p, lr))
            if ctl is None:
                kind, (m, v, slot) = self.eng.opt_state(name)
                store = self.eng.store_array(name)
                slot_gap = max(slot_gap, float(np.max(np.abs(
                    np.asarray(slot) - self.steps_done))))
                nonfinite += float(
                    np.size(store) - np.isfinite(np.asarray(store)).sum())
                per_dev = store.shape[0] // self.W
                for arr in (store, m, v):
                    bad_shards += sum(
                        1 for sh in arr.addressable_shards
                        if sh.data.shape != (per_dev,))
                    bad_shards += abs(len(arr.addressable_shards) - self.W)
        out = [
            ("first3_err", first3, lim["first3_err"]),
            ("final_err", final, lim["final_err"]),
        ]
        if rounding is None:
            out += [
                ("adam_step_slot_gap", slot_gap, 0.0),
                ("nonfinite_in_sampled_stores", nonfinite, 0.0),
                ("shards_not_1_over_W", float(bad_shards), 0.0),
            ]
        return out


Driver = DenseDriver
