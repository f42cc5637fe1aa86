"""``dense_tree_mixed_push_pull``: the closed-loop driver of a gradient tree
handed over whole in mixed precision: the job's gradients and parameters
are of the configuration's ``job_dtype`` (bfloat16), the server's store,
moments, sums and norms of its ``dtype`` (float32).

It is ``dense_tree_push_pull`` (one bucket of keys with their own lengths,
one ``KVWorker.push_pull`` and one ``wait`` a step, the store initialised
from ``--seed``, ``lamb_reference.py`` following whole keys) with the job's
side narrowed: the bucket is registered with ``job_dtype=``, the gradient is
one device array ``[W, parameters]`` of the job's dtype, a row a worker as
a jitted backward pass leaves it (``bfloat16[1, 336226108]`` on one chip),
``get_pulled`` is a vector ``[parameters]`` of the job's dtype, payload and
byte counters count the job's bytes.

The comparison reads the f32 STORE against the float64 reference (fed the
bf16 gradients widened), since the store is what the configuration's
precision is about and a pulled bf16 value cannot tell an f32 master from a
bf16 one; the pulled values are held to the store (bit-equal to its
rounding) and to the reference (within half a bf16 step).
"""

import inspect
import time
from typing import Dict, List, Tuple

import numpy as np

import harness
from driver_base import CHECKED_STEPS, Comparison
from lamb_bytes import over_vmem
from lamb_mixed_bytes import dense_lamb_mixed_step
from lamb_reference import NO_ADAPT, NO_DECAY, LambReference
from reference import Rounding, bf16, scaled_error

TreeDriver = harness.load_driver(harness.search_dirs(),
                                 "dense_tree_push_pull")

from pslite_tpu.parallel.engine import (KEY_NO_ADAPT, KEY_NO_DECAY,  # noqa: E402
                                        CollectiveEngine)

if "job_dtype" not in inspect.signature(
        CollectiveEngine.register_dense).parameters:
    # A checkout from before a bucket's job and store could differ in
    # dtype cannot run this cell: say so where the driver is loaded,
    # before anything boots.
    raise RuntimeError(
        "this checkout's engine keeps one dtype a dense bucket "
        "(register_dense has no job_dtype=): it cannot run a cell whose "
        "job pushes and pulls another dtype than the server stores")


def _not_rounded(pulled: np.ndarray, store: np.ndarray) -> int:
    """How many pulled bfloat16 values are not, bit for bit, the f32
    ``store``'s values rounded to nearest-even (``reference.bf16``: numpy's
    own integers, nothing of the program's or of ``ml_dtypes``)."""
    want = (bf16(store).astype(np.float32).view(np.uint32)
            >> np.uint32(16)).astype(np.uint16)
    got = np.ascontiguousarray(pulled).view(np.uint16)
    return int(np.count_nonzero(got != want))


class Driver(TreeDriver):
    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        import jax.numpy as jnp

        super().__init__(cluster, config, traffic, seed)
        self.job_dtype = jnp.dtype(config["job_dtype"])
        self.store_dtype = jnp.dtype(config["dtype"])
        if self.job_dtype != jnp.bfloat16:
            raise ValueError("the comparison rounds as bfloat16 does; the "
                             f"configuration states {self.job_dtype}")
        self._check_store: Dict[int, list] = {}

    # -- set-up --------------------------------------------------------------

    @property
    def payload_bytes_per_step(self) -> int:
        """One worker's push plus its pull, in the job's dtype."""
        return 2 * self.job_dtype.itemsize * self.params_total

    def expected_counters(self, steps: int) -> Tuple[int, int]:
        one = self.job_dtype.itemsize * self.params_total * steps
        return one, one

    def least_bytes(self) -> Dict[str, float]:
        return dense_lamb_mixed_step(
            self.params_total, self.W,
            over_vmem(self.lens, self.W, self.store_dtype.itemsize),
            self.job_dtype.itemsize, self.store_dtype.itemsize)

    def _generator(self):
        """The dense driver's gradients (a pure function of seed, index,
        worker and element), rows ``[W, parameters]`` a worker's device
        each, rounded to the job's dtype."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.eng.mesh, P(self.eng.axis, None))
        words = np.random.SeedSequence(self.seed).generate_state(
            2, np.uint32)
        job = self.job_dtype
        # The seed is an argument, not a constant of the program; every
        # gradient of this driver is of the tree's length: one program.
        prog = jax.jit(
            lambda w, i: self._rows(w, i, self.params_total).astype(job),
            out_shardings=sharding)

        def gen(index: int, total: int):
            assert total == self.params_total
            return prog(words, np.uint32(index))

        return gen

    def setup(self) -> Dict[str, float]:
        import jax

        t0 = time.perf_counter()
        init = np.concatenate([self._init_key(k)
                               for k in range(len(self.lens))])
        flags = np.where(self.excluded, KEY_NO_DECAY | KEY_NO_ADAPT, 0)
        self.kv.register_dense(self.BUCKET, self.keys, lens=self.lens,
                               flags=flags, init=init,
                               dtype=self.store_dtype,
                               job_dtype=self.job_dtype)
        del init
        t1 = time.perf_counter()
        self._gen = self._generator()
        self.grad = self._gen(0, self.params_total)
        self.sampled = self._sample()
        for k in self.sampled:
            self._check_grads[k], self._check_pulled[k] = [], []
            self._check_store[k] = []
        jax.block_until_ready(self.grad)
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    # -- the step is the tree driver's ----------------------------------------

    def _key_rows(self, grad, k: int) -> np.ndarray:
        """Key k's gradient ``[W, n_k]``, widened exactly (every bfloat16
        is a float32)."""
        return np.asarray(grad[:, self._slice(k)]).astype(np.float32)

    def _store_keys(self, keys: List[int]) -> Dict[int, np.ndarray]:
        """The f32 store of ``keys`` as it stands (a snapshot of the whole
        store is taken and let go: the engine hands out no part)."""
        store = self.eng.store_array(self.BUCKET)
        out = {k: np.asarray(store[self._slice(k)]) for k in keys}
        del store
        return out

    def checked_steps(self) -> None:
        """The first three steps from the initialised store, each with a
        gradient of its own; of the sampled keys the gradients, the pulled
        values and the store are kept on the host."""
        for s in range(CHECKED_STEPS):
            grad = self._gen(s + 1, self.params_total)
            self.step(grad)
            for k in self.sampled:
                self._check_grads[k].append(self._key_rows(grad, k))
                self._check_pulled[k].append(
                    np.asarray(self.params[self._slice(k)]))
            del grad
            for k, part in self._store_keys(self.sampled).items():
                self._check_store[k].append(part)

    # -- the comparison ------------------------------------------------------

    def _collect(self) -> dict:
        limit = int(self.traffic["followed_key_elements"])
        followed = [k for k in self.sampled if self.lens[k] <= limit]
        out = {"followed": followed, "grad": {}, "pulled": {}}
        for k in followed:
            # Summed over W once, in float64: the same sum every step.
            out["grad"][k] = self._key_rows(self.grad, k).astype(
                np.float64).sum(axis=0)
        for k in self.sampled:
            out["pulled"][k] = np.asarray(self.params[self._slice(k)])
        pulled_dtype = self.params.dtype
        self.grad = self.params = None
        store = self.eng.store_array(self.BUCKET)
        per_dev = store.shape[0] // self.W
        bad = self._bad_shards(store, per_dev)
        wrong = int(store.dtype != self.store_dtype)
        wrong += int(pulled_dtype != self.job_dtype)
        out["store"] = {k: np.asarray(store[self._slice(k)])
                        for k in self.sampled}
        del store
        nonfinite = sum(float(part.size - np.isfinite(part).sum())
                        for part in out["store"].values())
        kind, (m, v, slot) = self.eng.opt_state(self.BUCKET)
        bad += self._bad_shards(m, per_dev) + self._bad_shards(v, per_dev)
        wrong += sum(int(a.dtype != self.store_dtype) for a in (m, v))
        out.update(
            slot_gap=float(np.max(np.abs(np.asarray(slot)
                                         - self.steps_done))),
            nonfinite=nonfinite, bad_shards=float(bad),
            wrong_dtypes=float(wrong))
        return out

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        """Run after the window.  With ``rounding`` the numbers are the
        control's: the reference with every stored value in lower
        precision (a 16-bit master and moments), put in the store's
        place."""
        if self._after is None:
            self._after = self._collect()
        after = self._after
        lr = self.hyper["lr"]
        flags = [NO_DECAY | NO_ADAPT if self.excluded[k] else 0
                 for k in self.sampled]

        def start(**kw):
            return LambReference([self._init_key(k) for k in self.sampled],
                                 flags, **self.hyper, **kw)

        ref = start()
        ctl = start(rounding=rounding) if rounding is not None else None
        first3 = pulled_err = 0.0
        mismatches = 0

        def held(store, pulled, want):
            """The pulled values against the store's rounding (bits) and
            against the reference."""
            nonlocal pulled_err, mismatches
            mismatches += _not_rounded(pulled, store)
            pulled_err = max(pulled_err, scaled_error(
                pulled.astype(np.float64), want, lr))

        for s in range(CHECKED_STEPS):
            want = ref.step([self._check_grads[k][s] for k in self.sampled])
            if ctl is not None:
                got = ctl.step([self._check_grads[k][s]
                                for k in self.sampled])
                for g, w in zip(got, want):
                    first3 = max(first3, scaled_error(g, w, lr))
                    pulled_err = max(pulled_err,
                                     scaled_error(bf16(g), w, lr))
                continue
            for k, w in zip(self.sampled, want):
                store = self._check_store[k][s]
                first3 = max(first3, scaled_error(store, w, lr))
                held(store, self._check_pulled[k][s], w)
        # Every later step pushed the window's gradient: follow the whole
        # of each key that is small enough through all of them.
        where = [self.sampled.index(k) for k in after["followed"]]
        sums = [after["grad"][k] for k in after["followed"]]
        for r in (ref, ctl):
            if r is not None:
                r.keep(where)
                for _ in range(self.steps_done - CHECKED_STEPS):
                    r.step(sums)
        final = 0.0
        for i, k in enumerate(after["followed"]):
            if ctl is not None:
                final = max(final, scaled_error(ctl.p[i], ref.p[i], lr))
                pulled_err = max(pulled_err, scaled_error(
                    bf16(ctl.p[i]), ref.p[i], lr))
                continue
            final = max(final, scaled_error(after["store"][k], ref.p[i], lr))
            held(after["store"][k], after["pulled"][k], ref.p[i])
        if ctl is None:
            # The keys too large to follow: the last pulled values against
            # the last store, bits alone.
            for k in self.sampled:
                if k not in after["followed"]:
                    mismatches += _not_rounded(after["pulled"][k],
                                               after["store"][k])
        out = [
            ("first3_err", first3, self.limits["first3_err"]),
            ("final_err", final, self.limits["final_err"]),
            ("pulled_err", pulled_err, self.limits["pulled_err"]),
        ]
        if rounding is None:
            out += [
                ("pulled_not_rounded_store", float(mismatches), 0.0),
                ("lamb_step_slot_gap", after["slot_gap"], 0.0),
                ("nonfinite_in_sampled_stores", after["nonfinite"], 0.0),
                ("shards_not_1_over_W", after["bad_shards"], 0.0),
                ("store_or_moments_not_f32_or_pulled_not_job_dtype",
                 after["wrong_dtypes"], 0.0),
            ]
        return out
