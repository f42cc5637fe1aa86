"""``dense_tree_push_pull``: the closed-loop driver of a gradient tree handed
over whole, under a server handle that treats keys apart (``lamb:...``).

One key a tensor, in the order of the configuration's list, each with its
own length: the bucket is registered with ``lens`` (the reference's
``KVPairs.lens``) and the flags the configuration's ``no_decay_no_adapt``
rule gives, its store initialised from ``--seed`` as a ps-lite server's
first push leaves it.  A step is ONE ``KVWorker.push_pull`` of all keys with
one ``[W, parameters]`` gradient that lives on the device (a jitted backward
pass leaves every gradient at the same moment, at the length of the keys'
values: what the engine keeps behind the last key is not the job's to know),
``get_pulled``, one ``wait``.

It takes the dense driver's class through the harness's own loader and keeps
its gradient generator, payload count and byte counters.  Its own: the
registration, the step, the checked steps (a gradient of their own each,
made and let go one at a time: three more whole trees would not leave the
chip the room a deployment has) and the comparison with
``lamb_reference.py`` beside ``drivers/``, which follows whole keys.
"""

import fnmatch
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

import harness
from buckets import expand_tensors
from driver_base import CHECKED_STEPS, Comparison
from lamb_bytes import dense_lamb_step, over_vmem
from lamb_reference import (NO_ADAPT, NO_DECAY, LambReference,
                            parse_lamb_handle)
from reference import Rounding, scaled_error

try:
    from pslite_tpu.parallel.engine import KEY_NO_ADAPT, KEY_NO_DECAY
except ImportError as exc:
    # A checkout from before dense buckets kept their keys' lengths cannot
    # run this cell: say so where the driver is loaded, before anything
    # boots.
    raise RuntimeError(
        "this checkout's engine keeps no per-key lengths or flags "
        "(register_dense(..., lens=, flags=)): it cannot run a cell under "
        "a server handle that treats keys apart") from exc

DenseDriver = harness.load_driver(harness.search_dirs(), "dense_push_pull")

# The store before the first push (the configuration's ``assumed`` states
# it): weights N(0, INIT_STD^2), BERT's ``initializer_range``; the gains of
# the layer norms 1; every other key out of decay and adaptation 0.
INIT_STD = np.float32(0.02)
GAIN_KEYS = ("*.ln.g",)


def _matches(name: str, patterns) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in patterns)


class Driver(DenseDriver):
    BUCKET = "tree"

    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        self.kv = cluster.kv
        self.eng = cluster.engine
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.W = int(self.eng.num_workers)
        tensors = expand_tensors(config["tensors"])
        self.tensor_names = [name for name, _ in tensors]
        self.lens = np.array([n for _, n in tensors], dtype=np.int64)
        self.starts = np.concatenate([[0], np.cumsum(self.lens)])
        self.params_total = int(self.lens.sum())
        want = config.get("parameters")
        if want is not None and want != self.params_total:
            raise ValueError(
                f"tensor list sums to {self.params_total:,}, the "
                f"configuration states {want:,}")
        self.hyper = parse_lamb_handle(config["server_handle"])
        self.excluded = np.array([
            _matches(name, config["no_decay_no_adapt"])
            for name in self.tensor_names])
        self.keys = np.arange(1000, 1000 + len(tensors), dtype=np.uint64)
        self.limits = config["limits"]
        self.grad = None
        self.params = None
        self.steps_done = 0
        self.sampled: List[int] = []
        self._gen = None
        self._check_grads: Dict[int, list] = {}
        self._check_pulled: Dict[int, list] = {}
        self._after: Optional[dict] = None

    # -- set-up --------------------------------------------------------------

    def least_bytes(self) -> Dict[str, float]:
        return dense_lamb_step(self.params_total, self.W,
                               over_vmem(self.lens, self.W))

    def _slice(self, k: int) -> slice:
        return slice(int(self.starts[k]), int(self.starts[k + 1]))

    def _init_key(self, k: int) -> np.ndarray:
        """Key k's stored value before the first push, from the seed and k
        alone: the comparison makes the sampled keys' again."""
        n = int(self.lens[k])
        if not self.excluded[k]:
            rng = np.random.default_rng([self.seed, k])
            return INIT_STD * rng.standard_normal(n, dtype=np.float32)
        gain = _matches(self.tensor_names[k], GAIN_KEYS)
        return np.full(n, 1.0 if gain else 0.0, np.float32)

    def setup(self) -> Dict[str, float]:
        """Register the tree as one bucket of keys with their own lengths,
        its store initialised (inside ``register``), and make the window's
        gradient on the device."""
        import jax

        t0 = time.perf_counter()
        init = np.concatenate([self._init_key(k)
                               for k in range(len(self.lens))])
        flags = np.where(self.excluded, KEY_NO_DECAY | KEY_NO_ADAPT, 0)
        self.kv.register_dense(self.BUCKET, self.keys, lens=self.lens,
                               flags=flags, init=init)
        del init
        t1 = time.perf_counter()
        self._gen = self._generator()
        self.grad = self._gen(0, self.params_total)
        self.sampled = self._sample()
        for k in self.sampled:
            self._check_grads[k], self._check_pulled[k] = [], []
        jax.block_until_ready(self.grad)
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    def _sample(self) -> List[int]:
        """The keys the traffic file names (the largest, one on no lane
        border, the smallest, ...) and one drawn for each of its patterns."""
        names = self.tensor_names
        chosen = [names.index(n) for n in self.traffic["always_sampled"]]
        rng = np.random.default_rng(self.seed)
        for pattern in self.traffic["drawn_sampled"]:
            hits = [k for k, n in enumerate(names)
                    if fnmatch.fnmatchcase(n, pattern) and k not in chosen]
            chosen.append(int(rng.choice(hits)))
        want = int(self.traffic.get("sampled_keys", len(chosen)))
        if len(set(chosen)) != want:
            raise ValueError(f"the traffic file samples {len(set(chosen))} "
                             f"keys and states {want}")
        return sorted(chosen)

    # -- the step ------------------------------------------------------------

    def step(self, grad=None) -> Tuple[float, float, float]:
        """One call for the whole tree, then one wait."""
        kv = self.kv
        grad = self.grad if grad is None else grad
        t0 = time.perf_counter()
        with self._span("bench_issue"):
            ts = kv.push_pull(self.keys, grad, None)
            self.params = kv.get_pulled(ts)
        t1 = time.perf_counter()
        with self._span("bench_wait"):
            kv.wait(ts)
        t2 = time.perf_counter()
        self.steps_done += 1
        return t0, t1, t2

    def checked_steps(self) -> None:
        """The first three steps from the initialised store, each with a
        gradient of its own, through the window's own ``step``; the sampled
        keys' gradients and pulled values are kept on the host."""
        for s in range(CHECKED_STEPS):
            grad = self._gen(s + 1, self.params_total)
            self.step(grad)
            for k in self.sampled:
                sl = self._slice(k)
                self._check_grads[k].append(np.asarray(grad[:, sl]))
                self._check_pulled[k].append(np.asarray(self.params[sl]))
            del grad

    # -- the comparison ------------------------------------------------------

    def _collect(self) -> dict:
        """Once, after the last step: what the comparison reads of the
        device, with the gradient and the pulled tree let go before the
        state is looked at (``opt_state`` copies m and v)."""
        limit = int(self.traffic["followed_key_elements"])
        followed = [k for k in self.sampled if self.lens[k] <= limit]
        out = {"followed": followed, "grad": {}, "pulled": {}}
        for k in followed:
            sl = self._slice(k)
            # Summed over W once, in float64: the same sum every step.
            out["grad"][k] = np.asarray(self.grad[:, sl]).astype(
                np.float64).sum(axis=0)
            out["pulled"][k] = np.asarray(self.params[sl])
        self.grad = self.params = None
        store = self.eng.store_array(self.BUCKET)
        per_dev = store.shape[0] // self.W
        bad = self._bad_shards(store, per_dev)
        nonfinite = 0.0
        for k in self.sampled:
            part = np.asarray(store[self._slice(k)])
            nonfinite += float(part.size - np.isfinite(part).sum())
        del store
        kind, (m, v, slot) = self.eng.opt_state(self.BUCKET)
        bad += self._bad_shards(m, per_dev) + self._bad_shards(v, per_dev)
        out.update(
            slot_gap=float(np.max(np.abs(np.asarray(slot)
                                         - self.steps_done))),
            nonfinite=nonfinite, bad_shards=float(bad))
        return out

    def _bad_shards(self, arr, per_dev: int) -> int:
        shards = arr.addressable_shards
        return (sum(1 for sh in shards if sh.data.shape != (per_dev,))
                + abs(len(shards) - self.W))

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        """Run after the window.  With ``rounding`` the numbers are the
        control's: the reference in lower precision, put in the program's
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
        first3 = 0.0
        for s in range(CHECKED_STEPS):
            grads = [self._check_grads[k][s] for k in self.sampled]
            want = ref.step(grads)
            got = (ctl.step(grads) if ctl is not None
                   else [self._check_pulled[k][s] for k in self.sampled])
            for g, w in zip(got, want):
                first3 = max(first3, scaled_error(g, w, lr))
        # Every later step pushed the window's gradient: follow the whole
        # of each key that is small enough through all of them.
        where = [self.sampled.index(k) for k in after["followed"]]
        sums = [after["grad"][k] for k in after["followed"]]
        final = 0.0
        for r in (ref, ctl):
            if r is not None:
                r.keep(where)
                for _ in range(self.steps_done - CHECKED_STEPS):
                    r.step(sums)
        got = (ctl.p if ctl is not None
               else [after["pulled"][k] for k in after["followed"]])
        for g, w in zip(got, ref.p):
            final = max(final, scaled_error(g, w, lr))
        out = [
            ("first3_err", first3, self.limits["first3_err"]),
            ("final_err", final, self.limits["final_err"]),
        ]
        if rounding is None:
            out += [
                ("lamb_step_slot_gap", after["slot_gap"], 0.0),
                ("nonfinite_in_sampled_stores", after["nonfinite"], 0.0),
                ("shards_not_1_over_W", after["bad_shards"], 0.0),
            ]
        return out
