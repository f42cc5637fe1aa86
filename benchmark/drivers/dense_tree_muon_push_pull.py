"""``dense_tree_muon_push_pull``: the closed-loop driver of a gradient tree
handed over whole under a server handle that works on whole matrices
(``muon:...``).

It is ``dense_tree_push_pull`` (one bucket of keys with their own lengths,
one ``KVWorker.push_pull`` and one ``wait`` a step, the store initialised
from ``--seed``, the dense driver's gradient generator, payload count and
byte counters) with what Muon asks for: the bucket is registered with each
key's ``shapes`` besides its ``lens``, and with ``KEY_ELEMENTWISE`` on the
keys the configuration's ``adamw_keys`` name; the window's gradient is made
after the checked steps' are let go (a second tree beside it would not leave
the chip the room a deployment has); and the comparison follows
``muon_reference.py`` beside ``drivers/``, one matrix at a time.

What is compared (``compare``), each number beside its limit:

- ``first3_err`` / ``final_err``: the worst ``|pulled - ref|`` of a sampled
  key over that key's largest ``|ref|``: a single element gone wrong shows
  here whatever the key's size;
- ``first3_rms`` / ``final_rms``: the root mean square of ``pulled - ref``
  over a sampled key, as a share of the root mean square of the reference's
  own last step of that key: two correct bfloat16 computations of one
  recurrence differ by roundings that flip, a few units in the last place in
  some elements and little on average, where a store kept in bfloat16 or a
  Newton-Schulz step left out is off by a tenth of a step and more in every
  element;
- exact: the step slot = steps issued, the sampled stores finite, the
  optimizer state no larger than 4 B a Muon value + 8 B an AdamW value +
  the step slot, store and state whole on the one device.

With ``rounding`` (``readings.py``) the numbers are the two controls',
each put in the program's place: every stored value rounded, and four
Newton-Schulz steps in place of five.
"""

import fnmatch
import time
from typing import Dict, List, Optional

import numpy as np

import harness
from driver_base import CHECKED_STEPS, Comparison
from muon_flops import expand_shapes, is_adamw
from muon_ops import rest_bytes
from muon_reference import MuonReference, parse_muon_handle
from reference import Rounding

try:
    from pslite_tpu.parallel.engine import KEY_ELEMENTWISE
except ImportError as exc:
    # A checkout from before a server handle could work on whole matrices
    # cannot run this cell: say so where the driver is loaded, before
    # anything boots.
    raise RuntimeError(
        "this checkout's engine keeps no per-key shapes and has no handle "
        "that works on whole matrices (register_dense(..., shapes=), "
        "KEY_ELEMENTWISE, the server handle muon:...): it cannot run a cell "
        "under Muon") from exc

TreeDriver = harness.load_driver(harness.search_dirs(),
                                 "dense_tree_push_pull")

# The store before the first push (the configuration's ``assumed`` states
# it): weights N(0, INIT_STD^2), gains 1.
INIT_STD = np.float32(0.02)
GAIN_KEYS = ("*.norm.g",)


def _rms(x: np.ndarray) -> float:
    return float(np.sqrt(np.mean(np.square(x, dtype=np.float64))))


def _key_error(got, want, floor: float) -> float:
    """The worst ``|got - want|`` of a key over the key's largest
    ``|want|`` (or ``floor``)."""
    got = np.asarray(got, np.float64).reshape(-1)
    want = np.asarray(want, np.float64).reshape(-1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), floor))


def _rms_error(got, want, before) -> float:
    """``rms(got - want)`` as a share of ``rms(want - before)``, the
    reference's own step."""
    got = np.asarray(got, np.float64).reshape(-1)
    if got.shape != want.shape or not np.isfinite(got).all():
        return float("inf")
    return _rms(got - want) / max(_rms(want - before), 1e-30)


class Driver(TreeDriver):
    def __init__(self, cluster, config: dict, traffic: dict, seed: int):
        self.kv = cluster.kv
        self.eng = cluster.engine
        self.config, self.traffic, self.seed = config, traffic, int(seed)
        self.W = int(self.eng.num_workers)
        tensors = expand_shapes(config["tensors"])
        self.tensor_names = [name for name, _ in tensors]
        self.shapes = np.array([shape for _, shape in tensors], np.int64)
        self.lens = self.shapes[:, 0] * self.shapes[:, 1]
        self.starts = np.concatenate([[0], np.cumsum(self.lens)])
        self.params_total = int(self.lens.sum())
        want = config.get("parameters")
        if want is not None and want != self.params_total:
            raise ValueError(
                f"tensor list sums to {self.params_total:,}, the "
                f"configuration states {want:,}")
        self.hyper = parse_muon_handle(config["server_handle"])
        self.adamw = np.array([is_adamw(name, config["adamw_keys"])
                               for name in self.tensor_names])
        self.keys = np.arange(1000, 1000 + len(tensors), dtype=np.uint64)
        self.limits = config["limits"]
        self.grad = None
        self.params = None
        self.steps_done = 0
        self.sampled: List[int] = []
        self._first_only: List[int] = []
        self._gen = None
        self._check_grads: Dict[int, list] = {}
        self._check_pulled: Dict[int, list] = {}
        self._after: Optional[dict] = None
        self._sound: Optional[dict] = None

    # -- set-up --------------------------------------------------------------

    def least_bytes(self) -> Dict[str, float]:
        """What any implementation moves through HBM on the one device:
        ``muon_ops.rest_bytes``.  No FLOP has a place in this count (§7 of
        PERF.md): ``roofline_share`` reads the HBM bound of a step the MXU
        bounds."""
        muon = int(self.lens[~self.adamw].sum())
        return {"hbm": rest_bytes(muon, self.params_total - muon),
                "ici": 0.0}

    def _window(self, k: int) -> slice:
        """What of key k is compared: the key whole, or for an AdamW key
        longer than the traffic's ``sliced_key_elements`` a seeded slice of
        that length (its update is element-wise: a slice is followed as
        exactly as the whole)."""
        n, most = int(self.lens[k]), int(self.traffic["sliced_key_elements"])
        lo = 0
        if self.adamw[k] and n > most:
            lo = int(np.random.default_rng([self.seed, k, 1]).integers(
                0, n - most + 1))
            n = most
        lo += int(self.starts[k])
        return slice(lo, lo + n)

    def _init_key(self, k: int) -> np.ndarray:
        """Key k's stored value before the first push, from the seed and k
        alone: the comparison makes the sampled keys' again."""
        n = int(self.lens[k])
        if any(fnmatch.fnmatchcase(self.tensor_names[k], p)
               for p in GAIN_KEYS):
            return np.ones(n, np.float32)
        rng = np.random.default_rng([self.seed, k])
        return INIT_STD * rng.standard_normal(n, dtype=np.float32)

    def setup(self) -> Dict[str, float]:
        """Register the tree as one bucket of keys with their own lengths
        and shapes, its store initialised (inside ``register``).  The
        window's gradient is made after the checked steps."""
        t0 = time.perf_counter()
        init = np.concatenate([self._init_key(k)
                               for k in range(len(self.lens))])
        self.kv.register_dense(
            self.BUCKET, self.keys, lens=self.lens,
            flags=np.where(self.adamw, KEY_ELEMENTWISE, 0),
            shapes=self.shapes, init=init)
        del init
        t1 = time.perf_counter()
        self._gen = self._generator()
        self.sampled = self._sample()
        names = self.tensor_names
        self._first_only = [names.index(n)
                            for n in self.traffic["first_step_sampled"]]
        for k in self.sampled + self._first_only:
            self._check_grads[k], self._check_pulled[k] = [], []
        return {"register": t1 - t0, "inputs": time.perf_counter() - t1}

    def checked_steps(self) -> None:
        """The first three steps from the initialised store, each with a
        gradient of its own, through the window's own ``step``; what is
        compared of the sampled keys is kept on the host (a key of
        ``first_step_sampled`` in the first step alone: its reference is a
        teraflop a step).  Then the window's gradient."""
        import jax

        for s in range(CHECKED_STEPS):
            grad = self._gen(s + 1, self.params_total)
            self.step(grad)
            for k in self.sampled + (self._first_only if s == 0 else []):
                sl = self._window(k)
                self._check_grads[k].append(np.asarray(grad[:, sl]))
                self._check_pulled[k].append(np.asarray(self.params[sl]))
            del grad
        self.grad = self._gen(0, self.params_total)
        jax.block_until_ready(self.grad)

    # -- the comparison ------------------------------------------------------

    def _collect(self) -> dict:
        """Once, after the last step: what the comparison reads of the
        device, with the gradient and the pulled tree let go before the
        state is looked at (``opt_state`` copies it)."""
        limit = int(self.traffic["followed_key_elements"])
        followed = [k for k in self.sampled if self.lens[k] <= limit]
        out = {"followed": followed, "grad": {}, "pulled": {}}
        for k in followed:
            sl = self._window(k)
            # Summed over W once, in float64: the same sum every step.
            out["grad"][k] = np.asarray(self.grad[:, sl]).astype(
                np.float64).sum(axis=0)
            out["pulled"][k] = np.asarray(self.params[sl])
        self.grad = self.params = None
        store = self.eng.store_array(self.BUCKET)
        bad = self._bad_shards(store, store.shape[0] // self.W)
        nonfinite = 0.0
        for k in self.sampled:
            part = np.asarray(store[self._slice(k)])
            nonfinite += float(part.size - np.isfinite(part).sum())
        del store
        held = int(self.eng.opt_state_nbytes(self.BUCKET))
        muon = int(self.lens[~self.adamw].sum())
        allowed = 4 * muon + 8 * (self.params_total - muon) + 4 * self.W
        kind, (mom, m, v, slot) = self.eng.opt_state(self.BUCKET)
        for arr in (mom, m, v):
            bad += abs(len(arr.addressable_shards) - self.W)
        out.update(
            slot_gap=float(np.max(np.abs(np.asarray(slot)
                                         - self.steps_done))),
            nonfinite=nonfinite, bad_shards=float(bad),
            state_bytes_over=float(max(0, held - allowed)))
        return out

    def _reference(self, **kw) -> MuonReference:
        """The reference of every compared key (the sampled ones, then
        those of the first step alone), each at what is compared of it."""
        keys = self.sampled + self._first_only
        init, shapes = [], []
        for k in keys:
            sl = self._window(k)
            lo = sl.start - int(self.starts[k])
            n = sl.stop - sl.start
            init.append(self._init_key(k)[lo:lo + n])
            shapes.append((1, n) if self.adamw[k] else self.shapes[k])
        return MuonReference(init, shapes, [self.adamw[k] for k in keys],
                             **self.hyper, **kw)

    def _numbers(self, got_of) -> dict:
        """The four numbers of ``got_of(stage, s, j)``, what stands in the
        program's place, against the sound reference's trajectory (made
        once and kept)."""
        floor = float(self.traffic["error_floor"])
        if self._sound is None:
            self._sound = self._trajectory(self._reference())
        out = {"first3_err": 0.0, "first3_rms": 0.0, "final_err": 0.0,
               "final_rms": 0.0}
        for stage in ("first3", "final"):
            for (s, j), (want, before) in self._sound[stage].items():
                got = got_of(stage, s, j)
                out[stage + "_err"] = max(out[stage + "_err"],
                                          _key_error(got, want, floor))
                out[stage + "_rms"] = max(out[stage + "_rms"],
                                          _rms_error(got, want, before))
        return out

    def _trajectory(self, ref: MuonReference) -> dict:
        """``ref`` through the steps the program took.  ``first3[(s, j)]``
        and ``final[(0, j)]`` are (parameters, parameters one step before)
        of the j-th key compared at that point."""
        after = self._after
        every = self.sampled + self._first_only
        out = {"first3": {}, "final": {}}
        for s in range(CHECKED_STEPS):
            keys = every if s == 0 else self.sampled
            if s == 1:
                ref.keep(range(len(self.sampled)))
            before = [p.copy() for p in ref.p]
            ref.step([self._check_grads[k][s] for k in keys])
            for j in range(len(keys)):
                out["first3"][(s, j)] = (ref.p[j].copy(), before[j])
        # Every later step pushed the window's gradient: follow the whole
        # of each key that is small enough through all of them.
        where = [self.sampled.index(k) for k in after["followed"]]
        sums = [after["grad"][k] for k in after["followed"]]
        ref.keep(where)
        before = [p.copy() for p in ref.p]
        for _ in range(self.steps_done - CHECKED_STEPS):
            before = [p.copy() for p in ref.p]
            ref.step(sums)
        for j in range(len(where)):
            out["final"][(0, j)] = (ref.p[j].copy(), before[j])
        return out

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        """Run after the window.  With ``rounding`` the numbers are the
        controls': the reference with every stored value rounded, and the
        reference with a Newton-Schulz step left out, each put in the
        program's place."""
        if self._after is None:
            self._after = self._collect()
        after = self._after
        every = self.sampled + self._first_only

        def pulled(stage, s, j):
            if stage == "final":
                return after["pulled"][after["followed"][j]]
            return self._check_pulled[every[j]][s]

        names = ("first3_err", "first3_rms", "final_err", "final_rms")
        if rounding is None:
            got = self._numbers(pulled)
            return [(n, got[n], self.limits[n]) for n in names] + [
                ("muon_step_slot_gap", after["slot_gap"], 0.0),
                ("nonfinite_in_sampled_stores", after["nonfinite"], 0.0),
                ("state_bytes_over_4_a_muon_8_an_adamw_value",
                 after["state_bytes_over"], 0.0),
                ("shards_not_1_over_W", after["bad_shards"], 0.0),
            ]
        out = []
        for label, kw in (("stored values rounded", {"rounding": rounding}),
                          ("4 newton-schulz steps", {"ns_steps": 4})):
            control = self._trajectory(self._reference(**kw))
            got = self._numbers(
                lambda stage, s, j: control[stage][(s, j)][0])
            out += [(f"{n}[{label}]", got[n], self.limits[n]) for n in names]
        return out
