"""``dense_tree_muon_owners_push_pull``: ``dense_tree_muon_push_pull`` over
several colocated servers, W workers' rows in one call.

It takes that driver's class through the harness's own loader and keeps its
registration, its gradient generator (a row a worker, all different, from
``--seed``), its checked steps, its sampled keys and its comparison with
``muon_reference.py``, which knows no layout: a key's gradient is the four
rows' sum, in float64.  Its own:

- the sampled keys lie on at least ``owners_sampled`` of the owners (the
  traffic file's number): where the seed's draw does not, the draw goes on
  with the same generator until it does;
- ``least_bytes``: what any implementation that shards the state by owner
  moves on one chip (``muon_owner_ops.py``);
- what is compared besides, exact: the store and every array of the state
  lie a W-th on each chip; no matrix key lies across a shard's border (by
  the program's own plan, read from the bucket); every worker's copy of the
  pulled tree is the first worker's, bit for bit, over the whole tree; the
  optimizer state is no larger than 4 B a Muon value + 8 B an AdamW value
  + the plan's padding (``state_padding`` of the traffic file at most) +
  the step slots.
"""

import fnmatch
from typing import Dict, List

import numpy as np

import harness
from driver_base import Comparison
from muon_owner_ops import least_bytes_a_chip
from reference import Rounding

MuonDriver = harness.load_driver(harness.search_dirs(),
                                 "dense_tree_muon_push_pull")


class Driver(MuonDriver):
    def _plan(self):
        """The program's owner plan of the bucket, made at registration:
        which key lies on which shard.  None on a checkout without one,
        which refuses ``muon`` over several shards by name at its first
        push."""
        made = getattr(self.eng.bucket(self.BUCKET), "owner_plan", None)
        return None if made is None else made[1]

    def least_bytes(self) -> Dict[str, float]:
        muon = int(self.lens[~self.adamw].sum())
        return least_bytes_a_chip(muon, self.params_total - muon, self.W)

    # -- set-up --------------------------------------------------------------

    def _key_owners(self, k: int) -> set:
        """The shards key k lies on, by the plan's runs of the key order."""
        plan = self._plan()
        lo, hi = int(self.starts[k]), int(self.starts[k + 1])
        runs = plan.segments
        runs = runs[(runs[:, 0] < hi) & (runs[:, 0] + runs[:, 2] > lo)]
        owners = set()
        for src, dst, n in runs.tolist():
            first = dst + max(lo - src, 0)
            last = dst + min(hi - src, n) - 1
            owners |= set(range(first // plan.shard_len,
                                last // plan.shard_len + 1))
        return owners

    def _spread_sample(self) -> None:
        """Before the first step: where the sampled keys lie on fewer
        owners than the traffic asks, draw on."""
        if self._plan() is None:
            return
        want = int(self.traffic["owners_sampled"])
        names = self.tensor_names
        rng = np.random.default_rng([self.seed, 7])
        fixed = [names.index(n) for n in self.traffic["always_sampled"]]
        for _ in range(64):
            owners = set().union(*(self._key_owners(k)
                                   for k in self.sampled))
            if len(owners) >= want:
                return
            chosen = list(fixed)
            for pattern in self.traffic["drawn_sampled"]:
                hits = [k for k, n in enumerate(names)
                        if fnmatch.fnmatchcase(n, pattern)
                        and k not in chosen]
                chosen.append(int(rng.choice(hits)))
            self.sampled = sorted(chosen)
            for k in self.sampled:
                self._check_grads.setdefault(k, [])
                self._check_pulled.setdefault(k, [])
        raise ValueError(f"no draw of the sampled keys lies on {want} owners")

    def checked_steps(self) -> None:
        self._spread_sample()
        super().checked_steps()

    # -- the comparison ------------------------------------------------------

    def _copies_differ(self) -> float:
        """How many workers' copies of the pulled tree are not the first
        worker's bit for bit: each chip sums its own copy's bit patterns
        (mod 2^32, whole tree), and the sums are compared."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        def sums(tree):
            bits = lax.bitcast_convert_type(tree, jnp.uint32)
            n = bits.shape[0] - bits.shape[0] % 1024
            folded = bits[:n].reshape(-1, 1024).sum(axis=0, dtype=jnp.uint32)
            weighed = folded * jnp.arange(1, 1025, dtype=jnp.uint32)
            return (weighed.sum(dtype=jnp.uint32)
                    + bits[n:].sum(dtype=jnp.uint32)).reshape(1)

        got = np.asarray(jax.jit(jax.shard_map(
            sums, mesh=self.eng.mesh, in_specs=P(None),
            out_specs=P(self.eng.axis), check_vma=False))(self.params))
        return float(np.count_nonzero(got != got[0])
                     + abs(len(got) - self.W))

    def _collect(self) -> dict:
        """As the one-chip driver's, with what only several chips have;
        the gradient and the pulled tree are let go before the state is
        looked at."""
        limit = int(self.traffic["followed_key_elements"])
        followed = [k for k in self.sampled if self.lens[k] <= limit]
        out = {"followed": followed, "grad": {}, "pulled": {}}
        for k in followed:
            sl = self._window(k)
            # Summed over W once, in float64: the same sum every step.
            out["grad"][k] = np.asarray(self.grad[:, sl]).astype(
                np.float64).sum(axis=0)
            out["pulled"][k] = np.asarray(self.params[sl])
        copies_differ = self._copies_differ()
        self.grad = self.params = None
        plan = self._plan()
        # Store and state a W-th on each chip, as they are kept.
        bad = 0
        for spec in (self.eng.store_spec(self.BUCKET),
                     *self.eng.opt_state_specs(self.BUCKET)):
            per_dev = spec.sharding.shard_shape(spec.shape)
            bad += (per_dev[0] * self.W != spec.shape[0]
                    or len(spec.sharding.device_set) != self.W)
        bad += self.eng.store_spec(self.BUCKET).shape[0] != plan.padded_len
        # No matrix key across a shard's border; every key somewhere.
        across = sum(len(self._key_owners(k)) != 1
                     for k in np.flatnonzero(~self.adamw))
        nowhere = sum(not self._key_owners(k)
                      for k in range(len(self.lens)))
        store = self.eng.store_array(self.BUCKET)      # key order
        nonfinite = 0.0
        for k in self.sampled:
            part = np.asarray(store[self._slice(k)])
            nonfinite += float(part.size - np.isfinite(part).sum())
        del store
        held = int(self.eng.opt_state_nbytes(self.BUCKET))
        muon = int(self.lens[~self.adamw].sum())
        least = 4 * muon + 8 * (self.params_total - muon)
        allowed = int(least * (1.0 + float(self.traffic["state_padding"]))
                      ) + 4 * self.W
        kind, (mom, m, v, slot) = self.eng.opt_state(self.BUCKET)
        bad += (mom.shape != (muon,)) + (m.shape != v.shape) + (
            m.shape != (self.params_total - muon,))
        sampled_on = set().union(*(self._key_owners(k)
                                   for k in self.sampled))
        out.update(
            slot_gap=float(np.max(np.abs(np.asarray(slot)
                                         - self.steps_done))),
            nonfinite=nonfinite, bad_shards=float(bad),
            state_bytes_over=float(max(0, held - allowed)),
            across=float(across + nowhere), copies_differ=copies_differ,
            owners_short=float(max(
                0, int(self.traffic["owners_sampled"]) - len(sampled_on))))
        return out

    def compare(self, rounding: Rounding = None) -> List[Comparison]:
        out = super().compare(rounding)
        if rounding is not None:
            return out
        after = self._after
        renamed = {"state_bytes_over_4_a_muon_8_an_adamw_value":
                   "state_bytes_over_4_a_muon_8_an_adamw_value_and_padding"}
        return [(renamed.get(n, n), v, lim) for n, v, lim in out] + [
            ("matrix_keys_across_a_border_or_keys_nowhere",
             after["across"], 0.0),
            ("workers_whose_pulled_tree_differs", after["copies_differ"],
             0.0),
            ("sampled_keys_owners_short_of_the_traffics", after["owners_short"],
             0.0),
        ]
