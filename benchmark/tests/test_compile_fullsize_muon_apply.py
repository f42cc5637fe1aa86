"""Compile-only: how a key's new values reach the store and the pulled
tree in the one program ``moonlight-16b-muon.tree`` runs, at full size, for
one v5e chip (PR 45).

The parent's program made a key's new values as a temporary (the apply's
fusions), wrote them into the store by a ``dynamic-update-slice`` of its own
(a window that starts 512 values into a tile for every second layer's
keys), transposed O of the tall keys back first (``copy``) and cut the
pulled tree from the new store after the fact (``%slice f32[568484352]``):
four passes for one piece of work.  Here ``muon_row_apply`` takes a chunk's
bfloat16 O, reads p where it lies, and writes the new values in place and
into the pulled vector, which the first call makes and every later call,
``muon_row_adamw``'s too, takes and aliases; that vector is what the
program returns.  ``test_compile_fullsize_muon.py``'s
``test_nothing_of_tree_size_but_the_updates_in_place_and_the_cut`` names the
parent's form (``PERF.md`` §7).

The program is ``test_compile_fullsize_muon.py``'s, compiled by its fixture
(once more for this file: a fixture of a module is that module's).  A
compile that passes says a program LOWERS and FITS, never that it runs or
how fast.
"""

import re

import numpy as np

from test_compile_fullsize_muon import HBM, compiled, topo  # noqa: F401
from test_compile_fullsize_muon_cut import _entry, _kernels

_TYPE = re.compile(r"(\w+)\[([\d,]*)\]")


def _values(kind):
    """The values of each array a result type names."""
    return [int(np.prod([int(d) for d in dims.split(",") if d] or [1],
                        dtype=np.int64))
            for _, dims in _TYPE.findall(kind)]


def test_every_key_is_written_back_by_a_kernel(compiled):
    _, plan, _, _ = compiled
    assert len(plan.apply_keys) == 153 and plan.pulls


def test_no_copy_slice_or_fusion_of_the_trees_size(compiled):
    """Nothing XLA's own touches the tree any more: every result of the
    store's or the pulled vector's size is a kernel's, a view of one
    (``bitcast``) or the barrier between two chunks."""
    exe, _, total, padded = compiled
    big = {}
    for name, kind, op, _ in _entry(exe):
        if op in ("parameter", "get-tuple-element", "tuple"):
            continue
        if max(_values(kind), default=0) >= total:
            big.setdefault(op, []).append(name)
    assert set(big) <= {"custom-call", "bitcast", "opt-barrier"}, {
        op: names[:3] for op, names in big.items()}
    text = exe.as_text()
    entry = text[text.index("ENTRY"):]
    # No transpose of a chunk's O for the tall keys' sake either: the
    # kernel turns a block in VMEM.
    for line in entry.splitlines():
        if re.search(r" (copy|slice|dynamic-update-slice)\(", line):
            assert max(_values(line.split(" = ")[1].split(" ")[0]),
                       default=0) < 2 ** 20, line[:200]


def test_the_apply_writes_the_store_in_place_and_the_pulled_vector(compiled):
    """A ``muon_row_apply`` call's first result is the f32 store (how
    ``benchmark/muon_ops.py`` ``is_ns`` keeps reading it as the rest),
    aliased to the store it was handed; its second the pulled vector,
    made by the first call and aliased by every later one."""
    exe, plan, total, padded = compiled
    calls = _kernels(exe, "muon_row_apply")
    mixed = sum(len(set(c.tall)) == 2 for c in plan.chunks)
    assert len(calls) == len(plan.chunks) + mixed == 17
    store = f"f32[{padded // 128},128]"
    pulled = f"f32[{total // 128},128]"
    fresh = 0
    for name, kind, _, rest in calls:
        assert re.match(
            rf"\({re.escape(store)}\S*, {re.escape(pulled)}\S*\)$", kind), (
                name, kind)
        assert "{0}: (3, {})" in rest, name         # the store where it lies
        fresh += "{1}: (4, {})" not in rest
    assert fresh == 1                               # the call that makes it
    adamw = _kernels(exe, "muon_row_adamw")
    assert len(adamw) == len(plan.adamw_keys) == 18
    for name, kind, _, rest in adamw:
        assert kind.startswith(f"({store}"), (name, kind)
        # Behind alpha and the keys' offsets: p, m, v and the pulled
        # vector, each where it lies.
        for alias in ("{0}: (3, {})", "{1}: (4, {})", "{2}: (5, {})",
                      "{3}: (6, {})"):
            assert alias in rest, (name, alias)


def test_the_pulled_tree_is_a_kernels_result(compiled):
    """The program's last result, ``f32[568484352]``, is a view of the
    last kernel's pulled vector: no operation of XLA's own makes it."""
    exe, _, total, _ = compiled
    entry = {name: (kind, op, rest) for name, kind, op, rest in _entry(exe)}
    root = next((kind, rest) for kind, op, rest in entry.values()
                if op == "tuple" and f"f32[{total}]" in kind)
    last = re.findall(r"%([\w.\-]+)", root[1])[-1]
    seen = []
    while entry[last][1] in ("bitcast", "get-tuple-element"):
        seen.append(entry[last][1])
        last = re.findall(r"%([\w.\-]+)", entry[last][2])[0]
    kind, op, rest = entry[last]
    assert op == "custom-call" and last.startswith("muon_row_a"), (last, op)
    assert "tpu_custom_call" in rest and "bitcast" in seen


def test_the_way_in_is_as_it_was(compiled):
    exe, _, _, _ = compiled
    assert len(_kernels(exe, "muon_row_momentum")) == 17
    assert len(_kernels(exe, "muon_row_vector")) == 18


def test_aliases_and_temporaries_within_the_parents_bounds(compiled):
    """``test_store_and_state_are_donated_and_the_step_fits``'s bounds,
    and no temporary of a key's new values any more."""
    exe, plan, total, padded = compiled
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * padded + plan.state_bytes
    assert mem.temp_size_in_bytes < 1.5e9
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert held + 4 * total < 0.85 * HBM
