"""Compile-only: how a key's gradient leaves the row in the one program
``moonlight-16b-muon.tree`` runs, at full size, for one v5e chip (PR 44).

The chip lays the gradient row ``f32[1, 568484352]`` out one sublane of
eight to a tile, and XLA's cut of a key from it is a ``reduce`` that
squeezes ``[1, n]`` to ``[n]`` at a fifth of the HBM rate and writes the key
out once more before the momentum pass reads it (``slice_reduce_fusion``:
18.4 ms of a 187 ms step, ``PERF.md``, PR 43).  Here every reader of the
row is a kernel: ``muon_row_momentum`` takes a chunk's gradients where
they lie and does the momentum pass itself, M in place and first among its
results, and ``muon_row_vector`` hands an AdamW key over as a vector.

The program is ``test_compile_fullsize_muon.py``'s, compiled by its fixture
(once more for this file: a fixture of a module is that module's).  A
compile that passes says a program LOWERS and FITS, never that it runs or
how fast.
"""

import re

from test_compile_fullsize_muon import compiled, topo  # noqa: F401

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\((.*)$")


def _entry(exe):
    """(name, result type, operation, the rest of the line) of every
    instruction of the entry computation."""
    text = exe.as_text()
    lines = text[text.index("ENTRY"):].splitlines()[1:]
    return [m.groups() for m in map(_INSTRUCTION.match, lines) if m]


def _kernels(exe, name):
    return [i for i in _entry(exe)
            if i[2] == "custom-call" and i[0].startswith(name)
            and 'custom_call_target="tpu_custom_call"' in i[3]]


def test_every_key_takes_the_row_path(compiled):
    _, plan, _, _ = compiled
    assert len(plan.row_keys) == 153 and all(c.row for c in plan.chunks)


def test_nothing_but_the_kernels_reads_the_row(compiled):
    """No ``reduce`` of a slice of the row, no ``slice``, ``copy`` or
    ``fusion`` of it: whatever is handed the row, or the row as it comes
    out of a barrier, is a Mosaic kernel."""
    exe, _, total, _ = compiled
    row = f"f32[1,{total}]"
    entry = _entry(exe)
    aliases = {name for name, kind, op, _ in entry
               if kind.startswith(row) and op in ("parameter",
                                                  "get-tuple-element")}
    assert aliases, "the row is an argument of the program"
    carriers = ("tuple", "opt-barrier", "get-tuple-element")
    readers = [(name, op, rest) for name, _, op, rest in entry
               if op not in carriers
               and any(re.search(rf"%{re.escape(a)}\b", rest)
                       for a in aliases)]
    assert len(readers) == 17 + 18, [r[:2] for r in readers]
    for name, op, rest in readers:
        assert op == "custom-call" and "tpu_custom_call" in rest, (name, op)
        assert name.startswith(("muon_row_momentum", "muon_row_vector"))
    text = exe.as_text()
    assert "slice_reduce_fusion" not in text
    for line in text.splitlines():
        if " reduce(" in line:
            assert row not in line and not re.search(
                r"= f32\[1,\d+\]\S* ", line), line[:200]


def test_the_momentum_pass_writes_m_in_place_and_x_and_nothing_else(compiled):
    """Between the row and a chunk's Newton-Schulz steps nothing writes an
    f32 result of the chunk's size but M itself: a call's results are the
    f32 momentum, FIRST (``benchmark/muon_ops.py`` ``is_ns`` tells the
    passes apart by an operation's first result) and aliased to the state
    it was handed, and the bfloat16 X; the second call of a chunk that
    holds wide and tall keys takes both from the first."""
    exe, plan, _, _ = compiled
    calls = _kernels(exe, "muon_row_momentum")
    mixed = sum(len(set(c.tall)) == 2 for c in plan.chunks)
    assert len(calls) == len(plan.chunks) + mixed == 17
    by_shape = {}
    for name, kind, _, rest in calls:
        m = re.match(r"\(f32\[([\d,]+)\]\S*, bf16\[([\d,]+)\]\S*\)$", kind)
        assert m and m.group(1) == m.group(2), (name, kind)
        by_shape.setdefault(m.group(1), []).append((name, rest))
        assert "{0}: (3, {})" in rest, name        # M where it lies
    want = {}
    for c in plan.chunks:
        shape = f"{len(c.keys)},{c.m},{c.n}"
        want[shape] = want.get(shape, 0) + len(set(c.tall))
    assert {s: len(v) for s, v in by_shape.items()} == want
    seconds = [(name, rest) for v in by_shape.values() for name, rest in v
               if "{1}: (4, {})" in rest]
    assert len(seconds) == mixed
    # What reads a kernel's results: another kernel, or an operation that
    # leaves no f32 array of a chunk's size (the norms ``f32[B]``, the
    # bfloat16 steps).
    entry = _entry(exe)
    results = {name for name, kind, op, rest in entry
               if op == "get-tuple-element"
               and re.search(r"%muon_row_momentum[\w.]*\)", rest)
               and kind.startswith("bf16")}
    chunk_f32 = {f"f32[{s}]" for s in want}
    for name, kind, op, rest in entry:
        if op in ("get-tuple-element", "custom-call"):
            continue
        if any(re.search(rf"%{re.escape(r)}\b", rest) for r in results):
            assert not any(kind.startswith(c) or f"({c}" in kind
                           for c in chunk_f32), (name, kind)


def test_the_adamw_keys_leave_the_row_as_vectors(compiled):
    exe, plan, _, _ = compiled
    calls = _kernels(exe, "muon_row_vector")
    sizes = sorted(int(re.match(r"f32\[(\d+)\]", kind).group(1))
                   for _, kind, _, _ in calls)
    assert len(calls) == len(plan.adamw_keys) == 18
    assert sum(sizes) == plan.adamw_len and sizes[-2:] == [41943040] * 2


def test_the_step_still_fits(compiled):
    exe, plan, total, padded = compiled
    mem = exe.memory_analysis()
    assert mem.alias_size_in_bytes >= 4 * padded + plan.state_bytes
    # A chunk's bfloat16 temporaries as before; its gradient cut into f32
    # matrices (0.28 GB for 24 experts) is gone.
    assert mem.temp_size_in_bytes < 1.5e9
