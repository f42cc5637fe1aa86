"""``muon_ns_ms``, ``muon_ns_mxu_share``, ``muon_rest_ms`` and
``muon_rest_roofline`` on a synthetic trace of the shape the chip's has
(``test_trace_reduce.py``) with the kinds of operation the full-size program
compiles to for a v5e (``test_compile_fullsize_muon.py``): which operations
each counts, that the two times add up to the device's, that the MXU's
share cannot pass 100 with the least count, and that all four read nothing
where there is no device plane or no Newton-Schulz operation (a program
from before the handle).  ``muon_flops.py`` and ``muon_ops.py`` against
hand sums; the loader on the entries PR 43 adds to ``BENCHMARK.json``."""

import json
import os

import pytest

import harness
import muon_flops
import muon_ops
import trace_reduce as tr
from conftest import BENCH, ROOT
from test_lamb_readers import ADAM_STEP, _profile

CELL = "moonlight-16b-muon.tree"
T = "{2,1,0:T(8,128)(2,1)}"

# One chunk of a step of ``jit__push_pull`` on one chip, nanoseconds: the
# gradient cut into a batch, momentum (an f32 momentum is its first
# result), the normalisation, a step's three products and epilogues, a cut
# batch, the apply pass in place, AdamW.
STEP = [
    ("%slice_reduce_fusion.5 = f32[2883584]{0:T(1024)} fusion(%agg)", 9_000),
    ("%copy.31 = f32[2048,1408]{0,1:T(8,128)} copy(%reshape.7)", 11_000),
    ("%fusion.40 = (f32[24,1408,2048]{2,1,0:T(8,128)}, bf16[24,1408,2048]"
     + T + ") fusion(%state, %g)", 600_000),
    ("%fusion.41 = f32[24]{0:T(128)} fusion(%x)", 50_000),
    ("%fusion.42 = bf16[24,1408,2048]" + T + " fusion(%x, %n)", 200_000),
    ("%convolution_convert_fusion.1 = bf16[24,1408,1408]" + T
     + " fusion(%x, %x)", 1_500_000),
    ("%fusion.43 = bf16[24,1408,1408]" + T + " fusion(%a, %a)", 1_100_000),
    ("%fusion.44 = bf16[24,1408,2048]" + T + " fusion(%b, %x)", 1_600_000),
    ("%fusion.45 = bf16[3,2048,2048]" + T + " fusion(%y, %y)", 400_000),
    ("%fusion.46 = bf16[576,2048]{1,0:T(8,128)(2,1)} fusion(%z)", 150_000),
    ("%fusion.292 = f32[568484352]{0:T(1024)} fusion(%pulled, %o)", 20_000),
    ("%dynamic_update_slice.7 = f32[568524800]{0:T(1024)} "
     "dynamic-update-slice(%store, %p)", 30_000),
    ("%fusion.625 = f32[1,41943040]{1,0:T(1,128)} fusion(%m, %v)", 250_000),
    # A batch of another side than any of the configuration's: the rest.
    ("%fusion.9 = bf16[24,1000,1000]" + T + " fusion(%q)", 80_000),
]
NS_NS = 200_000 + 1_500_000 + 1_100_000 + 1_600_000 + 400_000 + 150_000
REST_NS = sum(ns for _, ns in STEP) - NS_NS


def _ctx(reduction, peaks=None):
    cell = harness.load_cell(CELL)
    return harness.LayerContext(
        spans=[], compiles_in_window=0, reduction=reduction,
        least={"hbm": 1.0, "ici": 0.0},
        peaks=peaks or {"hbm_gb_s": 819, "bf16_tflop_s": 197},
        config=cell.config, traffic=cell.traffic)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return [harness.load_reader(search, name) for name in
            ("muon_ns_ms", "muon_ns_mxu_share", "muon_rest_ms",
             "muon_rest_roofline")]


def test_the_readers_on_a_trace_of_the_muon_step(readers):
    ns_ms, mxu, rest_ms, rest_roof = readers
    ctx = _ctx(tr.reduce_trace(_profile(STEP)))
    assert ctx.reduction.steps == 2
    assert ns_ms(ctx) == pytest.approx(NS_NS / 1e6)
    assert rest_ms(ctx) == pytest.approx(REST_NS / 1e6)
    # The two add up to the device's busy time a step.
    assert ns_ms(ctx) + rest_ms(ctx) == pytest.approx(
        ctx.reduction.busy_s * 1e3 / ctx.reduction.steps)
    least_ms = 14262857891840.0 / 197e12 * 1e3          # 72.4 ms
    assert mxu(ctx) == pytest.approx(100 * least_ms / (NS_NS / 1e6))
    rest_least_ms = (24 * 484573184 + 32 * 83911168) / 819e9 * 1e3
    assert rest_roof(ctx) == pytest.approx(
        100 * rest_least_ms / (REST_NS / 1e6))


def test_the_mxu_share_cannot_pass_100_with_the_least_count(readers):
    """A program that ran the published fifteen full products at the MXU's
    peak would take ``published / peak``: of that the least count is 69%.
    Even one that halved the two symmetric products and ran at the peak
    reads 100, not more."""
    _, mxu, _, _ = readers
    shapes = muon_flops.matrices(harness.load_cell(CELL).config)
    for flops, share in ((muon_flops.published(shapes), 69.13),
                         (muon_flops.least(shapes), 100.0)):
        at_peak_ns = flops / 197e12 * 1e9
        step = [("%fusion.44 = bf16[24,1408,2048]" + T + " fusion(%b, %x)",
                 int(round(at_peak_ns))),
                ("%fusion.292 = f32[568484352]{0:T(1024)} fusion(%p)", 1000)]
        got = mxu(_ctx(tr.reduce_trace(_profile(step, steps=2))))
        assert got == pytest.approx(share, abs=0.01) and got <= 100.0 + 1e-6
    for s in shapes:
        m, n = min(s), max(s)
        assert muon_flops.least([s]) <= muon_flops.published([s])
        assert muon_flops.least([s]) == 5 * (3 * m * m * n + m ** 3)


def test_the_readers_find_nothing_to_read(readers):
    """No device plane; a program under another handle (no operation told
    as Newton-Schulz: the parent commit's, on which the new readers have to
    return nothing and not raise); a cell under another handle."""
    for ctx in (_ctx(None), _ctx(tr.reduce_trace(_profile(ADAM_STEP)))):
        assert [r(ctx) for r in readers] == [None] * 4
    other = harness.load_cell("bert-large-lamb.tree")
    ctx = _ctx(tr.reduce_trace(_profile(STEP)))
    ctx.config, ctx.traffic = other.config, other.traffic
    assert [r(ctx) for r in readers] == [None] * 4


def test_which_operations_are_newton_schulz():
    sizes = muon_ops.cell_sizes(harness.load_cell(CELL).config)
    groups = sizes["groups"]
    assert sizes["matrices"] == 135 and sum(groups.values()) == 135
    told = lambda shape: muon_ops.is_ns(shape, groups)
    for shape in ("bf16[24,1408,2048]", "bf16[24,1408,1408]",
                  "bf16[96,1408,2048]", "bf16[3,2048,11264]",
                  "bf16[5,512,512]", "bf16[4,64,64]", "bf16[2,2048,2048]",
                  "bf16[576,2048]"):
        assert told(shape), shape
    for shape in ("f32[24,1408,2048]",      # the momentum
                  "bf16[97,1408,2048]",     # more than the side has
                  "bf16[24,2048,1408]",     # tall keys lie transposed
                  "bf16[1,568484352]", "f32[568524800]", "f32[24]",
                  "bf16[24,1408,2048,2]"):
        assert not told(shape), shape


def test_flops_and_bytes_against_hand_sums():
    assert muon_flops.published([(2, 3)]) == 5 * (4 * 4 * 3 + 2 * 8)
    assert muon_flops.published([(3, 2)]) == muon_flops.published([(2, 3)])
    assert muon_flops.least([(2, 3)]) == 5 * (3 * 4 * 3 + 8)
    assert muon_flops.by_group([(2, 3), (3, 2), (4, 4)]) == {(2, 3): 2,
                                                            (4, 4): 1}
    assert muon_flops.expand_shapes(
        [["a", [5]], {"repeat": 2, "name": "l", "tensors": [["w", [2, 3]]]}]
    ) == [("a", (1, 5)), ("l.0.w", (2, 3)), ("l.1.w", (2, 3))]
    with pytest.raises(ValueError, match="no matrix or vector"):
        muon_flops.expand_shapes([["c", [2, 3, 4]]])
    assert muon_ops.rest_bytes(10, 3) == 4 * (6 * 10 + 8 * 3)
    # The expander names and sizes the tensors as the harness's own does.
    import buckets

    cfg = harness.load_cell(CELL).config
    assert [(n, r * c) for n, (r, c) in muon_flops.expand_shapes(
        cfg["tensors"])] == buckets.expand_tensors(cfg["tensors"])
    driver = harness.resolve(harness.load_cell(CELL))
    assert driver.__name__ == "Driver"


def test_the_loader_takes_the_new_entries(bench_root):
    with open(os.path.join(bench_root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cell = harness.load_cell(CELL, root=bench_root)
    assert cell.chips == 1
    assert cell.traffic["driver"] == "dense_tree_muon_push_pull"
    assert cell.config["server_handle"] == "muon:1e-3,0.95,0.1,0.9,0.95,1e-8"
    assert cell.config["reduced"] == ["num_hidden_layers",
                                      "n_routed_experts", "vocab_size"]
    names = [m["name"] for m in cell.per_layer]
    new = ("muon_ns_ms", "muon_ns_mxu_share", "muon_rest_ms",
           "muon_rest_roofline")
    for name in new + ("busy_ms", "roofline_share", "ops_per_step",
                       "launches_per_step", "compiles_in_window",
                       "issue_exposed_ms"):
        assert name in names
    for name in ("lamb_update_ms", "combine_ms", "route_ms", "convert_ms"):
        assert name not in names
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in new:
        assert entries[name]["workloads"] == [CELL]
        assert entries[name]["source"] == "device_trace"
        assert entries[name]["layer"] == "xla programs and kernels"
    assert entries["muon_ns_mxu_share"]["moves"] == "goodput"
    assert entries["muon_ns_ms"]["moves"] == "step_p50"
    assert entries["route_ms"]["workloads"] == [
        "bert-large-adam.device", "gpt2-large-adam.device.4chip",
        "bert-large-lamb.tree"]
    assert [os.path.relpath(os.path.join(bench_root, c["file"]), ROOT)
            for c in bench["configs"] if c["name"] == "moonlight-16b-muon"] \
        == ["benchmark/configs/moonlight-16b-muon.json"]
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    for name in ("muon_reference.py", "muon_flops.py", "muon_ops.py"):
        assert os.path.exists(os.path.join(BENCH, name))
