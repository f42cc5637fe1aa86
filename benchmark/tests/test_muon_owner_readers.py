"""``muon_owned_ns_ms``, ``muon_owned_ns_mxu_share``, ``muon_exchange_ms``,
``muon_exchange_ici_share``, ``muon_place_ms``, ``muon_owned_rest_ms``,
``muon_owned_rest_roofline`` and ``muon_owner_flops_spread`` on a synthetic
trace of the shape the chip's has
(``test_route_readers.py``'s planes): the Newton-Schulz time is the FULLEST
chip's, not the chips' mean; its share is of the least FLOPs any deal of
whole matrices gives its fullest owner; the exchange is every collective,
told by kind; its share is the driver's least ICI bytes at the published
link rate; the placing passes are what a chip's program runs before its
first collective and after its last, the owner's other passes what lies
between and is neither a collective nor Newton-Schulz; the spread is the
program's gauge; and each reads nothing where there is nothing to
read (a CPU run, one chip, a program from before the owners' layout)."""

import pytest

import harness
import muon_flops
import muon_owner_ops as ops
import trace_reduce as tr
from test_trace_reduce import Ev, Line, Plane, Profile

CELL = "moonlight-16b-muon.tree.4chip"
T = "{2,1,0:T(8,128)}"
N = 568_484_352

# A step as a 2x2 runs it (ns): the row laid (XLA's copies, a run of keys
# each), the sum, the cut, a chunk's momentum pass, three Newton-Schulz
# operations, the way out, AdamW, the gather and the tree laid back.
STEP = [
    ("%slice_dynamic-update-slice_fusion.24 = f32[1,578027520]{1,0:T(1,128)} "
     "fusion(%param.86)", 1000),
    ("%slice_dynamic-update-slice_fusion.31 = f32[1,578027520]{1,0:T(1,128)} "
     "fusion(%slice_dynamic-update-slice_fusion.24, %param.86)", 2000),
    ("%all-reduce = f32[578027520]{0:T(1024)} all-reduce(%bitcast.24)",
     40_000),
    ("%dynamic-slice = f32[144506880]{0:T(1024)} dynamic-slice(%all-reduce)",
     700),
    ("%muon_row_momentum.3 = (f32[24,1408,2048]" + T + ", bf16[24,1408,2048]"
     + T + ") custom-call(%l, %s, %row, %m)", 900),
    ("%fusion.11 = bf16[24,1408,1408]" + T + " fusion(%x)", 2000),
    ("%convolution.3 = bf16[24,1408,2048]" + T + " convolution(%b, %x)",
     3000),
    ("%fusion.12 = bf16[1,2048,11264]" + T + " fusion(%x)", 1000),
    ("%muon_row_apply.17 = f32[1128960,128]{1,0:T(8,128)} custom-call(%f)",
     800),
    ("%muon_row_adamw.1 = (f32[1128960,128]{1,0}, f32[164352,128]{1,0}) "
     "custom-call(%a)", 600),
    ("%all-gather.4 = f32[578027520]{0:T(1024)} all-gather(%bitcast.93)",
     20_000),
    ("%fusion.574 = (f32[7471104]{0:T(1024)}, f32[568484352]{0:T(1024)}) "
     "fusion(%all-gather.4, %slice_dynamic-update-slice_fusion.7)", 1500),
    ("%dynamic-update-slice.4 = f32[568484352]{0:T(1024)} "
     "dynamic-update-slice(%fusion.18, %slice-done, %constant.284)", 2000),
]
NS_NS = 2000 + 3000 + 1000
REST_NS = 700 + 900 + 800 + 600


def _profile(per_step, steps=2, devices=4, every=200_000):
    """``test_route_readers.py``'s planes, a step every 200 us."""
    host = Line("python3")
    planes = []
    for s in range(steps):
        base = 100_000 + s * every
        host.events += [Ev(tr.STEP, base, every), Ev(tr.ISSUE, base, 1000),
                        Ev(tr.WAIT, base + 1000, every - 1000)]
    for d in range(devices):
        mods, opl = Line(tr.MODULES_LINE), Line(tr.OPS_LINE)
        for s in range(steps):
            at = 100_010 + s * every
            mods.events.append(Ev("jit__push_pull(1)", at, every - 5000))
            for name, ns in per_step:
                opl.events.append(Ev(name, at, ns))
                at += ns
        planes.append(Plane(f"/device:TPU:{d}", [mods, opl]))
    return Profile(planes + [Plane("/host:CPU", [host])])


def _reader(name):
    return harness.load_reader(harness.search_dirs(), name)


def _ctx(profile, cell=CELL, least=None, spans=()):
    reduction = tr.reduce_trace(profile) if profile is not None else None
    cell = harness.load_cell(cell)
    if least is None and "adamw_keys" in cell.config:
        muon = sum(r * c for r, c in muon_flops.matrices(cell.config))
        least = ops.least_bytes_a_chip(muon, N - muon, cell.config["chips"])
    return harness.LayerContext(
        spans=list(spans), compiles_in_window=0, reduction=reduction,
        least=least or {"hbm": 1e9, "ici": 1e9},
        peaks={"hbm_gb_s": 819, "ici_gbit_s": 1600,
               "bf16_tflop_s": 197},
        config=cell.config, traffic=cell.traffic, profile=profile)


def _uneven(profile, device=2, extra=1500):
    """One chip's Newton-Schulz operations take ``extra`` ns longer each."""
    line = profile.planes[device].lines[1]
    for ev in line.events:
        if "bf16[24,1408,1408]" in ev.name:
            ev.duration_ns += extra
    return profile


def test_the_newton_schulz_time_is_the_fullest_chips():
    ctx = _ctx(_uneven(_profile(STEP)))
    assert _reader("muon_owned_ns_ms")(ctx) == pytest.approx(
        (NS_NS + 1500) / 1e6)
    # The mean over the chips, which the one-chip cell's reader takes,
    # would hide the owner the step waits for.
    assert _reader("muon_ns_ms")(ctx) == pytest.approx(
        (NS_NS + 1500 / 4) / 1e6)


def test_the_share_is_of_the_least_flops_of_a_fullest_owner():
    ctx = _ctx(_uneven(_profile(STEP)))
    shapes = muon_flops.matrices(ctx.config)
    assert len(shapes) == 135
    flops = ops.fullest_owner_flops(ctx.config)
    # A quarter of the tree's least count: no matrix is heavier.
    assert flops == muon_flops.least(shapes) / 4
    assert flops > max(muon_flops.least([s]) for s in shapes)
    share = _reader("muon_owned_ns_mxu_share")(ctx)
    assert share == pytest.approx(
        100 * flops / 197e12 * 1e3 / ((NS_NS + 1500) / 1e6))
    # One heavy matrix and three owners that could not share it.
    lone = dict(ctx.config, tensors=[["w", [4096, 4096]], ["v", [8, 8]]],
                adamw_keys=[])
    assert ops.fullest_owner_flops(lone) == muon_flops.least([(4096, 4096)])
    assert ops.fullest_owner_flops(dict(ctx.config,
                                        server_handle="lamb")) is None


def test_the_exchange_is_every_collective_and_its_share_of_the_links():
    ctx = _ctx(_profile(STEP))
    assert _reader("muon_exchange_ms")(ctx) == pytest.approx(60_000 / 1e6)
    # 3/4 of the tree out and 3/4 in: 6 x 568,484,352 B a chip at 200 GB/s.
    assert ctx.least["ici"] == 6 * N == 3_410_906_112
    least_ms = 6 * N / 200e9 * 1e3
    assert _reader("muon_exchange_ici_share")(ctx) == pytest.approx(
        100 * least_ms / 0.06)
    # A reduce-scatter in place of the all-reduce and the cut is still read.
    other = [(("%reduce-scatter.1 = f32[144506880]{0} reduce-scatter(%b)",
               25_000) if op[0].startswith("%all-reduce") else op)
             for op in STEP]
    assert _reader("muon_exchange_ms")(_ctx(_profile(other))) == \
        pytest.approx(45_000 / 1e6)


def test_the_least_bytes_of_a_chip():
    muon, adamw = 484_573_184, 83_911_168
    least = ops.least_bytes_a_chip(muon, adamw, 4)
    assert muon + adamw == N
    assert least["hbm"] == 4 * N + 3 * N + (24 * muon + 32 * adamw) / 4
    assert ops.least_bytes_a_chip(muon, adamw, 1) == {
        "hbm": 4 * N + 24 * muon + 32 * adamw, "ici": 0.0}


def test_the_placing_passes_are_what_runs_outside_the_exchange():
    ctx = _ctx(_profile(STEP))
    assert _reader("muon_place_ms")(ctx) == pytest.approx(6500 / 1e6)
    # Told by no name and no shape: kernels of the program's own are read.
    kernels = [("%muon_place_row.1 = f32[1,578027520]{1,0:T(1,128)} "
                "custom-call(%p, %z)", 2500)] + STEP[2:-2] + [
        ("%muon_unplace_vector.1 = f32[4441284,128]{1,0:T(8,128)} "
         "custom-call(%b, %z)", 3000)]
    assert _reader("muon_place_ms")(_ctx(_profile(kernels))) == \
        pytest.approx(5500 / 1e6)
    # One shard: no collective, and nothing is placed.
    alone = [op for op in STEP if "all-" not in op[0]]
    assert _reader("muon_place_ms")(_ctx(_profile(alone))) is None


def test_the_owners_other_passes_are_the_fullest_chips():
    profile = _profile(STEP)
    late = 0
    for ev in profile.planes[1].lines[1].events:    # one chip's pass is slower
        if ev.name == STEP[0][0]:
            late = 0
        ev.start_ns += late
        if "muon_row_momentum" in ev.name:
            ev.duration_ns += 400
            late = 400
    ctx = _ctx(profile)
    assert _reader("muon_owned_rest_ms")(ctx) == pytest.approx(
        (REST_NS + 400) / 1e6)
    # A quarter of the one-chip cell's least bytes at the HBM peak.
    muon = sum(r * c for r, c in muon_flops.matrices(ctx.config))
    least_ms = (24 * muon + 32 * (N - muon)) / 4 / 819e9 * 1e3
    assert _reader("muon_owned_rest_roofline")(ctx) == pytest.approx(
        100 * least_ms / ((REST_NS + 400) / 1e6))
    # An operation the trace shows whole and by its parts is counted once:
    # a branch an owner takes, with a product inside it.
    whole = list(STEP)
    whole.insert(8, ("%conditional.1 = f32[144506880]{0:T(1024)} "
                     "conditional(%which, %a, %b)", 0))
    nested = _profile(whole)
    for plane in nested.planes[:4]:
        events = plane.lines[1].events
        for i, ev in enumerate(events):
            if ev.name.startswith("%conditional"):
                # It spans the two operations that follow it.
                ev.duration_ns = (events[i + 1].duration_ns
                                  + events[i + 2].duration_ns)
    assert _reader("muon_owned_rest_ms")(_ctx(nested)) == pytest.approx(
        REST_NS / 1e6)


@pytest.mark.parametrize("name", ["muon_owned_ns_ms",
                                  "muon_owned_ns_mxu_share",
                                  "muon_exchange_ms",
                                  "muon_exchange_ici_share", "muon_place_ms",
                                  "muon_owned_rest_ms",
                                  "muon_owned_rest_roofline"])
def test_trace_readers_read_nothing_where_there_is_nothing(name):
    read = _reader(name)
    assert read(_ctx(None)) is None                     # a CPU run
    # A program from before the handle ran over several shards: nothing of
    # a step under muon in its trace.
    other = [("%fusion.3 = f32[1000]{0} fusion(%a)", 500)]
    assert read(_ctx(_profile(other))) is None
    # Another cell's configuration: not under muon.
    if "exchange" in name:
        assert read(_ctx(_profile(STEP),
                         cell="gpt2-large-adam.device.4chip")) is None


def test_the_spread_is_the_programs_gauge(monkeypatch):
    import pslite_tpu as ps

    read = _reader("muon_owner_flops_spread")
    spans = [(0.0, 0.001, 0.1)]

    class Engine:
        muon_owner_flops = 1019

    class Node:
        class van:
            engine = Engine()

    monkeypatch.setattr(ps, "postoffice", lambda role: Node)
    assert read(_ctx(None, spans=spans)) == 1.019
    assert read(_ctx(None)) is None                     # no window
    Engine.muon_owner_flops = 0                         # no step under muon
    assert read(_ctx(None, spans=spans)) is None
    Node.van.engine = object()          # a program without the gauge
    assert read(_ctx(None, spans=spans)) is None
