"""``mixed_update_ms``, ``mixed_update_roofline`` and ``convert_ms`` on a
synthetic trace of the shape the chip's has (``test_trace_reduce.py``) with
the operations the full-size program of ``bert-large-lamb-bf16.tree``
compiles to for a v5e (``test_compile_fullsize_lamb_mixed.py``): which
operations each counts, that ``convert_ms`` reads 0 of the program as
built and the time of a pass of tree size that a worse program would make,
and that all three read nothing where there is no device plane, no such
kernel, or a configuration with one dtype (the f32 sibling).
``lamb_mixed_bytes.py``'s counts against hand sums."""

import pytest

import harness
import lamb_mixed_bytes
import mixed_ops
import trace_reduce as tr
from test_lamb_readers import ADAM_STEP, _profile

CELL = "bert-large-lamb-bf16.tree"
SIBLING = "bert-large-lamb.tree"
T = "{1,0:T(8,128)}"
VEC = "f32[2627072,128]"
N = 336226108
PULLED = "bf16[" + str(N) + "]{0:T(1024)(128)(2,1)}"

# One step of ``jit__push_pull`` on one chip, nanoseconds: the gradient a
# parameter, the pulled tree the second kernel's second result.
STEP = [
    ("%pad_maximum_fusion = f32[2]{0:T(128)S(1)} fusion(%div.11, %div.10)", 900),
    ("%lamb_moments.1 = (" + VEC + T + ", " + VEC + T + ", f32[796]{0:T(1024)S(1)})"
     " custom-call(%pad_maximum_fusion, %constant.9)", 11_000_000),
    ("%reshape.23 = f32[398,2]{1,0:T(8,128)S(1)} reshape(%jit_lamb_moments_.7)", 1_000),
    ("%multiply_reduce_fusion = f32[398]{0:T(512)S(1)} fusion(%reshape.23)", 2_000),
    ("%lamb_apply.1 = (" + VEC + T + ", " + PULLED + ") custom-call(%pad_maximum_fusion)",
     9_000_000),
]
# What a program that left the dtypes to XLA would add: the gradient
# widened before the first kernel, the store rounded and cut after the
# second.
PASSES = [
    ("%convert.3 = f32[1," + str(N) + "]{1,0:T(1,128)} convert(%grads)", 2_500_000),
    ("%convert_slice_fusion = " + PULLED + " fusion(%bitcast.4)", 3_000_000),
]


def _ctx(reduction, cell=CELL):
    cell = harness.load_cell(cell)
    return harness.LayerContext(spans=[], compiles_in_window=0,
                                reduction=reduction,
                                least={"hbm": 1.0, "ici": 0.0},
                                peaks={"hbm_gb_s": 819},
                                config=cell.config, traffic=cell.traffic)


@pytest.fixture
def readers():
    search = harness.search_dirs()
    return [harness.load_reader(search, name) for name in
            ("mixed_update_ms", "mixed_update_roofline", "convert_ms")]


def test_the_readers_on_a_trace_of_the_mixed_step(readers):
    update, roofline, convert = readers
    ctx = _ctx(tr.reduce_trace(_profile(STEP)))
    assert ctx.reduction.steps == 2
    assert update(ctx) == pytest.approx(20.0)
    assert convert(ctx) == 0.0
    # 2 B of gradient, 24 B of p, m, v, 2 B of pulled tree an element and
    # 12 B more for emb.word: 9.789 GB at 819 GB/s is 11.95 ms of the 20.
    least_ms = (28 * N + 12 * 31254528) / 819e9 * 1e3
    assert roofline(ctx) == pytest.approx(100 * least_ms / 20.0)
    assert 59 < roofline(ctx) < 60


def test_convert_ms_reads_a_pass_of_tree_size_outside_the_kernels(readers):
    update, roofline, convert = readers
    ctx = _ctx(tr.reduce_trace(_profile(PASSES[:1] + STEP + PASSES[1:])))
    assert update(ctx) == pytest.approx(20.0)
    assert convert(ctx) == pytest.approx(5.5)
    # Half a tree is not a tree; the store's own shape outside a kernel is.
    half = [("%copy.2 = bf16[" + str(N // 2) + "]{0} copy(%x)", 1_000_000)]
    whole = [("%copy.3 = " + VEC + T + " copy(%y)", 4_000_000)]
    assert convert(_ctx(tr.reduce_trace(_profile(STEP + half)))) == 0.0
    assert convert(_ctx(tr.reduce_trace(_profile(STEP + whole)))) \
        == pytest.approx(4.0)


def test_the_readers_find_nothing_to_read(readers):
    for ctx in (_ctx(None), _ctx(tr.reduce_trace(_profile(ADAM_STEP))),
                _ctx(tr.reduce_trace(_profile(STEP)), SIBLING)):
        assert [r(ctx) for r in readers] == [None, None, None]


def test_the_cells_sizes_come_from_its_configuration():
    sizes = mixed_ops.cell_sizes(harness.load_cell(CELL).config)
    assert sizes["parameters"] == N and sizes["chips"] == 1
    assert sizes["update_bytes"] == 28 * N + 12 * 31254528
    assert mixed_ops.cell_sizes(harness.load_cell(SIBLING).config) is None
    assert mixed_ops.elements("f32[2627072,128]") == 2627072 * 128
    assert mixed_ops.elements("bf16[336226108]") == N
    assert mixed_ops.elements("f32[]") == 1


def test_least_bytes_against_hand_sums():
    mixed = lamb_mixed_bytes
    # One device: gradient and pulled tree at 2 B, p, m, v at 24.
    assert mixed.lamb_mixed_update(1000, 1, 0) == 28000
    assert mixed.lamb_mixed_update(1000, 1, 400) == 28000 + 4800
    # Several: the f32 sum is read, the pulled tree is the gather's.
    assert mixed.lamb_mixed_update(1000, 4, 400) == (28000 + 4800) / 4
    one = mixed.dense_lamb_mixed_step(1000, 1)
    assert one == {"hbm": 2000 + 24000 + 2000, "ici": 0.0}
    four = mixed.dense_lamb_mixed_step(1000, 4, over=400)
    assert four["hbm"] == 2000 + 6000 + 2000 + 1200
    assert four["ici"] == 2 * 2 * 1000 * 3 / 4
    # On one device the whole step is the update; today's two kernels move
    # 40 B an element, the least is below it.
    assert one["hbm"] == mixed.lamb_mixed_update(1000, 1, 0)
    assert mixed.lamb_mixed_update(N, 1, 31254528) < 40 * N
    # The f32 sibling's count at the f32 sizes, the pulled write apart.
    import lamb_bytes

    assert mixed.lamb_mixed_update(1000, 1, 400, 4, 4) \
        == lamb_bytes.lamb_update(1000, 1, 400) + 4000
