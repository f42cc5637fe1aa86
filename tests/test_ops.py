"""Pallas kernels: fused optimizer updates and int8 quantization
(interpreter mode on the CPU mesh; the same code compiles on TPU)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from pslite_tpu.ops import (
    adam_update,
    dequantize_int8,
    quantize_int8,
    sgd_update,
)


def test_sgd_update_matches_reference():
    rng = np.random.default_rng(0)
    n = 3000  # not block-aligned
    store = rng.normal(size=n).astype(np.float32)
    mom = rng.normal(size=n).astype(np.float32)
    agg = rng.normal(size=n).astype(np.float32)

    new_store, new_mom = sgd_update(
        jnp.asarray(store), jnp.asarray(mom), jnp.asarray(agg),
        lr=0.1, momentum=0.9, interpret=True,
    )
    ref_mom = 0.9 * mom + agg
    ref_store = store - 0.1 * ref_mom
    np.testing.assert_allclose(np.asarray(new_mom), ref_mom, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_store), ref_store, rtol=1e-6,
                               atol=1e-6)


def test_adam_update_matches_reference():
    rng = np.random.default_rng(1)
    n = 2048
    store = rng.normal(size=n).astype(np.float32)
    m = np.zeros(n, np.float32)
    v = np.zeros(n, np.float32)
    agg = rng.normal(size=n).astype(np.float32)
    lr, b1, b2, eps = 1e-2, 0.9, 0.999, 1e-8

    new_store, new_m, new_v = adam_update(
        jnp.asarray(store), jnp.asarray(m), jnp.asarray(v),
        jnp.asarray(agg), step=1, lr=lr, beta1=b1, beta2=b2, eps=eps,
        interpret=True,
    )
    ref_m = (1 - b1) * agg
    ref_v = (1 - b2) * agg * agg
    alpha = lr * np.sqrt(1 - b2) / (1 - b1)
    ref_store = store - alpha * ref_m / (np.sqrt(ref_v) + eps)
    np.testing.assert_allclose(np.asarray(new_m), ref_m, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_v), ref_v, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new_store), ref_store, rtol=1e-4,
                               atol=1e-6)


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(2)
    n = 5000
    x = (rng.normal(size=n) * 10).astype(np.float32)
    q, scales = quantize_int8(jnp.asarray(x), interpret=True)
    assert q.dtype == jnp.int8
    out = np.asarray(dequantize_int8(q, scales, n, interpret=True))
    # Error bounded by half a quantization step per 128-lane row.
    per_elem_scale = np.repeat(np.asarray(scales)[:, 0], 128)[:n]
    assert np.all(np.abs(out - x) <= per_elem_scale * 0.5 + 1e-6)
    # Wire form: int8 payload + one fp32 scale per row => ~4x smaller.
    wire = q.nbytes + np.asarray(scales)[:, 0].nbytes
    assert wire * 3 <= x.nbytes + 4 * 128 * 32 * 4
    # Compact wire scales round-trip too.
    out2 = np.asarray(
        dequantize_int8(q, np.asarray(scales)[:, 0].copy(), n,
                        interpret=True)
    )
    np.testing.assert_allclose(out2, out)


def test_quantize_zero_input():
    x = jnp.zeros(1024, jnp.float32)
    q, s = quantize_int8(x, interpret=True)
    out = dequantize_int8(q, s, 1024, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), 0)


# -- wire codec registry (ops/codecs.py — docs/compression.md) ---------------


def _codec_names():
    from pslite_tpu.ops import codecs

    return codecs.names()


@pytest.mark.parametrize("name", ["int8", "fp8_e4m3", "bf16"])
def test_codec_roundtrip_error_bounded(name):
    """Property: decode(encode(x)) lands within the codec's per-block
    quantization step, for aligned and ragged lengths."""
    from pslite_tpu.ops import codecs

    if name not in _codec_names():
        pytest.skip(f"{name} unavailable (ml_dtypes)")
    c = codecs.get_codec(name)
    rng = np.random.default_rng(3)
    for n in (128, 127, 5000, 65536 + 17):
        x = (rng.normal(size=n) * 10).astype(np.float32)
        codes, scales, flags = c.encode(x)
        out = c.decode(np.ascontiguousarray(codes), scales, n,
                       flags=flags)
        if name == "bf16":
            # RNE to 8 mantissa bits: relative error <= 2^-9.
            assert np.all(np.abs(out - x) <= np.abs(x) * 2.0 ** -8 + 1e-30)
            assert codes.nbytes == 2 * n and scales.size == 0
        else:
            starts = np.arange(0, n, codecs.BLOCK)
            step = np.maximum.reduceat(np.abs(x), starts) / (
                127.0 if name == "int8" else 448.0
            )
            sizes = np.diff(np.append(starts, n))
            per_elem = np.repeat(step, sizes)
            # int8 rounds to the nearest step; fp8 keeps ~3 mantissa
            # bits of the scaled value (error < max(step, |x|/16)).
            bound = (per_elem * 0.51 if name == "int8"
                     else np.maximum(per_elem, np.abs(x) / 14.0))
            assert np.all(np.abs(out - x) <= bound + 1e-7), name
            assert codes.nbytes == n
            assert scales.size == (n + 127) // 128


@pytest.mark.parametrize("name", ["int8", "fp8_e4m3", "bf16"])
def test_codec_ragged_per_key_blockwise(name):
    """lens payloads scale PER KEY: a huge-magnitude key must not
    flatten a small-magnitude neighbour's resolution."""
    from pslite_tpu.ops import codecs

    if name not in _codec_names():
        pytest.skip(f"{name} unavailable")
    c = codecs.get_codec(name)
    rng = np.random.default_rng(4)
    lens = np.array([1, 127, 128, 129, 700], np.int64)
    small = rng.normal(size=int(lens[:-1].sum())).astype(np.float32)
    huge = (rng.normal(size=int(lens[-1])) * 1e6).astype(np.float32)
    x = np.concatenate([small, huge])
    codes, scales, flags = c.encode(x, lens=lens)
    out = c.decode(np.ascontiguousarray(codes), scales, x.size,
                   lens=lens, flags=flags)
    # The small keys' error must be set by THEIR own block maxes, not
    # the 1e6 neighbour (a shared scale would give errors ~1e6/127).
    assert np.abs(out[: small.size] - small).max() < 0.2, name
    if name != "bf16":
        assert scales.size == int(
            ((lens + codecs.BLOCK - 1) // codecs.BLOCK).sum()
        )


@pytest.mark.parametrize("name", ["int8", "fp8_e4m3", "bf16"])
def test_codec_nan_inf_policy(name):
    """Policy (docs/compression.md): NaN propagates through every
    codec; +/-Inf saturates to the block max (bf16 keeps Inf); scales
    are computed over FINITE values only, so one bad element cannot
    zero its block's resolution."""
    from pslite_tpu.ops import codecs

    if name not in _codec_names():
        pytest.skip(f"{name} unavailable")
    c = codecs.get_codec(name)
    x = np.linspace(-4, 4, 512).astype(np.float32)
    x[10], x[200], x[300] = np.nan, np.inf, -np.inf
    codes, scales, flags = c.encode(x)
    out = c.decode(np.ascontiguousarray(codes), scales, x.size,
                   flags=flags)
    assert np.isnan(out[10]), name
    if name == "bf16":
        assert out[200] == np.inf and out[300] == -np.inf
    else:
        # Saturated to the FINITE block max (scale unpoisoned).
        assert np.isfinite(out[200]) and out[200] > 0
        assert np.isfinite(out[300]) and out[300] < 0
        # The rest of the NaN/Inf blocks kept their resolution.
        fin = np.isfinite(x)
        assert np.abs(out[fin] - x[fin]).max() < 0.5


@pytest.mark.parametrize("name", ["int8", "fp8_e4m3", "bf16"])
def test_codec_empty_vals_rejected(name):
    from pslite_tpu.ops import codecs

    if name not in _codec_names():
        pytest.skip(f"{name} unavailable")
    with pytest.raises(ValueError):
        codecs.get_codec(name).encode(np.empty(0, np.float32))


def test_codec_native_kernel_bit_identical_to_numpy():
    """The C fused kernels (psl_codec_encode/decode — mixed clusters
    depend on this) must produce byte-identical codes, scales, decodes
    AND error-feedback residuals to the numpy fallback."""
    from pslite_tpu.ops import codecs

    if codecs._native_codec() is None:
        pytest.skip("native codec kernels unavailable (make native)")
    rng = np.random.default_rng(5)
    try:
        for name in ("int8", "fp8_e4m3"):
            if name not in _codec_names():
                continue
            c = codecs.get_codec(name)
            for scale_f in (1.0, 1e6, 1e-9):
                x = (rng.normal(size=300_017) * scale_f).astype(
                    np.float32
                )
                x[7], x[13], x[17] = np.nan, np.inf, -np.inf
                co_n, sc_n, fl_n = c.encode(x)
                co_n = bytes(co_n)
                o_n = c.decode(np.frombuffer(co_n, np.uint8), sc_n,
                               x.size, flags=fl_n).copy()
                rn = np.zeros(x.size, np.float32)
                c.encode(x, resid=rn)
                codecs._native_lib = None  # force the numpy fallback
                co_p, sc_p, fl_p = c.encode(x)
                o_p = c.decode(np.ascontiguousarray(co_p), sc_p,
                               x.size, flags=fl_p).copy()
                rp = np.zeros(x.size, np.float32)
                c.encode(x, resid=rp)
                codecs._native_probed = False
                codecs._native_codec()
                assert bytes(co_p) == co_n and fl_p == fl_n, name
                assert np.array_equal(np.asarray(sc_p),
                                      np.asarray(sc_n)), name
                assert np.array_equal(o_p, o_n, equal_nan=True), name
                assert np.array_equal(rn, rp), name
    finally:
        codecs._native_probed = False
        codecs._native_codec()


def test_error_feedback_removes_quantization_bias():
    """The EF mechanism (docs/compression.md): repeatedly quantizing
    the SAME gradient without EF leaves a persistent bias (components
    below the quantization step round to zero forever); with the
    residual folded back in, the mean of the decoded stream converges
    to the true value."""
    from pslite_tpu.ops import codecs

    c = codecs.get_codec("int8")
    rng = np.random.default_rng(6)
    # One dominant component per block pushes the others under the
    # step — the no-EF worst case.
    x = (rng.normal(size=4096) * 0.01).astype(np.float32)
    x[::128] = 10.0
    rounds = 64
    resid = np.zeros(x.size, np.float32)
    acc_ef = np.zeros_like(x)
    acc_raw = np.zeros_like(x)
    for _ in range(rounds):
        co, sc, fl = c.encode(x, resid=resid)
        acc_ef += c.decode(np.ascontiguousarray(co), sc, x.size,
                           flags=fl)
        co, sc, fl = c.encode(x)
        acc_raw += c.decode(np.ascontiguousarray(co), sc, x.size,
                            flags=fl)
    err_ef = np.abs(acc_ef / rounds - x).max()
    err_raw = np.abs(acc_raw / rounds - x).max()
    # Without EF the small components are ALL zero forever (bias =
    # their full magnitude); with EF the mean error shrinks ~rounds-x.
    assert err_raw > 0.009, err_raw  # the bias is real
    assert err_ef < err_raw / 10, (err_ef, err_raw)


def test_error_feedback_bank_bounded_and_evicts_loudly():
    """ErrorFeedback slots are bounded; exceeding the cap evicts LRU
    with a loud log, and a size change under the same key resets the
    slot."""
    import logging

    from pslite_tpu.ops import codecs

    bank = codecs.ErrorFeedback(max_slots=2)
    r1, _ = bank.slot(("a",), 8)
    r1[:] = 1.0
    bank.slot(("b",), 8)
    assert len(bank) == 2
    # The repo logger does not propagate; attach a capture handler.
    msgs = []
    h = logging.Handler()
    h.emit = lambda rec: msgs.append(rec.getMessage())
    logging.getLogger("pslite_tpu").addHandler(h)
    try:
        bank.slot(("c",), 8)  # evicts "a" (LRU)
    finally:
        logging.getLogger("pslite_tpu").removeHandler(h)
    assert len(bank) == 2
    assert bank.evictions == 1
    assert any("error-feedback" in m for m in msgs)
    # "a" comes back zeroed (its residual was genuinely dropped).
    r1b, _ = bank.slot(("a",), 8)
    assert not r1b.any()
    # Same key, new size: slot resets rather than aliasing stale data.
    r2, _ = bank.slot(("b",), 16)
    assert r2.size == 16 and not r2.any()
    assert bank.residual_norm() >= 0.0


def test_adagrad_update_matches_reference():
    from pslite_tpu.ops.fused_update import adagrad_update

    rng = np.random.default_rng(3)
    n = 3000  # not block-aligned
    store = rng.normal(size=n).astype(np.float32)
    acc = np.abs(rng.normal(size=n)).astype(np.float32)
    agg = rng.normal(size=n).astype(np.float32)
    lr, eps = 0.05, 1e-8

    new_store, new_acc = adagrad_update(
        jnp.asarray(store), jnp.asarray(acc), jnp.asarray(agg),
        lr=lr, eps=eps, interpret=True,
    )
    ref_acc = acc + agg * agg
    ref_store = store - lr * agg / (np.sqrt(ref_acc) + eps)
    np.testing.assert_allclose(np.asarray(new_acc), ref_acc, rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_store), ref_store, rtol=1e-5,
                               atol=1e-6)
