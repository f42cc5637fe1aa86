"""Blockwise int8 quantization kernels (Pallas, TPU).

Gradient compression for the DCN/TCP vans: the reference moves raw fp32
bytes; quantized push quarters wire bytes on bandwidth-limited links (the
EQuARX-style trade, PAPERS.md).  Symmetric per-row scaling: the flat vector
is laid out as rows of 128 lanes; each row gets ``scale = max|row| / 127``.
Tiles are ``(32, 128)`` (the int8 minimum), so rows are padded to a
multiple of 32.  Scales come back lane-replicated ``[rows, 128]``; send
``scales[:, 0]`` on the wire and re-broadcast on receive.

``interpret`` is the caller's decision (compiled for a TPU, interpreted
anywhere else); nothing here looks at the process default backend.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUANT_BLOCK = 128  # elements per scale (one lane row)
_TILE_ROWS = 32    # int8 min sublane tile


def np_quantize_int8(x):
    """Host-side (numpy) variant for the DCN/TCP message path: flat fp32 ->
    (int8 [rows,128], fp32 scales [rows]).  Same layout/semantics as the
    Pallas kernel, minus lane replication."""
    import numpy as _np

    x = _np.asarray(x, dtype=_np.float32).reshape(-1)
    n = x.shape[0]
    pad = (-n) % QUANT_BLOCK
    if pad:
        x = _np.pad(x, (0, pad))
    rows = x.shape[0] // QUANT_BLOCK
    x2 = x.reshape(rows, QUANT_BLOCK)
    scales = _np.maximum(
        _np.abs(x2).max(axis=1) / 127.0, 1e-12
    ).astype(_np.float32)
    q = _np.clip(
        _np.rint(x2 / scales[:, None]), -127, 127
    ).astype(_np.int8)
    return q, scales, n


def np_dequantize_int8(q, scales, n: int):
    import numpy as _np

    x = q.astype(_np.float32) * _np.asarray(scales, _np.float32)[:, None]
    return x.reshape(-1)[:n]


def decode_int8_payload(q_sarray, scales_sarray, val_len: int):
    """Decode the wire layout of an int8-compressed message payload
    (data[1] = int8 codes, data[2] = fp32 scales, meta.val_len =
    uncompressed byte count) — the single decoder both directions of the
    message path share."""
    import numpy as _np

    q = q_sarray.astype_view(_np.int8).numpy().reshape(-1, QUANT_BLOCK)
    scales = scales_sarray.astype_view(_np.float32).numpy()
    return np_dequantize_int8(q, scales, val_len // 4)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_int8(x, *, interpret: bool):
    """flat fp32 -> (int8 ``[rows, 128]``, fp32 scales ``[rows, 128]``).

    Keep the original length for :func:`dequantize_int8`.
    """
    from jax.experimental import pallas as pl

    x = x.astype(jnp.float32).reshape(-1)
    pad = (-x.shape[0]) % (QUANT_BLOCK * _TILE_ROWS)
    if pad:
        x = jnp.pad(x, (0, pad))
    rows = x.shape[0] // QUANT_BLOCK
    x2 = x.reshape(rows, QUANT_BLOCK)
    grid = rows // _TILE_ROWS

    def kernel(x_ref, q_ref, s_ref):
        blk = x_ref[:, :]
        scale = jnp.maximum(
            jnp.max(jnp.abs(blk), axis=1, keepdims=True) / 127.0, 1e-12
        )
        q_ref[:, :] = jnp.clip(
            jnp.round(blk / scale), -127, 127
        ).astype(jnp.int8)
        s_ref[:, :] = jnp.broadcast_to(scale, blk.shape)

    spec = pl.BlockSpec((_TILE_ROWS, QUANT_BLOCK), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((rows, QUANT_BLOCK), jnp.int8),
            jax.ShapeDtypeStruct((rows, QUANT_BLOCK), jnp.float32),
        ),
        grid=(grid,),
        in_specs=[spec],
        out_specs=(spec, spec),
        interpret=interpret,
    )(x2)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def dequantize_int8(q, scales, n: int, *, interpret: bool):
    """Inverse of :func:`quantize_int8`; ``n`` is the original length.

    ``scales`` may be lane-replicated ``[rows, 128]`` or compact
    ``[rows]``/``[rows, 1]`` (wire form) — re-broadcast as needed.
    """
    from jax.experimental import pallas as pl

    rows = q.shape[0]
    if scales.ndim == 1:
        scales = scales[:, None]
    if scales.shape[1] != QUANT_BLOCK:
        scales = jnp.broadcast_to(scales[:, :1], (rows, QUANT_BLOCK))

    def kernel(q_ref, s_ref, x_ref):
        x_ref[:, :] = q_ref[:, :].astype(jnp.float32) * s_ref[:, :]

    spec = pl.BlockSpec((_TILE_ROWS, QUANT_BLOCK), lambda i: (i, 0))
    x = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, QUANT_BLOCK), jnp.float32),
        grid=(rows // _TILE_ROWS,),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
    )(q, jnp.asarray(scales, jnp.float32))
    return x.reshape(-1)[:n]
