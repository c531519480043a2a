"""Block-wise int8 quantization with error feedback — the paper's LZO analogue.

The paper's observation: on a system whose bottleneck resource also pays for I/O,
*compressing the bytes that transit the bottleneck is a win even when compression costs
compute*. On TPU the slow resource is the interconnect; the TPU-native "LZO" is
block-quantization (cheap VPU math, fixed 2x(+eps) ratio, deterministic).

Error feedback (1-bit-Adam style) keeps the *training trajectory* honest: the
quantization residual is added back into the next step's gradient, so the compression
error is bounded instead of accumulating — `tests/test_compression.py` property-checks
this invariant.

The Pallas kernel in kernels/quantize provides the TPU hot path for `quantize_block`;
this module is the pure-jnp reference implementation used on CPU and in the dry-run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


BLOCK = 256


def int8_wire_bytes(n: int, block: int = BLOCK) -> int:
    """Wire bytes for a block-quantized payload of ``n`` scalars: one int8
    code per element plus one fp32 scale per block (zero-padded to a full
    final block). Shared accounting for the gradient codec and the
    ``mapreduce.codecs`` int8 shuffle codec."""
    n_pad = ((max(n, 1) + block - 1) // block) * block
    return n_pad + 4 * (n_pad // block)


def quantize_block(x, block: int = BLOCK):
    """x: [n] (any float dtype) -> (q int8 [n_pad], scales fp32 [n_pad/block], n).

    Per-block symmetric max-abs scaling.
    """
    n = x.shape[-1]
    pad = (-n) % block
    xf = jnp.pad(x.astype(jnp.float32), [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    blocks = xf.reshape(*x.shape[:-1], -1, block)
    scale = jnp.max(jnp.abs(blocks), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(*x.shape[:-1], -1), scale, n


def dequantize_block(q, scale, n: int, dtype=jnp.float32, block: int = BLOCK):
    blocks = q.reshape(*q.shape[:-1], -1, block).astype(jnp.float32)
    x = (blocks * scale[..., None]).reshape(*q.shape[:-1], -1)
    return x[..., :n].astype(dtype)


def compress_roundtrip(x, block: int = BLOCK):
    """dequant(quant(x)) — what the wire sees after one hop."""
    q, s, n = quantize_block(x.reshape(-1), block)
    return dequantize_block(q, s, n, x.dtype, block).reshape(x.shape)


def ef_compress(g, err, block: int = BLOCK):
    """Error-feedback compression step.

    Returns (g_compressed, new_err) with the invariant
        g_compressed + new_err == g + err          (up to fp32 rounding)
    so the residual never leaves the system.
    """
    if err is None:
        err = jnp.zeros_like(g, jnp.float32)
    corrected = g.astype(jnp.float32) + err
    sent = compress_roundtrip(corrected, block)
    new_err = corrected - sent
    return sent.astype(g.dtype), new_err


# ---------------------------------------------------------------------------
# Compressed collectives (bodies for shard_map manual regions)
# ---------------------------------------------------------------------------

def compressed_psum_1d(x, axis_name, block: int = BLOCK):
    """All-reduce of a 1D vector over ``axis_name`` (str or tuple) with int8 payloads.

    Quantized reduce-scatter (a2a of int8 chunks + local fp32 sum) followed by a
    quantized all-gather. Wire bytes ~= n int8 both phases vs 2n bf16 for a ring
    all-reduce (4x reduction + scales overhead).
    """
    R = jax.lax.axis_size(axis_name)
    if R == 1:
        return x
    n = x.shape[0]
    pad = (-n) % (R * block)
    xf = jnp.pad(x.astype(jnp.float32), (0, pad)).reshape(R, -1)
    q, s, m = quantize_block(xf)                       # q: [R, m_pad], s: [R, nb]
    q = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0, tiled=False)
    s = jax.lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0, tiled=False)
    chunk = jnp.sum(dequantize_block(q, s, m), axis=0)          # [m] fp32 reduced
    q2, s2, m2 = quantize_block(chunk)
    q2 = jax.lax.all_gather(q2, axis_name, axis=0)
    s2 = jax.lax.all_gather(s2, axis_name, axis=0)
    out = dequantize_block(q2, s2, m2)                          # [R, m]
    return out.reshape(-1)[:n].astype(x.dtype)


def psum_1d(x, axis_name: str):
    return jax.lax.psum(x, axis_name)
