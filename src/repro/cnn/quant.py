"""QASYMM8-style quantization for the CNN GEMM path (paper §VII-D).

ARM-CL's QASYMM8 uses asymmetric uint8 with per-tensor (we use per-output-
channel for weights, standard practice) scale+zero-point.  The paper's
point is architectural: quantization is *orthogonal* to Pipe-it — it
changes layer times (the T matrix) but not the scheduling algorithms.  We
reproduce that: ``quantize_tensor`` produces uint8 weights, and ``qgemm``
includes the de/re-quantization overhead the paper measures (Fig. 13).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def quantize_tensor(w: jnp.ndarray, axis=-1) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Asymmetric uint8 quantization along ``axis`` (per output channel),
    or per-tensor when ``axis is None``.

    Returns (q, scale, zero_point) with  w ~= scale * (q - zero_point).
    """
    if axis is None:
        reduce_axes = tuple(range(w.ndim))
    else:
        reduce_axes = tuple(i for i in range(w.ndim) if i != (axis % w.ndim))
    w_min = jnp.minimum(w.min(axis=reduce_axes, keepdims=True), 0.0)
    w_max = jnp.maximum(w.max(axis=reduce_axes, keepdims=True), 0.0)
    scale = (w_max - w_min) / 255.0
    scale = jnp.where(scale == 0, 1.0, scale)
    zp = jnp.clip(jnp.round(-w_min / scale), 0, 255)
    q = jnp.clip(jnp.round(w / scale + zp), 0, 255).astype(jnp.uint8)
    return q, scale, zp


def dequantize(q: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray) -> jnp.ndarray:
    return scale * (q.astype(jnp.float32) - zp)


def qgemm(a: jnp.ndarray, qw: jnp.ndarray, scale: jnp.ndarray, zp: jnp.ndarray) -> jnp.ndarray:
    """Quantized GEMM: quantize activations to uint8, int32 accumulate,
    dequantize the result — mirroring ARM-CL's QASYMM8 kernels including
    the re/de-quantization work the paper identifies as overhead."""
    qa, sa, za = quantize_tensor(a, axis=None)  # per-tensor for activations
    acc = jax.lax.dot_general(
        qa.astype(jnp.int32) - za.astype(jnp.int32),
        qw.astype(jnp.int32) - zp.astype(jnp.int32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return acc.astype(jnp.float32) * sa * scale
