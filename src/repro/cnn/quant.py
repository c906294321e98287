"""QASYMM8-style quantization for the CNN GEMM path (paper §VII-D).

ARM-CL's QASYMM8 uses asymmetric uint8 with per-tensor (we use per-output-
channel for weights, standard practice) scale+zero-point.  The paper's
point is architectural: quantization is *orthogonal* to Pipe-it — it
changes layer times (the T matrix) but not the scheduling algorithms.  We
reproduce that: ``quantize_graph_params`` produces int8 weights, and the
quantized gemm path includes the de/re-quantization overhead the paper
measures (Fig. 13).
"""
from __future__ import annotations

from typing import Dict, Tuple

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


def quantize_graph_params(params: Dict[str, Dict[str, jnp.ndarray]]):
    """Quantize every weight matrix/filter in a CNN graph's params."""
    out = {}
    for name, p in params.items():
        q, s, z = quantize_tensor(p["w"].reshape(-1, p["w"].shape[-1]), axis=-1)
        out[name] = {"qw": q, "scale": s, "zp": z, "b": p["b"], "shape": p["w"].shape}
    return out


def make_quant_gemm_fn(qparams_entry):
    """A gemm_fn closure for Graph.apply(..., gemm_fn=...) built from one
    layer's quantized params."""
    qw = qparams_entry["qw"]
    s = qparams_entry["scale"]
    z = qparams_entry["zp"]
    return lambda a, _ignored: qgemm(a, qw, s, z)


def make_quant_conv_fn(qparams_entry, *, stride: int = 1, pad: int = 0,
                       act: str = "none", pallas: bool = False):
    """The fused-conv counterpart of :func:`make_quant_gemm_fn`: a closure
    ``x -> y`` executing one quantized conv layer with the requant step
    fused into the kernel epilogue (`kernels/conv_fused.py`).

    ``pallas=True`` runs the Pallas kernel (TPU; interpret elsewhere per
    kernels/config.py); the default is the fused XLA lowering, which is
    what serves off-TPU."""
    from ..kernels.conv_fused import qconv2d_fused, qfused_route_ref

    qw, s, z = qparams_entry["qw"], qparams_entry["scale"], qparams_entry["zp"]
    b, shape = qparams_entry["b"], tuple(qparams_entry["shape"])
    fn = qconv2d_fused if pallas else qfused_route_ref
    return lambda x: fn(x, qw, s, z, b, shape, stride=stride, pad=pad, act=act)
