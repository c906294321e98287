"""CNN layer primitives, executed the ARM-CL way: im2col + GEMM.

Data layout is NHWC.  The GEMM route matters: it makes each conv's cost a
direct function of the (N, K, M) descriptor dims the paper's model uses.
These are the reference route's primitives (``KernelBackend("xla")``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def im2col(x: jnp.ndarray, fh: int, fw: int, stride: int, pad: int) -> jnp.ndarray:
    """[B,H,W,C] -> [B, OH*OW, FH*FW*C] patch matrix (the 'image matrix').

    Channel-major-last ordering matches the filter reshape below.
    """
    b, h, w, c = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=(fh, fw),
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )
    # conv_general_dilated_patches returns features ordered as C x FH x FW
    # (spatial minor); reorder to FH x FW x C to match filter.reshape(-1, M).
    oh, ow = patches.shape[1], patches.shape[2]
    patches = patches.reshape(b, oh, ow, c, fh, fw)
    patches = patches.transpose(0, 1, 2, 4, 5, 3)  # -> FH, FW, C
    return patches.reshape(b, oh * ow, fh * fw * c)


def conv2d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray],
    stride: int = 1,
    pad: int = 0,
    groups: int = 1,
) -> jnp.ndarray:
    """Convolution as im2col + GEMM.  ``w``: [FH, FW, Cin/groups, Cout]."""
    bsz, h, wdt, c = x.shape
    fh, fw, cin_g, cout = w.shape
    oh = (h - fh + 2 * pad) // stride + 1
    ow = (wdt - fw + 2 * pad) // stride + 1
    if groups == 1:
        cols = im2col(x, fh, fw, stride, pad)  # [B, N, K]
        filt = w.reshape(fh * fw * c, cout)  # [K, M]
        out = cols.reshape(-1, cols.shape[-1]) @ filt
        out = out.reshape(bsz, oh, ow, cout)
    else:
        # grouped conv: split channels, one GEMM per group (ARM-CL folds the
        # group loop into the row dim; vmap keeps it a single XLA op here)
        xg = x.reshape(bsz, h, wdt, groups, cin_g).transpose(3, 0, 1, 2, 4)
        wg = w.reshape(fh, fw, cin_g, groups, cout // groups).transpose(3, 0, 1, 2, 4)

        def one_group(xi, wi):
            cols = im2col(xi, fh, fw, stride, pad)
            return (
                cols.reshape(-1, cols.shape[-1]) @ wi.reshape(fh * fw * cin_g, -1)
            ).reshape(bsz, oh, ow, -1)

        out = jax.vmap(one_group)(xg, wg)  # [G, B, OH, OW, M/G]
        out = jnp.concatenate(list(out), axis=-1) if groups <= 4 else (
            out.transpose(1, 2, 3, 0, 4).reshape(bsz, oh, ow, cout)
        )
    if b is not None:
        out = out + b
    return out


def depthwise_conv2d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray],
    stride: int = 1,
    pad: int = 0,
) -> jnp.ndarray:
    """Depthwise conv.  ``w``: [FH, FW, 1, C] (HWIO with I=1).  Uses the
    native grouped convolution (one im2col GEMM per channel would be
    pathological; ARM-CL also special-cases depthwise)."""
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(stride, stride),
        padding=[(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=x.shape[-1],
    )
    if b is not None:
        out = out + b
    return out


def dense(x: jnp.ndarray, w: jnp.ndarray, b: Optional[jnp.ndarray]) -> jnp.ndarray:
    out = x.reshape(x.shape[0], -1) @ w
    return out + b if b is not None else out


def max_pool(x: jnp.ndarray, window: int, stride: int, pad: int = 0) -> jnp.ndarray:
    return jax.lax.reduce_window(
        x,
        -jnp.inf,
        jax.lax.max,
        (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)],
    )


def avg_pool(x: jnp.ndarray, window: int, stride: int, pad: int = 0) -> jnp.ndarray:
    summed = jax.lax.reduce_window(
        x,
        0.0,
        jax.lax.add,
        (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)],
    )
    counts = jax.lax.reduce_window(
        jnp.ones_like(x),
        0.0,
        jax.lax.add,
        (1, window, window, 1),
        (1, stride, stride, 1),
        [(0, 0), (pad, pad), (pad, pad), (0, 0)],
    )
    return summed / counts


def global_avg_pool(x: jnp.ndarray) -> jnp.ndarray:
    return x.mean(axis=(1, 2))


def lrn(x: jnp.ndarray, size: int = 5, alpha: float = 1e-4, beta: float = 0.75, k: float = 2.0) -> jnp.ndarray:
    """AlexNet local response normalization (cross-channel)."""
    sq = x * x
    half = size // 2
    pads = [(0, 0)] * 3 + [(half, half)]
    sq_p = jnp.pad(sq, pads)
    acc = sum(sq_p[..., i : i + x.shape[-1]] for i in range(size))
    return x / jnp.power(k + alpha * acc, beta)


def layer_norm(x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """Normalisation over the last (channel) axis, without an affine:
    NHWC maps and pooled ``[B, C]`` vectors alike."""
    mu = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def relu(x: jnp.ndarray) -> jnp.ndarray:
    return jnp.maximum(x, 0.0)


def softmax(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.softmax(x, axis=-1)
