"""Depthwise convolution on the vector unit — ConvNeXt's 7x7 and
MobileNet's 3x3 stride-1 convs.

A depthwise conv has no contraction over channels, so the matrix unit
has nothing to do: each output element is k*k per-channel multiply-adds.
XLA's grouped conv on the TPU may tile such a conv one output pixel and
one tap at a time, depending on the layout and memory space it gives
the operand; this kernel owns its tiling instead.

Grid: ``(B, C/bc)``.  Channels sit on lanes, as the full dim or in
blocks of 128; one step holds one image's ``[H, W, bc]`` block.  The
``same`` padding is zero-filled inside a VMEM scratch (the block's
columns start at an aligned sublane, ``left``), so no padded copy of the
input goes through HBM.  Output rows are computed in strips of ``rows``:
each padded input row of a strip is loaded once, shifted k times along
its columns, and each shifted row feeds every output row of the strip
that its filter rows reach.  Taps, sums and the epilogue (bias, then the
activation) are float32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import default_interpret
from .conv_fused import _ceil_to, apply_act

_LANES, _SUBLANES = 128, 8
_ACC_VREGS = 32  # accumulator registers a strip may hold (of the chip's 64)
_MAX_ROWS = 8  # longest strip: the body is unrolled rows * (rows + k - 1) * k
_VMEM_CAP = 96 * 2**20  # of a v5e core's 128 MiB


def _channel_block(c: int) -> int:
    return _LANES if c % _LANES == 0 else c


def _vmem_bytes(h: int, w: int, c: int, k: int, itemsize: int = 4) -> int:
    """VMEM one grid step holds: the input and output blocks, each double
    buffered, and the padded scratch, at (8, 128) tiles."""
    lanes = _ceil_to(_channel_block(c), _LANES)
    p = k // 2
    block = h * _ceil_to(w, _SUBLANES) * lanes
    scratch = (h + 2 * p) * _ceil_to(_ceil_to(p, _SUBLANES) + w + p, _SUBLANES) * lanes
    taps = k * k * lanes * 2 + 2 * lanes
    return (4 * block + scratch + taps) * itemsize


def supports(h: int, w: int, c: int, k: int, stride: int, pad: int) -> bool:
    """Shapes the kernel computes: stride 1, odd k, ``same`` padding, and a
    grid step that fits VMEM.  Everything else stays on XLA's grouped conv."""
    return (
        stride == 1 and k % 2 == 1 and pad == k // 2
        and _vmem_bytes(h, w, c, k) <= _VMEM_CAP
    )


def _strip_rows(h: int, w: int, bc: int) -> int:
    """Output rows a strip computes: the largest divisor of ``h`` whose
    accumulators fit ``_ACC_VREGS`` registers, at most ``_MAX_ROWS``."""
    per_row = -(-w // _SUBLANES) * -(-bc // _LANES)
    cap = max(1, min(_MAX_ROWS, _ACC_VREGS // per_row))
    return max(r for r in range(1, cap + 1) if h % r == 0)


def _dwconv_kernel(x_ref, w_ref, b_ref, o_ref, xp_ref, *, k, h, w, left, rows, act):
    p = k // 2
    xp_ref[...] = jnp.zeros_like(xp_ref)
    xp_ref[p:p + h, left:left + w, :] = x_ref[0]
    bias = b_ref[...].astype(jnp.float32)  # [1, bc]

    def strip(s, carry):
        r0 = s * rows
        taps = [w_ref[t:t + 1, :].astype(jnp.float32) for t in range(k * k)]
        accs = [None] * rows
        # padded input row r0 + t feeds output row r0 + o through filter
        # row i = t - o
        for t in range(rows + k - 1):
            line = xp_ref[r0 + t].astype(jnp.float32)  # [Wp, bc]
            for j in range(k):
                shifted = line[left - p + j:left - p + j + w]  # [w, bc]
                for o in range(max(0, t - k + 1), min(rows, t + 1)):
                    term = shifted * taps[(t - o) * k + j]
                    accs[o] = term if accs[o] is None else accs[o] + term
        for o in range(rows):
            y = apply_act(accs[o] + bias, act, in_kernel=True)
            o_ref[0, r0 + o] = y.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, h // rows, strip, 0)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def _dwconv_call(
    x: jnp.ndarray,  # [B, H, W, C]
    w2: jnp.ndarray,  # [k*k, C] taps, row-major over (filter row, column)
    bias: jnp.ndarray,  # [1, C] f32
    *,
    act: str,
    interpret: bool,
) -> jnp.ndarray:
    b, h, w, c = x.shape
    k = int(round(w2.shape[0] ** 0.5))
    p = k // 2
    bc = _channel_block(c)
    left = _ceil_to(p, _SUBLANES)
    wp = _ceil_to(left + w + p, _SUBLANES)
    rows = _strip_rows(h, w, bc)
    return pl.pallas_call(
        functools.partial(
            _dwconv_kernel, k=k, h=h, w=w, left=left, rows=rows, act=act
        ),
        grid=(b, c // bc),
        in_specs=[
            pl.BlockSpec((1, h, w, bc), lambda bi, ci: (bi, 0, 0, ci)),
            pl.BlockSpec((k * k, bc), lambda bi, ci: (0, ci)),
            pl.BlockSpec((1, bc), lambda bi, ci: (0, ci)),
        ],
        out_specs=pl.BlockSpec((1, h, w, bc), lambda bi, ci: (bi, 0, 0, ci)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((h + 2 * p, wp, bc), x.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(32 * 2**20, _vmem_bytes(h, w, c, k) + 8 * 2**20),
        ),
        interpret=interpret,
    )(x, w2, bias)


def dwconv2d_fused(
    x: jnp.ndarray,  # [B, H, W, C]
    w: jnp.ndarray,  # [k, k, 1, C]
    b: Optional[jnp.ndarray],
    *,
    stride: int = 1,
    pad: int = 0,
    act: str = "none",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Depthwise conv + bias + activation (``act`` in ``conv_fused.ACTS``)
    via the Pallas kernel.  ``interpret=None`` resolves by platform
    (kernels/config.py).  Shapes :func:`supports` rejects must be routed
    by the caller (backend.py) to ``conv_fused.fused_route_ref``."""
    bsz, h, wd, c = x.shape
    k = w.shape[0]
    if w.shape != (k, k, 1, c) or not supports(h, wd, c, k, stride, pad):
        raise ValueError(
            f"dwconv2d_fused takes stride 1, odd k, pad k // 2 and a [k, k, 1, C] "
            f"weight; got x {x.shape}, w {w.shape}, stride {stride}, pad {pad}"
        )
    bias = jnp.zeros((c,), jnp.float32) if b is None else b
    return _dwconv_call(
        x, w.reshape(k * k, c), bias.reshape(1, c).astype(jnp.float32),
        act=act, interpret=default_interpret(interpret),
    )
