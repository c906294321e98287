"""Fused implicit-GEMM convolution — the serving hot path's kernel.

The unfused conv-as-GEMM route (paper §V-A, `cnn/layers.py`) materializes
the full im2col patch matrix ``[B*Oh*Ow, Fh*Fw*C]`` in HBM before the
GEMM reads it back — for a 3x3 conv that is a 9x write+read amplification
of the input tensor.  This kernel is the *implicit* formulation: each
GEMM grid step forms its patch block in VMEM from one padded input row
and contracts it immediately, so the patch matrix never exists in HBM,
and the epilogue — bias add, then the activation (ReLU or exact GELU) —
runs inside the K-flush of the accumulator instead of as separate HBM
round trips.

Grid: ``(B, Oh, Ow/bm, Cout/bn, Fh * C/bk)`` with the fused K dimension
(filter row x channel block) innermost so the f32 accumulator tile
stays resident in VMEM scratch across the whole reduction.  The M tile
``bm`` spans output columns of one output row (the ARM-CL row-tile ``ts``
analogue), ``bn`` tiles output channels, ``bk`` tiles input channels;
(bm, bn, bk) is what `kernels/autotune.py` sweeps.

Block-wise patch formation: the wrapper splits each padded input row by
stride phase (column ``m*stride + r`` -> phase ``r``, position ``m``).
For output row ``oh`` and filter row ``fi`` the kernel holds padded input
row ``oh*stride + fi`` (one [stride, Wq, bk] VMEM block); filter column
``j = q*stride + r`` of the ``bm``-column tile at ``jm*bm`` is then the
contiguous window ``[jm*bm + q, +bm)`` of phase ``r``, and its
[bm, bk] x [bk, bn] product accumulates into the tile.

Off-TPU the Pallas kernel only runs under the interpreter (validation,
~100x), so `fused_route` resolves to the XLA equivalent — a direct
`lax.conv_general_dilated` with the same fused epilogue, which XLA fuses
into one kernel and which likewise never materializes a patch matrix.
Backend selection for serving lives in `kernels/backend.py`.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import default_interpret


ACTS = ("none", "relu", "gelu")

# float32 erf as x * P(x^2) / Q(x^2) on [-4, 4] (outside it erf is +-1 in
# float32), with the coefficients of Eigen's float32 erf.  Mosaic has no
# lowering for lax.erf_p; this form needs only mul, add and one divide,
# and stays within 5 float32 ulps of 1.0 of lax.erf (tests/test_convnext.py).
_ERF_P = (-2.72614225801306e-10, 2.77068142495902e-08, -2.10102402082508e-06,
          -5.69250639462346e-05, -7.34990630326855e-04, -2.95459980854025e-03,
          -1.60960333262415e-02)
_ERF_Q = (-1.45660718464996e-05, -2.13374055278905e-04, -1.68282697438203e-03,
          -7.37332916720468e-03, -1.42647390514189e-02)


def erf_f32(x: jnp.ndarray) -> jnp.ndarray:
    """erf of a float32 array from mul, add and divide alone."""
    x = jnp.clip(x, -4.0, 4.0)
    x2 = x * x
    p, q = _ERF_P[0], _ERF_Q[0]
    for c in _ERF_P[1:]:
        p = p * x2 + c
    for c in _ERF_Q[1:]:
        q = q * x2 + c
    return x * p / q


def apply_act(y: jnp.ndarray, act: str, *, in_kernel: bool = False) -> jnp.ndarray:
    """A node's activation, in a kernel's epilogue or after an XLA op:
    ``"relu"``, exact (erf) ``"gelu"``, or ``"none"``.  Inside a Pallas
    body (``in_kernel``) GELU's erf is :func:`erf_f32`; elsewhere XLA
    lowers ``jax.nn.gelu`` itself."""
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "gelu":
        if in_kernel:
            return 0.5 * y * (1.0 + erf_f32(y * 0.7071067811865476))
        return jax.nn.gelu(y, approximate=False)
    if act != "none":
        raise ValueError(f"act must be one of {ACTS}, got {act!r}")
    return y


# --------------------------------------------------------------- kernel body
def _conv_fused_kernel(
    x_ref, w_ref, b_ref, o_ref, acc_ref,
    *, fw: int, stride: int, bm: int, n_m: int, ext: int, n_k: int, act: str,
):
    k = pl.program_id(4)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # implicit im2col: output column o reads input column o*stride + j for
    # filter column j = q*stride + r, which is column o + q of stride
    # phase r — so every window is a contiguous [bm, bk] run of the row.
    # The tile starts at jm*bm, which the chip reads dynamically only at
    # a multiple of 8 (candidate_blocks keeps a split row's bm one): read
    # [jm*bm, +bm+ext) once per phase and slice the q offsets from it.
    m0 = 0
    if n_m > 1:
        m0 = pl.program_id(2) * bm
        if bm % 8 == 0:
            m0 = pl.multiple_of(m0, 8)
    phase = {}
    acc = None
    for j in range(fw):
        q, r = divmod(j, stride)
        if r not in phase:
            phase[r] = x_ref[0, 0, r, pl.ds(m0, bm + ext), :]
        win = phase[r][q:q + bm]  # [bm, bk]
        part = jnp.dot(win, w_ref[0, j], preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    acc_ref[...] += acc

    @pl.when(k == n_k - 1)
    def _flush():
        y = acc_ref[...] + b_ref[0]
        o_ref[0, 0] = apply_act(y, act, in_kernel=True).astype(o_ref.dtype)


def _pad_axis(x: jnp.ndarray, axis: int, to: int) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


def default_blocks(ow: int, cout: int, cin: int) -> Tuple[int, int, int]:
    """Untuned (bm, bn, bk) heuristic: whole output rows, 128-lane tiles."""
    return min(ow, 128), min(_ceil_to(cout, 8), 128), min(_ceil_to(cin, 8), 128)


@functools.partial(
    jax.jit,
    static_argnames=(
        "fh", "fw", "stride", "block_m", "block_n", "block_k",
        "act", "interpret",
    ),
)
def _conv_fused_call(
    xp: jnp.ndarray,  # [B, Hp, Wp, C] spatially pre-padded input
    w4: jnp.ndarray,  # [FH, FW, C, Cout] filter
    bias: jnp.ndarray,  # [Cout] f32
    *,
    fh: int, fw: int, stride: int,
    block_m: int, block_n: int, block_k: int,
    act: str, interpret: bool,
) -> jnp.ndarray:
    b, hp, wp, c = xp.shape
    cout = w4.shape[-1]
    oh = (hp - fh) // stride + 1
    ow = (wp - fw) // stride + 1
    bm = min(block_m, ow)
    bn = min(block_n, _ceil_to(cout, 1))
    bk = min(block_k, c)
    n_m, n_n, n_kc = -(-ow // bm), -(-cout // bn), -(-c // bk)
    n_k = fh * n_kc
    # pad so every tile is full: channels to bk, filters to (bn, bk), and
    # each stride phase long enough for the last column tile's window
    # (the kernel's aligned read overhangs a tile by ext columns)
    ext = _ceil_to((fw - 1) // stride, 8)
    wq = max(-(-wp // stride), n_m * bm + ext)
    xp = _pad_axis(_pad_axis(xp, 3, n_kc * bk), 2, wq * stride)
    # split columns by stride phase: xph[b, h, r, m] = xp[b, h, m*stride + r]
    xph = xp.reshape(b, hp, wq, stride, n_kc * bk).transpose(0, 1, 3, 2, 4)
    w4 = _pad_axis(_pad_axis(w4, 2, n_kc * bk), 3, n_n * bn)
    bias2 = _pad_axis(bias.reshape(1, -1).astype(jnp.float32), 1, n_n * bn)

    out = pl.pallas_call(
        functools.partial(
            _conv_fused_kernel,
            fw=fw, stride=stride, bm=bm, n_m=n_m, ext=ext, n_k=n_k,
            act=act,
        ),
        grid=(b, oh, n_m, n_n, n_k),
        in_specs=[
            # one padded input row, all stride phases (block height 1 =>
            # element row index), channel block k % n_kc, at filter row
            # fi = k // n_kc
            pl.BlockSpec(
                (1, 1, stride, wq, bk),
                lambda bi, i, jm, j, k, s=stride: (
                    bi, i * s + k // n_kc, 0, 0, k % n_kc
                ),
            ),
            pl.BlockSpec(
                (1, fw, bk, bn),
                lambda bi, i, jm, j, k: (k // n_kc, 0, k % n_kc, j),
            ),
            pl.BlockSpec((1, bn), lambda bi, i, jm, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, bm, bn), lambda bi, i, jm, j, k: (bi, i, jm, j)),
        out_shape=jax.ShapeDtypeStruct((b, oh, n_m * bm, n_n * bn), xp.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(xph, w4, bias2)
    return out[:, :, :ow, :cout]


# ------------------------------------------------------------- public entry
def supports(fh: int, fw: int, stride: int, groups: int = 1) -> bool:
    """Shapes the fused kernel can tile; everything else falls back to the
    XLA route (grouped convs keep their native implementation; depthwise
    convs have their own kernel, `kernels/dwconv.py`)."""
    return groups == 1 and stride >= 1 and fh >= 1 and fw >= 1


def conv2d_fused(
    x: jnp.ndarray,  # [B, H, W, C]
    w: jnp.ndarray,  # [FH, FW, C, Cout]
    b: Optional[jnp.ndarray],
    *,
    stride: int = 1,
    pad: int = 0,
    act: str = "none",
    block_m: Optional[int] = None,
    block_n: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused conv + bias + activation (``act`` in :data:`ACTS`) via the
    implicit-GEMM Pallas kernel.

    ``interpret=None`` resolves by platform (kernels/config.py).  Shapes
    the kernel cannot tile must be routed by the caller (backend.py) to
    :func:`fused_route_ref`; this entry asserts ``groups == 1``.
    """
    fh, fw, c, cout = w.shape
    assert supports(fh, fw, stride), (fh, fw, stride)
    ow = (x.shape[2] - fw + 2 * pad) // stride + 1
    dm, dn, dk = default_blocks(ow, cout, c)
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    bias = jnp.zeros((cout,), jnp.float32) if b is None else b
    return _conv_fused_call(
        xp, w, bias,
        fh=fh, fw=fw, stride=stride,
        block_m=block_m or dm, block_n=block_n or dn, block_k=block_k or dk,
        act=act, interpret=default_interpret(interpret),
    )


# ----------------------------------------------------- fused dense (fc) GEMM
def _matmul_fused_kernel(a_ref, b_ref, c_ref, o_ref, acc_ref, *, n_k, act):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k_step == n_k - 1)
    def _flush():
        y = acc_ref[...] + c_ref[0]
        o_ref[...] = apply_act(y, act, in_kernel=True).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "act", "interpret"),
)
def matmul_fused(
    a: jnp.ndarray,  # [M, K]
    w: jnp.ndarray,  # [K, N]
    bias: jnp.ndarray,  # [N]
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    act: str = "none",
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """GEMM with the dense layer's epilogue (bias, activation) in the K-flush —
    the fc-node counterpart of the fused conv kernel."""
    interpret = default_interpret(interpret)
    m, k = a.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    a_p = _pad_axis(_pad_axis(a, 0, _ceil_to(m, bm)), 1, _ceil_to(k, bk))
    w_p = _pad_axis(_pad_axis(w, 0, _ceil_to(k, bk)), 1, _ceil_to(n, bn))
    bias2 = _pad_axis(bias.reshape(1, -1).astype(jnp.float32), 1, w_p.shape[1])
    n_k = a_p.shape[1] // bk
    grid = (a_p.shape[0] // bm, w_p.shape[1] // bn, n_k)
    out = pl.pallas_call(
        functools.partial(_matmul_fused_kernel, n_k=n_k, act=act),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a_p.shape[0], w_p.shape[1]), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a_p, w_p, bias2)
    return out[:m, :n]


# ------------------------------------------------------- XLA fused fallback
def fused_route_ref(
    x: jnp.ndarray,
    w: jnp.ndarray,
    b: Optional[jnp.ndarray],
    *,
    stride: int = 1,
    pad: int = 0,
    groups: int = 1,
    act: str = "none",
) -> jnp.ndarray:
    """The fused route's XLA lowering: direct convolution + fused epilogue.

    Semantically identical to the Pallas kernel (same operation, no patch
    matrix in HBM, single fused epilogue); it is what `pallas_fused`
    resolves to off-TPU and the fallback for shapes `supports()` rejects.

    1x1 convolutions ARE the GEMM (the patch "matrix" is a reshape), so
    they skip the convolution lowering entirely: strided-slice + matmul +
    epilogue, which XLA fuses tighter than its conv path on CPU (MobileNet
    pointwise, SqueezeNet squeeze/expand).
    """
    if groups == 1 and w.shape[0] == 1 and w.shape[1] == 1 and pad == 0:
        bsz = x.shape[0]
        xs = x[:, ::stride, ::stride, :]
        oh, ow = xs.shape[1], xs.shape[2]
        y = xs.reshape(-1, xs.shape[-1]) @ w.reshape(w.shape[2], w.shape[3])
        y = y.reshape(bsz, oh, ow, -1)
        if b is not None:
            y = y + b
        return apply_act(y, act)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups,
    )
    if b is not None:
        y = y + b
    return apply_act(y, act)
