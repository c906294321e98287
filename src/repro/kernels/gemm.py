"""Tiled GEMM Pallas kernel — the paper's compute hot-spot, TPU-native.

The paper's ARM-CL GEMM tiles the image matrix along rows with a
cache-derived tile size ``ts`` and dispatches row-tiles to cores (§V-C).
The TPU adaptation re-thinks that for the memory hierarchy: HBM -> VMEM
blocks sized to the MXU (128-aligned), with a sequential K-reduction per
(i, j) output tile accumulated in an f32 VMEM scratch.

Grid: (M/bm, N/bn, K/bk), K innermost so the accumulator stays resident.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import default_interpret


def _gemm_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k_step == n_k - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret")
)
def gemm(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """[M,K] @ [K,N] -> [M,N] with VMEM tiling and f32 accumulation.

    Block sizes are MXU-aligned multiples of 128 by default; inputs are
    zero-padded up to block multiples (zeros contribute nothing to the
    reduction).  ``interpret=None`` resolves by platform: compiled on a
    real TPU, interpreted (kernel body as jax ops, validation only)
    elsewhere — see kernels/config.py.
    """
    interpret = default_interpret(interpret)
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # keep lane/sublane alignment when shapes allow it
    a_p = _pad_to(a, bm, bk)
    b_p = _pad_to(b, bk, bn)
    mp, kp = a_p.shape
    _, np_ = b_p.shape
    n_k = kp // bk
    grid = (mp // bm, np_ // bn, n_k)

    out = pl.pallas_call(
        functools.partial(_gemm_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a_p, b_p)
    return out[:m, :n]
