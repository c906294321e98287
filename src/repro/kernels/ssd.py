"""Pallas SSD (chunked selective-scan) kernel — the SSM hot-spot.

TPU adaptation of the Mamba-2 dual form (DESIGN.md §2): per (head, chunk)
grid step, the intra-chunk work is two small causal matmuls on the MXU
([Q,N]x[N,Q] scores and [Q,Q]x[Q,P] mix), and the inter-chunk state h
[N, P] lives in VMEM scratch carried across the sequential chunk axis —
the HBM<->VMEM traffic per step is just the (x, B, C, log_a) blocks.

Matches ``repro.models.ssm.ssd_scan`` (the jnp oracle lives there and in
ref-form below); validated in interpret mode by tests/test_kernels_ssd.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import default_interpret


def _ssd_kernel(x_ref, la_ref, b_ref, c_ref, h0_ref, y_ref, hout_ref, h_ref, *, n_c):
    c_step = pl.program_id(1)

    @pl.when(c_step == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[:, 0].astype(jnp.float32)  # [Q, P]
    la = la_ref[:, 0].astype(jnp.float32)  # [Q]
    B = b_ref[:, 0].astype(jnp.float32)  # [Q, N]
    C = c_ref[:, 0].astype(jnp.float32)  # [Q, N]
    q = x.shape[0]

    L = jnp.cumsum(la)  # [Q]
    l_end = L[-1]
    # intra-chunk: (C_t . B_s) exp(L_t - L_s) for s <= t
    scores = jax.lax.dot_general(
        C, B, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # [Q, Q]
    decay = jnp.exp(jnp.minimum(L[:, None] - L[None, :], 0.0))
    causal = (
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    )
    scores = jnp.where(causal, scores * decay, 0.0)
    y_intra = jnp.dot(scores, x, preferred_element_type=jnp.float32)

    # inter-chunk: C_t exp(L_t) h_prev
    h = h_ref[...]
    y_inter = jnp.exp(L)[:, None] * jnp.dot(C, h, preferred_element_type=jnp.float32)
    y_ref[:, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h <- exp(L_end) h + sum_s exp(L_end - L_s) B_s x_s^T
    w = jnp.exp(l_end - L)  # [Q]
    h_new = jnp.exp(l_end) * h + jax.lax.dot_general(
        B * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    h_ref[...] = h_new

    @pl.when(c_step == n_c - 1)
    def _flush():
        hout_ref[0] = h_new.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(
    x: jnp.ndarray,  # [S, H, P]
    log_a: jnp.ndarray,  # [S, H]
    B: jnp.ndarray,  # [S, H, N]
    C: jnp.ndarray,  # [S, H, N]
    h0: jnp.ndarray,  # [H, N, P]
    chunk: int = 128,
    interpret: Optional[bool] = None,
):
    """Single-sequence SSD: returns (y [S,H,P], h_final [H,N,P]).

    vmap over the batch dimension on top.  S must be padded to a chunk
    multiple by the caller (log_a=0, B=0 padding is exact).
    """
    interpret = default_interpret(interpret)
    s, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0, "pad S to a chunk multiple (log_a=0, B=0 is exact)"
    n_c = s // q
    grid = (h, n_c)

    scratch = [pltpu.VMEM((n, p), jnp.float32)]
    y, h_out = pl.pallas_call(
        functools.partial(_ssd_kernel, n_c=n_c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, 1, p), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((q, 1), lambda hh, cc: (cc, hh)),
            pl.BlockSpec((q, 1, n), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((q, 1, n), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((1, n, p), lambda hh, cc: (hh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((q, 1, p), lambda hh, cc: (cc, hh, 0)),
            pl.BlockSpec((1, n, p), lambda hh, cc: (hh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((s, h, p), x.dtype),
            jax.ShapeDtypeStruct((h, n, p), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(x, log_a, B, C, h0)
    return y, h_out
