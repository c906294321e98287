"""Flash-decode Pallas kernel: online-softmax decode attention.

Serves the decode path (one new token against a long KV cache).  On the
production mesh the KV cache is sequence-sharded across the "model" axis
(DESIGN.md §4) and each shard runs this kernel over its local cache slice;
partial (m, l, acc) statistics are then combined with psum — the classic
flash-decoding decomposition, TPU-native because each grid step is a dense
[Hq, bs] x [bs, D] MXU contraction.

This kernel handles ONE kv head: q [Hq, D] (the GQA query group), cache
k/v [S, D], valid ``length``.  vmap over kv heads on top.

Grid: (S/bs,) sequential; VMEM scratch carries the running max ``m``,
normalizer ``l`` and accumulator across grid steps.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .config import default_interpret

_NEG_INF = -1e30


def _flash_decode_kernel(
    len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, bs: int, n_b: int, scale: float
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0, 0]
    q = q_ref[...].astype(jnp.float32)  # [Hq, D]
    k = k_ref[...].astype(jnp.float32)  # [bs, D]
    v = v_ref[...].astype(jnp.float32)  # [bs, D]

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [Hq, bs]
    pos = step * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    logits = jnp.where(pos < length, logits, _NEG_INF)

    m_prev = m_ref[...]  # [Hq, 1]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)  # [Hq, bs]
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(step == n_b - 1)
    def _flush():
        o_ref[...] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(
    q: jnp.ndarray,  # [Hq, D]
    k: jnp.ndarray,  # [S, D]
    v: jnp.ndarray,  # [S, D]
    length: jnp.ndarray,  # scalar i32: valid cache prefix
    *,
    block_s: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    interpret = default_interpret(interpret)
    hq, d = q.shape
    s, _ = k.shape
    bs = min(block_s, s)
    pad_s = (-s) % bs
    if pad_s:
        k = jnp.pad(k, ((0, pad_s), (0, 0)))
        v = jnp.pad(v, ((0, pad_s), (0, 0)))
    sp = k.shape[0]
    n_b = sp // bs
    scale = 1.0 / (d ** 0.5)
    len_arr = jnp.asarray(length, jnp.int32).reshape(1, 1)

    scratch = [
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, 1), jnp.float32),
        pltpu.VMEM((hq, d), jnp.float32),
    ]
    return pl.pallas_call(
        functools.partial(_flash_decode_kernel, bs=bs, n_b=n_b, scale=scale),
        grid=(n_b,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((hq, d), lambda i: (0, 0)),
            pl.BlockSpec((bs, d), lambda i: (i, 0)),
            pl.BlockSpec((bs, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((hq, d), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((hq, d), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(len_arr, q, k, v)
