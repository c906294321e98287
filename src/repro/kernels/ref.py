"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth a kernel must reproduce; kernel
tests sweep shapes/dtypes and assert_allclose against these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_decode_ref(
    q: jnp.ndarray,  # [Hq, D]
    k: jnp.ndarray,  # [S, D]
    v: jnp.ndarray,  # [S, D]
    length: int | jnp.ndarray,  # valid prefix of the cache
    scale: float | None = None,
) -> jnp.ndarray:
    """Single-kv-head decode attention oracle: softmax(q k^T / sqrt(D)) v
    over the first ``length`` cache slots.  Returns [Hq, D]."""
    s, d = k.shape
    scale = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = (q.astype(jnp.float32) @ k.T.astype(jnp.float32)) * scale  # [Hq, S]
    mask = jnp.arange(s) < length
    logits = jnp.where(mask[None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return (w @ v.astype(jnp.float32)).astype(q.dtype)
