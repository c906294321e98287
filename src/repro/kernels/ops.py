"""Public wrappers for the scaling-substrate Pallas kernels (flash decode,
SSD scan), with backend selection.

``backend``:
  "compiled"   — compiled Pallas (real TPU).
  "interpret"  — Pallas interpret mode (CPU validation; kernel body runs
                 in Python, numerically identical to TPU semantics).
  "jnp"        — the pure-jnp reference path (fast on CPU; used by default
                 for CPU benchmarks so wall-times are meaningful).

Default resolves by platform: TPU -> compiled, CPU -> jnp.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref
from .flash_decode import flash_decode as _flash_decode_pallas


def _default_backend() -> str:
    return "compiled" if jax.default_backend() == "tpu" else "jnp"


def flash_decode(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, length,
    backend: Optional[str] = None,
) -> jnp.ndarray:
    backend = backend or _default_backend()
    if backend == "jnp":
        return ref.flash_decode_ref(q, k, v, length)
    return _flash_decode_pallas(q, k, v, length, interpret=(backend == "interpret"))


def ssd(x, log_a, B, C, h0, chunk: int = 128, backend: Optional[str] = None):
    """Chunked selective scan (single sequence [S,H,P]; vmap for batch).

    On TPU the Pallas kernel keeps the [N,P] state in VMEM scratch across
    chunks; the jnp path is repro.models.ssm.ssd_scan (the oracle) and is
    what the models lower through on this CPU container."""
    backend = backend or _default_backend()
    if backend == "jnp":
        from ..models.ssm import ssd_scan

        y, hf = ssd_scan(x[None], log_a[None], B[None], C[None], chunk=chunk, h0=h0[None])
        return y[0], hf[0]
    from .ssd import ssd as _ssd_pallas

    return _ssd_pallas(x, log_a, B, C, h0, chunk=chunk, interpret=(backend == "interpret"))
