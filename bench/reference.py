"""Plain forward pass of a configuration, and its lower-precision controls.

Straight ``jax.numpy``/``lax`` in float32 at ``Precision.HIGHEST``: direct
convolution, matmul, max-pool, residual add, global average pool, ReLU
and the final softmax, run in the order the configuration's family
(``bench/families/<family>.py``) gives.  It imports nothing of the
program and is given only the weights the benchmark made from the seed.

``quant`` computes it lower, as a control:

``"bf16"``  bfloat16 storage: the images, weights, biases and every
            layer's output (each conv and fc, residual sum and pool) are
            rounded to bfloat16; products and sums stay float32.
``"int8"``  every conv and fc operand rounded to int8 (activations per
            image and per tensor, weights per output channel, both
            symmetric); the products stay exact in float32.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .models import family

HI = lax.Precision.HIGHEST
QUANTS = (None, "bf16", "int8")


def _int8(x, axes):
    """Symmetric int8 rounding of ``x`` with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127) * s


class Ops:
    """The operations a family's ``forward`` runs, at one precision."""

    def __init__(self, quant: Optional[str] = None):
        if quant not in QUANTS:
            raise ValueError(f"quant must be one of {QUANTS}, got {quant!r}")
        self.quant = quant

    def store(self, x):
        """``x`` as a layer's output is kept between layers."""
        if self.quant == "bf16":
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return x

    def _operands(self, x, w, x_axes, w_axes):
        if self.quant == "int8":
            return _int8(x, x_axes), _int8(w, w_axes)
        return self.store(x), self.store(w)

    def conv(self, x, l, p):
        x, w = self._operands(x, p["w"], (1, 2, 3), (0, 1, 2))
        y = lax.conv_general_dilated(
            x, w, (l.stride, l.stride), [(l.pad, l.pad), (l.pad, l.pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HI,
        )
        return self.store(y + self.store(p["b"]))

    def fc(self, x, p):
        x = x.reshape(x.shape[0], -1)  # NHWC flattened row-major (H, W, C)
        x, w = self._operands(x, p["w"], (1,), (0,))
        return self.store(jnp.dot(x, w, precision=HI) + self.store(p["b"]))

    @staticmethod
    def max_pool(x, k, s, pad):
        return lax.reduce_window(
            x, -jnp.inf, lax.max, (1, k, k, 1), (1, s, s, 1),
            [(0, 0), (pad, pad), (pad, pad), (0, 0)],
        )

    @staticmethod
    def relu(x):
        return jnp.maximum(x, 0.0)

    def add(self, a, b):
        return self.store(a + b)

    def mean(self, x):
        """Global average pool over H and W."""
        return self.store(x.mean(axis=(1, 2)))


def forward(cfg, params, x, quant: Optional[str] = None):
    """Class probabilities ``[B, classes]`` for images ``x`` ``[B, H, W, C]``."""
    logits = family(cfg["family"]).forward(cfg, params, x, Ops(quant))
    return jax.nn.softmax(logits, axis=-1)


def run_blocks(cfg, params, images, block: int, quant: Optional[str] = None):
    """The forward pass over ``images`` (host array) in blocks of ``block``
    rows, one compiled program for all blocks; returns a host array."""
    import numpy as np

    fn = jax.jit(lambda p, x: forward(cfg, p, x, quant))
    n = len(images)
    outs = []
    for i in range(0, n, block):
        chunk = images[i:i + block]
        if len(chunk) < block:  # pad the last block to the compiled shape
            chunk = np.concatenate(
                [chunk, np.zeros((block - len(chunk), *chunk.shape[1:]), chunk.dtype)]
            )
        outs.append(np.asarray(fn(params, chunk)))
    return np.concatenate(outs)[:n]
