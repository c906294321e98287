"""Architectures of the benchmark's configurations, kept apart from the program.

A configuration file (``bench/configs/<name>.json``) describes its network
as data: a ``family`` and that family's published sizes.  The family is
``bench/families/<family>.py``, found by that name, so a configuration of
a new family comes with a file of its own.  ``layers(cfg)`` turns it into
the ordered list of layers that the plain reference runs, the weights are
made from, and the work counts are taken over.  Nothing
here imports the program; the parameter names are those the program's
``params`` dict uses (``conv1_1``, ``res2a_1``, ``fc6``, ...), which is
the one interface the benchmark shares with it.

Layer tuple fields (all sizes per image, NHWC):

    Layer(name, kind, in_hw, cin, cout, k, stride, pad, relu, init_scale)

``kind`` is ``conv`` or ``fc`` (``in_hw`` then is ``(1, 1)`` and ``cin``
the flattened feature count).
"""
from __future__ import annotations

import hashlib
import importlib
from typing import Dict, List, NamedTuple, Tuple


class Layer(NamedTuple):
    name: str
    kind: str  # "conv" | "fc"
    in_hw: Tuple[int, int]
    cin: int
    cout: int
    k: int = 1
    stride: int = 1
    pad: int = 0
    relu: bool = True
    init_scale: float = 1.0  # on the weights' initial std

    @property
    def out_hw(self) -> Tuple[int, int]:
        h, w = self.in_hw
        return (
            out_size(h, self.k, self.stride, self.pad),
            out_size(w, self.k, self.stride, self.pad),
        )


def out_size(n: int, k: int, s: int, p: int) -> int:
    return (n - k + 2 * p) // s + 1


def family(name: str):
    """The module ``bench/families/<name>.py``: its ``layers(cfg)`` lists
    the configuration's layers, and its ``forward(cfg, params, x, ops)``
    runs them with the reference's operations."""
    return importlib.import_module(f"bench.families.{name}")


def layers(cfg) -> List[Layer]:
    """The configuration's conv and fc layers, in execution order."""
    return family(cfg["family"]).layers(cfg)


# ------------------------------------------------------------------ work
def flops(layer: Layer, batch: int = 1) -> int:
    """Multiply-adds times two, as the layer's shapes require."""
    oh, ow = layer.out_hw
    return 2 * batch * oh * ow * layer.k * layer.k * layer.cin * layer.cout


def min_bytes(layer: Layer, batch: int, dtype_bytes: int) -> int:
    """Input, weights, bias and output, each moved once: the least traffic
    any implementation of the layer needs."""
    h, w = layer.in_hw
    oh, ow = layer.out_hw
    elems = (
        batch * h * w * layer.cin
        + layer.k * layer.k * layer.cin * layer.cout
        + layer.cout
        + batch * oh * ow * layer.cout
    )
    return dtype_bytes * elems


def flops_per_image(cfg) -> int:
    return sum(flops(l) for l in layers(cfg))


def roofline_seconds(layer: Layer, batch: int, dtype_bytes: int, peak) -> float:
    """The least time the chip could take for one call of the layer."""
    return max(
        flops(layer, batch) / peak["flops_per_s"],
        min_bytes(layer, batch, dtype_bytes) / peak["hbm_bytes_per_s"],
    )


# --------------------------------------------------------------- weights
def seed_words(seed: int, salt: str) -> Tuple[int, int]:
    """Two 32-bit words from any whole-number seed (negative or past 64
    bits included), so that distinct seeds never share a key."""
    d = hashlib.sha256(f"{salt}:{seed}".encode()).digest()
    return int.from_bytes(d[:4], "little"), int.from_bytes(d[4:8], "little")


def param_shapes(cfg) -> Dict[str, Dict[str, Tuple[int, ...]]]:
    out = {}
    for l in layers(cfg):
        if l.kind == "conv":
            out[l.name] = {"w": (l.k, l.k, l.cin, l.cout), "b": (l.cout,)}
        else:
            out[l.name] = {"w": (l.cin, l.cout), "b": (l.cout,)}
    return out


def make_params(cfg, seed: int):
    """All weights in one jitted call on the default device, float32.

    Conv weights are He-normal and fc weights LeCun-normal; biases are
    normal with ``init.bias_std``.  A family may scale a layer's std by
    its ``init_scale``: a residual network without normalisation layers
    otherwise grows its residual stream with depth until the softmax
    saturates and every output is one-hot.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    init = cfg["init"]
    ls = layers(cfg)

    def build(key):
        params = {}
        for i, l in enumerate(ls):
            kw, kb = jax.random.split(jax.random.fold_in(key, i))
            if l.kind == "conv":
                shape = (l.k, l.k, l.cin, l.cout)
                std = np.sqrt(2.0 / (l.k * l.k * l.cin)) * l.init_scale
            else:
                shape = (l.cin, l.cout)
                std = np.sqrt(1.0 / l.cin) * l.init_scale
            params[l.name] = {
                "w": jax.random.normal(kw, shape, jnp.float32) * std,
                "b": jax.random.normal(kb, (l.cout,), jnp.float32) * init["bias_std"],
            }
        return params

    key = jnp.asarray(seed_words(seed, "weights"), jnp.uint32)
    return jax.jit(build)(key)


def make_images(cfg, seed: int, n: int):
    """``n`` distinct standard-normal images on the host, float32."""
    import numpy as np

    rng = np.random.default_rng(seed_words(seed, "images"))
    return rng.standard_normal((n, *cfg["input_shape"]), dtype=np.float32)
