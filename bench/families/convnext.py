"""ConvNeXt (arXiv:2201.03545): a 4x4/4 patchify stem and LayerNorm, stages
of blocks (depthwise kxk conv, LayerNorm over channels, 1x1 conv to
``mlp_ratio`` times the width, exact GELU, 1x1 conv back, residual add),
LayerNorm and a 2x2/2 conv between stages, then global average pool,
LayerNorm and fc.

Configuration keys: ``input_shape``, ``stem`` (``out``, ``kernel``,
``stride``), ``depths`` and ``dims`` per stage, ``kernel`` (the depthwise
size), ``mlp_ratio``, ``ln_eps``, ``classes``, and ``init.layer_scale``,
the scale of each block's last 1x1 conv weights (its layer scale, folded
in).  LayerNorms carry no affine: each folds into the linear layer after
it, and the stem's stays at the identity it starts from.

A depthwise conv is listed as ``Layer(kind="conv", cin=1, cout=C)``: its
weight is ``[k, k, 1, C]`` with fan-in k*k, and ``bench/models.py``
counts its FLOPs as 2*k*k*C*OH*OW.  ``forward`` runs it itself, as a
grouped convolution, since ``Ops.conv`` is dense.
"""
import jax
import jax.numpy as jnp
from jax import lax

from bench.models import Layer

HI = lax.Precision.HIGHEST


def layers(cfg):
    h, w, c = cfg["input_shape"]
    st = cfg["stem"]
    k, r = cfg["kernel"], cfg["mlp_ratio"]
    scale = cfg["init"].get("layer_scale", 1.0)
    stem = Layer("stem", "conv", (h, w), c, st["out"], st["kernel"], st["stride"], 0,
                 relu=False)
    out = [stem]
    (h, w), c = stem.out_hw, st["out"]
    for si, (depth, dim) in enumerate(zip(cfg["depths"], cfg["dims"]), start=1):
        if si > 1:
            ds = Layer(f"ds{si}", "conv", (h, w), c, dim, 2, 2, 0, relu=False)
            out.append(ds)
            (h, w), c = ds.out_hw, dim
        for bi in range(1, depth + 1):
            tag = f"s{si}b{bi}"
            out += [
                Layer(f"{tag}_dw", "conv", (h, w), 1, c, k, 1, k // 2, relu=False),
                Layer(f"{tag}_pw1", "conv", (h, w), c, r * c, 1, 1, 0, relu=False),
                Layer(f"{tag}_pw2", "conv", (h, w), r * c, c, 1, 1, 0, relu=False,
                      init_scale=scale),
            ]
    out.append(Layer("fc", "fc", (1, 1), c, cfg["classes"], relu=False))
    return out


def forward(cfg, params, x, ops):
    ls = {l.name: l for l in layers(cfg)}
    eps = cfg["ln_eps"]

    def ln(x):
        mu = x.mean(axis=-1, keepdims=True)
        var = jnp.square(x - mu).mean(axis=-1, keepdims=True)
        return ops.store((x - mu) / jnp.sqrt(var + eps))

    def dwconv(x, l, p):
        x, w = ops._operands(x, p["w"], (1, 2, 3), (0, 1, 2))
        y = lax.conv_general_dilated(
            x, w, (l.stride, l.stride), [(l.pad, l.pad), (l.pad, l.pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=x.shape[-1], precision=HI,
        )
        return ops.store(y + ops.store(p["b"]))

    x = ln(ops.conv(x, ls["stem"], params["stem"]))
    for si, depth in enumerate(cfg["depths"], start=1):
        if si > 1:
            n = f"ds{si}"
            x = ops.conv(ln(x), ls[n], params[n])
        for bi in range(1, depth + 1):
            tag = f"s{si}b{bi}"
            y = ln(dwconv(x, ls[tag + "_dw"], params[tag + "_dw"]))
            y = ops.conv(y, ls[tag + "_pw1"], params[tag + "_pw1"])
            y = ops.store(jax.nn.gelu(y, approximate=False))
            y = ops.conv(y, ls[tag + "_pw2"], params[tag + "_pw2"])
            x = ops.add(y, x)
    return ops.fc(ln(ops.mean(x)), params["fc"])
