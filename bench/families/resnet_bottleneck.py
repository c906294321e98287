"""ResNet with bottleneck blocks (arXiv:1512.03385, the 50/101/152-layer
columns of Table 1), v1: a stage's stride-2 sits on its first block's
first 1x1 conv and on the projection shortcut.

Configuration keys: ``input_shape``, ``stem`` (``out``, ``kernel``,
``stride``, ``pool`` as ``[kernel, stride, pad]``), ``stages``
(``[channels, blocks]`` per stage), ``expansion``, ``classes``, and
``init.branch_out_scale``, the scale of each branch's last conv weights.
"""
from bench.models import Layer, out_size


def layers(cfg):
    h, w, c = cfg["input_shape"]
    st = cfg["stem"]
    out = [Layer("conv1", "conv", (h, w), c, st["out"], st["kernel"], st["stride"],
                 st["kernel"] // 2)]
    h, w = out[0].out_hw
    c = st["out"]
    pk, ps, pp = st["pool"]
    h, w = out_size(h, pk, ps, pp), out_size(w, pk, ps, pp)
    e = cfg["expansion"]
    scale = cfg["init"].get("branch_out_scale", 1.0)
    for si, (ch, blocks) in enumerate(cfg["stages"], start=2):
        for bi in range(blocks):
            s = 2 if (bi == 0 and si > 2) else 1
            tag = f"res{si}{chr(97 + bi)}"
            a = Layer(f"{tag}_1", "conv", (h, w), c, ch, 1, s, 0)
            b = Layer(f"{tag}_2", "conv", a.out_hw, ch, ch, 3, 1, 1)
            d = Layer(f"{tag}_3", "conv", b.out_hw, ch, ch * e, 1, 1, 0, relu=False,
                      init_scale=scale)
            out += [a, b, d]
            if bi == 0:
                out.append(Layer(f"{tag}_proj", "conv", (h, w), c, ch * e, 1, s, 0,
                                 relu=False))
            h, w = d.out_hw
            c = ch * e
    out.append(Layer("fc", "fc", (1, 1), c, cfg["classes"], relu=False))
    return out


def forward(cfg, params, x, ops):
    ls = {l.name: l for l in layers(cfg)}
    x = ops.relu(ops.conv(x, ls["conv1"], params["conv1"]))
    x = ops.max_pool(x, *cfg["stem"]["pool"])
    for si, (_, blocks) in enumerate(cfg["stages"], start=2):
        for bi in range(blocks):
            tag = f"res{si}{chr(97 + bi)}"
            y = x
            for part in ("_1", "_2", "_3"):
                n = tag + part
                y = ops.conv(y, ls[n], params[n])
                if ls[n].relu:
                    y = ops.relu(y)
            sc = x
            if bi == 0:
                n = f"{tag}_proj"
                sc = ops.conv(x, ls[n], params[n])
            x = ops.relu(ops.add(y, sc))
    return ops.fc(ops.mean(x), params["fc"])
