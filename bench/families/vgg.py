"""VGG (arXiv:1409.1556): stages of same-size convs, each closed by a 2x2
max-pool, then fully connected layers.

Configuration keys: ``input_shape``, ``conv_stages`` (``[channels,
repeats]`` per stage), ``kernel``, ``fc`` (output widths).
"""
from bench.models import Layer, out_size


def layers(cfg):
    h, w, c = cfg["input_shape"]
    k = cfg["kernel"]
    out = []
    for bi, (ch, reps) in enumerate(cfg["conv_stages"], start=1):
        for ri in range(1, reps + 1):
            out.append(Layer(f"conv{bi}_{ri}", "conv", (h, w), c, ch, k, 1, k // 2))
            c = ch
        h, w = out_size(h, 2, 2, 0), out_size(w, 2, 2, 0)  # 2x2 max-pool, stride 2
    feats = h * w * c
    fcs = cfg["fc"]
    for i, of in enumerate(fcs):
        last = i == len(fcs) - 1
        out.append(Layer(f"fc{6 + i}", "fc", (1, 1), feats, of, relu=not last))
        feats = of
    return out


def forward(cfg, params, x, ops):
    ls = {l.name: l for l in layers(cfg)}
    for bi, (_, reps) in enumerate(cfg["conv_stages"], start=1):
        for ri in range(1, reps + 1):
            n = f"conv{bi}_{ri}"
            x = ops.relu(ops.conv(x, ls[n], params[n]))
        x = ops.max_pool(x, 2, 2, 0)
    for i in range(len(cfg["fc"])):
        n = f"fc{6 + i}"
        x = ops.fc(x, params[n])
        if ls[n].relu:
            x = ops.relu(x)
    return x
