"""The fused conv kernel's share of its roofline, in percent: over the conv
kernel's events in the traced stretch, the least time each call could take
on the chip, over the device time the calls took.

A call's least time is the larger of its FLOPs over peak FLOP/s and its
input, weights, bias and output moved once over peak bytes/s, at the
served dtype (``bench/models.py::roofline_seconds``), for the layer the
call computes.  The conv kernel's events are the device ops whose HLO
instruction is the Pallas call ``conv2d_fused`` makes
(``%_conv_fused_call.N = f32[B,OH,..] custom-call(x, w[FH,FW,C,COUT], ..)``);
the batch, output height and weight shape in that text name the layer.
An event cut by the stretch's edge counts by the part inside it.  Where
no event is found, or one names no layer of the configuration, the metric
is left out."""
import re

from bench import devtrace, models

KERNEL = "%_conv_fused_call"
_SHAPES = re.compile(
    r"= \w+\[(\d+),(\d+),\d+,\d+\]\S* custom-call\(\w+\[[\d,]+\]\S* [^,]+, "
    r"\w+\[(\d+),(\d+),(\d+),(\d+)\]"
)


def is_conv(name: str) -> bool:
    return name.startswith(KERNEL) and "tpu_custom_call" in name


def layer_of(name: str, convs):
    """``(layer, batch)`` that a conv kernel event computes, or None.  The
    kernel may pad channels to its blocks, so the closest layer whose
    channels fit the weight's shape is taken."""
    m = _SHAPES.search(name)
    if m is None:
        return None
    b, oh, fh, _fw, c, cout = map(int, m.groups())
    fits = [
        l for l in convs
        if l.k == fh and l.out_hw[0] == oh and l.cin <= c and l.cout <= cout
    ]
    if not fits:
        return None
    return min(fits, key=lambda l: (c - l.cin) + (cout - l.cout)), b


def read(run):
    if not run.devices or not run.trace_window or run.peak is None:
        return None
    lo, hi = run.trace_window
    cfg = run.cell.config
    convs = [l for l in models.layers(cfg) if l.kind == "conv"]
    ideal = took = 0.0
    for d in sorted(run.devices)[:run.cell.chips]:
        for name, s, dur in run.devices[d]:
            inside = min(s + dur, hi) - max(s, lo)
            if inside <= 0 or not is_conv(name):
                continue
            hit = layer_of(name, convs)
            if hit is None:
                return None
            layer, b = hit
            ideal += models.roofline_seconds(layer, b, cfg["dtype_bytes"], run.peak) * inside / dur
            took += inside * 1e-9
    return 100.0 * ideal / took if took > 0 else None
