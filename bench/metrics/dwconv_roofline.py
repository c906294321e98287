"""The depthwise convs' share of their roofline, in percent: over XLA's
depthwise-conv ops in the traced stretch, the least time each could take
on the chip, over the device time they took.

A depthwise op is an ``XLA Ops`` event of a convolution, or of an output
fusion (``kind=kOutput``) around one, that reads a weight operand
``[k,k,1,C]`` and writes a ``[B,OH,OW,C]`` result; the configuration's
depthwise layer (``cin`` 1, ``cout`` C, kernel k, output height OH) names
what it computes.  Its least time is the larger of 2*k*k*C*OH*OW*B FLOPs
over peak FLOP/s and its input, weights, bias and output moved once over
peak bytes/s, counted here: ``bench/models.py::min_bytes`` assumes a
dense conv and would count a depthwise input as one channel.  On a v5e
XLA fuses the bias add and the next LayerNorm's first sum into the same
op (a tuple result); the whole op's time counts against the conv's least
time.  Weight copies (``copy-start``, ``copy-done``) and the weight's
bitcasts read and write ``[k,k,1,C]`` too, but are no convolution.  An
event cut by the stretch's edge counts by the part inside it.  Where no
such event is found, or one names no depthwise layer of the
configuration, the metric is left out."""
import re

from bench import models

# "%fusion.4 = <result shapes> fusion(<operands>), kind=kOutput, ..."
_OP = re.compile(r"^%?[\w.-]+ = (.*?) (convolution|fusion)\((.*)$")
_ARRAY4 = re.compile(r"\w+\[(\d+),(\d+),(\d+),(\d+)\]")
_WEIGHT = re.compile(r"\w+\[(\d+),(\d+),1,(\d+)\]")


def depthwise_shape(name: str):
    """``(batch, out_h, k, channels)`` of a depthwise-conv event, or None."""
    m = _OP.match(name)
    if m is None:
        return None
    result, op, rest = m.groups()
    if op == "fusion" and "kind=kOutput" not in rest:
        return None
    for w in _WEIGHT.finditer(rest):
        kh, kw, c = map(int, w.groups())
        if kh != kw:
            continue
        for out in _ARRAY4.finditer(result):
            b, oh, _ow, oc = map(int, out.groups())
            if oc == c:
                return b, oh, kh, c
    return None


def least_seconds(layer, batch: int, dtype_bytes: int, peak) -> float:
    h, w = layer.in_hw
    oh, ow = layer.out_hw
    c, k = layer.cout, layer.k
    flops = 2 * batch * k * k * c * oh * ow
    elems = batch * h * w * c + k * k * c + c + batch * oh * ow * c
    return max(flops / peak["flops_per_s"], elems * dtype_bytes / peak["hbm_bytes_per_s"])


def read(run):
    if not run.devices or not run.trace_window or run.peak is None:
        return None
    lo, hi = run.trace_window
    cfg = run.cell.config
    dws = [l for l in models.layers(cfg) if l.kind == "conv" and l.cin == 1]
    ideal = took = 0.0
    for d in sorted(run.devices)[:run.cell.chips]:
        for name, s, dur in run.devices[d]:
            inside = min(s + dur, hi) - max(s, lo)
            if inside <= 0:
                continue
            shape = depthwise_shape(name)
            if shape is None:
                continue
            b, oh, k, c = shape
            fits = [l for l in dws if l.k == k and l.cout == c and l.out_hw[0] == oh]
            if not fits:
                return None
            ideal += least_seconds(fits[0], b, cfg["dtype_bytes"], run.peak) * inside / dur
            took += inside * 1e-9
    return 100.0 * ideal / took if took > 0 else None
