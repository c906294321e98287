"""ConvNeXt-T's plain reference against the program's graph, its control,
and the depthwise convs' roofline reader.

On the CPU at 64x64 images (every width as published): the reference
(``bench/families/convnext.py``) must agree with ``Graph.apply`` on the
``"xla"`` route, its softmax must not saturate, and its int8 control must
read above the configuration's limit.  ``dwconv_roofline`` is read from a
recorded stretch of a v5e trace of ``convnext_tiny.offline``.
"""
import os
import types

import numpy as np
import pytest

from bench import devtrace, harness, models, reference

HW = 64
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_convnext_tiny_offline.json")


def _cfg(hw=HW):
    cfg = harness.load_json(harness.BENCH_DIR, "configs", "convnext_tiny.json")
    return dict(cfg, input_shape=[hw, hw, 3])


@pytest.fixture(scope="module")
def small():
    """(cfg, params, images, reference answers, program answers, graph)."""
    import jax

    from repro.cnn.models import convnext_tiny

    cfg = _cfg()
    params = models.make_params(cfg, 2**31 + 5)
    images = models.make_images(cfg, 2**31 + 5, 4)
    graph = convnext_tiny()
    graph.input_shape = (HW, HW, 3)
    prog = np.asarray(jax.jit(lambda p, x: graph.apply(p, x, backend="xla"))(params, images))
    ref = reference.run_blocks(cfg, params, images, block=2)
    return cfg, params, images, ref, prog, graph


def test_reference_matches_program_graph(small):
    cfg, params, images, ref, prog, _ = small
    assert ref.shape == prog.shape == (4, 1000)
    assert np.allclose(ref.sum(axis=1), 1.0, atol=1e-5)
    assert harness.logit_gap(prog, ref).max() < 1e-4


def test_softmax_not_saturated(small):
    assert small[3].max() < 0.5


def test_int8_control_fails_the_limit(small):
    cfg, params, images, ref, prog, _ = small
    ctl = reference.run_blocks(cfg, params, images, block=2, quant="int8")
    limit = cfg["check"]["limit"]
    assert harness.logit_gap(ctl, ref).max() > limit
    assert harness.logit_gap(prog, ref).max() < limit / 10


def test_layer_names_and_shapes_are_the_program_graphs(small):
    import jax

    cfg, params, _, _, _, graph = small
    program = jax.eval_shape(graph.init, jax.random.PRNGKey(0))
    want = {n: {k: tuple(v.shape) for k, v in p.items()} for n, p in program.items()}
    assert models.param_shapes(cfg) == want
    assert [l.name for l in models.layers(cfg)] == [n.name for n in graph.major_nodes()]


def test_work_counts_at_224():
    cfg = _cfg(224)
    ls = models.layers(cfg)
    dw = [l for l in ls if l.cin == 1]
    assert len(ls) == 59 and len(dw) == 18
    assert models.flops(dw[0]) == 2 * 49 * 96 * 56 * 56
    assert models.flops_per_image(cfg) == 8_911_062_528
    n_params = sum(int(np.prod(s["w"])) + int(np.prod(s["b"]))
                   for s in models.param_shapes(cfg).values())
    assert round(n_params / 1e6, 2) == 28.57


@pytest.fixture(scope="module")
def cell():
    return types.SimpleNamespace(config=_cfg(224), chips=1)


@pytest.fixture(scope="module")
def traced(cell):
    devs, host = devtrace.load_fixture(FIXTURE)
    return types.SimpleNamespace(devices=devs, trace_window=devtrace.window(host),
                                 peak=harness.peak_for("TPU v5 lite"), cell=cell)


def test_dwconv_roofline_on_recorded_trace(traced):
    """Each depthwise op of the stretch names a layer of the configuration,
    and nothing else (kernel calls, weight copies) reads as one."""
    read = harness.reader("dwconv_roofline")
    shape = read.__globals__["depthwise_shape"]
    ops = traced.devices[sorted(traced.devices)[0]]
    hits = {shape(n) for n, _, _ in ops} - {None}
    dws = {(l.out_hw[0], l.k, l.cout) for l in models.layers(traced.cell.config) if l.cin == 1}
    assert hits and {h[1:] for h in hits} <= dws
    assert not any(shape(n) for n, _, _ in ops if not n.startswith("%fusion"))
    share = read(traced)
    assert share is not None and 0.0 < share <= 100.0


def test_dwconv_roofline_by_hand(cell):
    """Made-up events of s1b1's depthwise conv at batch 32, each taking
    twice its least time, read 50%, whether XLA's output fusion writes the
    conv alone or beside the LayerNorm's first sum (a tuple result); the
    weight's copies and bitcast, which read and write [7,7,1,96] too, are
    no conv."""
    mod = harness.reader("dwconv_roofline").__globals__
    peak = harness.peak_for("TPU v5 lite")
    (layer,) = [l for l in models.layers(cell.config) if l.name == "s1b1_dw"]
    least = mod["least_seconds"](layer, 32, 4, peak)
    bytes_ = 4 * (2 * 32 * 56 * 56 * 96 + 49 * 96 + 96)
    assert least == pytest.approx(bytes_ / peak["hbm_bytes_per_s"])
    w = "f32[7,7,1,96]{3,2,1,0:T(1,128)S(1)}"
    x = "f32[32,56,56,96]{3,0,2,1:T(8,128)S(1)}"
    conv = (f"%fusion.4 = {x} fusion({w} %copy-done, {x} %subtract_multiply_fusion.1), "
            "kind=kOutput, calls=%fused_computation.8")
    with_ln = (f"%fusion.76 = (f32[32,56,56]{{0,2,1:T(8,128)S(1)}}, {x}) fusion(f32[96]{{0}} "
               f"%copy-done.45, {x} %copy.127, {w} %copy-done), kind=kOutput, "
               "calls=%fused_computation.122")
    weights = [
        f"%copy-start = ({w}, f32[7,7,1,96]{{3,2,1,0:T(1,128)}}, u32[]{{:S(2)}}) "
        "copy-start(f32[7,7,1,96]{3,2,1,0:T(1,128)} %p__s1b1_dw____w__.1)",
        f"%copy-done = {w} copy-done(({w}, f32[7,7,1,96], u32[]) %copy-start)",
        f"%fusion.89 = f32[7,7,1,96]{{3,2,1,0:T(1,128)}} fusion({w} %param_2.305), "
        "kind=kLoop, calls=%bitcast_fusion.11",
    ]
    assert [mod["depthwise_shape"](n) for n in (conv, with_ln)] == [(32, 56, 7, 96)] * 2
    assert [mod["depthwise_shape"](n) for n in weights] == [None] * 3
    ops = [(conv, 0.0, 2 * least * 1e9), (with_ln, 0.0, 2 * least * 1e9)]
    ops += [(n, 0.0, 1e6) for n in weights]
    run = types.SimpleNamespace(devices={"/device:TPU:0": ops}, trace_window=(0.0, 1e12),
                                peak=peak, cell=cell)
    assert harness.reader("dwconv_roofline")(run) == pytest.approx(50.0)
    ops.append((conv.replace("32,56,56,96", "32,9,9,96"), 0.0, 1.0))
    assert harness.reader("dwconv_roofline")(run) is None  # names no layer
