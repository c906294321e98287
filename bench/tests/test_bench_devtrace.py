"""The trace reduction, on a recorded TPU trace and on made-up ones."""
import os

import numpy as np
import pytest

from bench import devtrace, harness

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_vgg16_offline.json")


@pytest.fixture(scope="module")
def recorded():
    devs, host = devtrace.load_fixture(FIXTURE)
    lo, hi = devtrace.window(host)
    return devs["/device:TPU:0"], host, lo, hi


def _busy_by_grid(ops, lo, hi, step=1000.0):
    """The union of the op intervals, counted on a 1 us grid."""
    grid = np.zeros(int((hi - lo) / step) + 1, bool)
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) / step):int(np.ceil((b - lo) / step))] = True
    return grid.sum() * step


def test_window_is_the_benchmark_span(recorded):
    ops, host, lo, hi = recorded
    assert hi - lo == pytest.approx(0.4e9)


def test_busy_matches_a_grid_count(recorded):
    ops, host, lo, hi = recorded
    busy = devtrace.busy_ns(ops, lo, hi)
    assert busy == pytest.approx(_busy_by_grid(ops, lo, hi), rel=2e-3)
    assert 0.5 < busy / (hi - lo) < 1.0  # the chip idles part of the time


def test_conv_kernel_time(recorded):
    ops, host, lo, hi = recorded
    conv = harness.reader("conv_roofline").__globals__["is_conv"]
    n = sum(1 for name, _, _ in ops if conv(name))
    assert n == 35  # 13 convs per batch of 32, batches cut by the window's end
    kernel = devtrace.op_ns(ops, lo, hi, conv)
    assert 0 < kernel < devtrace.busy_ns(ops, lo, hi)
    # the two 224x224 convs take the most device time
    top = devtrace.top_ops(ops, lo, hi, 2)
    assert all(name.startswith("%_conv_fused_call") and "[32,224," in name for name, _ in top)
    assert top[0][1] >= top[1][1]


def test_idle_gaps_are_labelled(recorded):
    ops, host, lo, hi = recorded
    gaps = devtrace.idle_gaps(ops, host, lo, hi, 5)
    assert len(gaps) == 5
    secs = [s for _, s in gaps]
    assert secs == sorted(secs, reverse=True)
    busy = devtrace.busy_ns(ops, lo, hi)
    assert sum(secs) <= (hi - lo - busy) * 1e-9 + 1e-12
    assert all("@" in label for label, _ in gaps)


def test_made_up_trace():
    ops = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 10.0), ("a", 95.0, 10.0)]
    host = [("t", "bench.submit", 16.0, 10.0), ("u", "other", 40.0, 50.0),
            ("t", devtrace.WINDOW_SPAN, 0.0, 100.0)]
    lo, hi = devtrace.window(host)
    assert devtrace.busy_intervals(ops, lo, hi) == [(0.0, 15.0), (30.0, 40.0), (95.0, 100.0)]
    assert devtrace.busy_ns(ops, lo, hi) == 30.0
    assert devtrace.op_ns(ops, lo, hi, lambda n: n == "a") == 15.0
    top = devtrace.top_ops(ops, lo, hi)
    assert [n for n, _ in top] == ["a", "b", "c"]
    assert [s for _, s in top] == pytest.approx([15e-9, 10e-9, 10e-9])
    gaps = devtrace.idle_gaps(ops, host, lo, hi)
    assert [n for n, _ in gaps] == ["u/other @0.000s", "t/bench.submit @0.000s"]
    assert [s for _, s in gaps] == pytest.approx([55e-9, 15e-9])


def test_conv_events_name_their_layers(recorded):
    """Every conv kernel event's shapes name a vgg16 layer, and the share of
    the roofline over the recorded stretch lies between 0 and 100%."""
    import types

    from bench import models

    ops, host, lo, hi = recorded
    mod = harness.reader("conv_roofline").__globals__
    cfg = harness.load_json(harness.BENCH_DIR, "configs", "vgg16.json")
    convs = [l for l in models.layers(cfg) if l.kind == "conv"]
    hits = [mod["layer_of"](n, convs) for n, _, _ in ops if mod["is_conv"](n)]
    assert len(hits) == 35 and all(h is not None for h in hits)
    assert {b for _, b in hits} == {32}
    assert {l.name for l, _ in hits} >= {"conv1_1", "conv1_2", "conv4_1"}
    run = types.SimpleNamespace(
        devices={"/device:TPU:0": ops}, trace_window=(lo, hi),
        peak=harness.peak_for("TPU v5 lite"),
        cell=types.SimpleNamespace(config=cfg, chips=1),
    )
    share = harness.reader("conv_roofline")(run)
    assert 0 < share < 100
    # a call the configuration has no layer for leaves the metric out
    odd = [(n.replace("f32[3,3,64,64]", "f32[5,5,64,64]"), s, d) for n, s, d in ops]
    run.devices = {"/device:TPU:0": odd}
    assert harness.reader("conv_roofline")(run) is None
