"""Tests for the layer-performance model (paper §V, Eqs. 3-8)."""
import math

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ConvDescriptor,
    GemmDims,
    MultiCoreModel,
    SingleCoreModel,
    conv_descriptor,
    fc_descriptor,
    hikey970,
)
from repro.core.calibration import (
    _synthetic_multicore_samples,
    microbenchmark_grid,
    synthetic_model,
)


# ------------------------------------------------------------- Eq. 3 and 4
def test_output_shape_eq3():
    d = ConvDescriptor("c", i_w=224, i_h=224, i_d=3, f_w=11, f_h=11, ofm=96,
                       pad=0, stride=4)
    ow, oh, od = d.output_shape()
    assert (ow, oh, od) == (54, 54, 96)  # AlexNet conv1 with pad 0


def test_gemm_dims_eq4():
    d = conv_descriptor("c", 56, 64, 3, 128, stride=1, pad=1)
    g = d.gemm_dims()
    assert g.N == 56 * 56
    assert g.K == 3 * 3 * 64
    assert g.M == 128
    assert g.macs == g.N * g.K * g.M


def test_fc_descriptor_gemm():
    d = fc_descriptor("fc", 4096, 1000)
    g = d.gemm_dims()
    assert (g.N, g.K, g.M) == (1, 4096, 1000)


def test_depthwise_gemm_dims():
    d = conv_descriptor("dw", 56, 64, 3, 64, depthwise=True)
    g = d.gemm_dims()
    assert g.K == 9  # F_d = 1 per group
    assert g.N == 56 * 56 * 64  # group loop folded into rows


@given(
    st.integers(min_value=1, max_value=256),
    st.integers(min_value=1, max_value=11),
    st.integers(min_value=0, max_value=5),
    st.integers(min_value=1, max_value=4),
)
@settings(max_examples=100, deadline=None)
def test_output_shape_matches_floor_formula(i, f, pad, s):
    if f > i + 2 * pad:
        return
    d = ConvDescriptor("c", i_w=i, i_h=i, i_d=8, f_w=f, f_h=f, ofm=8, pad=pad, stride=s)
    ow, oh, _ = d.output_shape()
    assert ow == math.floor((i - f + 2 * pad) / s) + 1
    assert ow >= 1


# ---------------------------------------------------------------- Eq. 5-8
def test_single_core_fit_recovers_linear_model():
    """Eq. 5 is exactly recoverable when the data is generated by Eq. 5."""
    rng = np.random.default_rng(0)
    beta = np.array([1e-8, 2e-8, 3e-8, 4e-10, 5e-10, 6e-10, 7e-12, 1e-5])
    dims = [
        GemmDims(int(n), int(k), int(m))
        for n, k, m in rng.integers(8, 2048, size=(64, 3))
    ]
    feats = lambda g: np.array(
        [g.N, g.K, g.M, g.N * g.K, g.K * g.M, g.N * g.M, g.N * g.M * g.K, 1.0]
    )
    samples = [(g, float(feats(g) @ beta)) for g in dims]
    model = SingleCoreModel.fit(samples)
    assert model.mean_abs_pct_error(samples) < 0.5


def test_multicore_alpha_recovery():
    single = synthetic_model().single
    grid = microbenchmark_grid()
    samples = [(d.gemm_dims(), single.predict(d.gemm_dims())) for d in grid]
    multi_samples = _synthetic_multicore_samples(
        single, samples, tile_size=16, per_iter_dispatch_s=3e-6, pool_overhead_s=2e-5
    )
    m = MultiCoreModel.fit(single, multi_samples, tile_size=16)
    assert abs(m.alpha[1] - 3e-6) < 1e-7
    assert abs(m.alpha[2] - 2e-5) < 1e-6


def test_prediction_error_within_paper_band():
    """Paper Table III: average error 13.2% (Big) / 11.4% (Small).  On the
    synthetic-roofline ground truth our regression must do at least as well
    (<= 15% on held-out shapes)."""
    model = synthetic_model()
    F, B, C = 2.0e9, 8.0e9, 30e-6
    held_out = [
        conv_descriptor("h1", 28, 96, 3, 160),
        conv_descriptor("h2", 56, 48, 5, 96),
        conv_descriptor("h3", 14, 192, 3, 224),
        conv_descriptor("h4", 7, 256, 1, 512),
    ]
    errs = []
    for d in held_out:
        g = d.gemm_dims()
        truth = max(g.flops / F, g.bytes_touched() / B) + C
        pred = model.single.predict(g)
        errs.append(abs(pred - truth) / truth)
    assert float(np.mean(errs)) * 100 < 15.0


def test_multicore_speedup_concave_and_bounded():
    """Fig. 11: speedup is concave, positive, and <= H."""
    model = synthetic_model()
    g = conv_descriptor("c", 56, 64, 3, 128).gemm_dims()
    t = [model.predict(g, cores=h) for h in range(1, 5)]
    speedup = [t[0] / x for x in t]
    assert all(s2 >= s1 - 1e-9 for s1, s2 in zip(speedup, speedup[1:]))
    assert all(s <= h + 1e-9 for h, s in zip(range(1, 5), speedup))
    # concavity: marginal gains shrink (up to ceil() discretization jitter)
    gains = [s2 - s1 for s1, s2 in zip(speedup, speedup[1:])]
    assert all(g2 <= g1 + 0.15 for g1, g2 in zip(gains, gains[1:]))
    # with a small layer (few row-tiles) the overheads dominate and the
    # saturation is unmistakable (paper Fig. 11, conv5-type layers)
    from repro.core import GemmDims
    g_small = GemmDims(N=32, K=64, M=64)
    ts = [model.predict(g_small, cores=h) for h in range(1, 5)]
    sp = [ts[0] / x for x in ts]
    assert sp[3] < 2.5  # far below linear 4x


def test_small_cluster_slower_than_big():
    """Eq. 11 capability ordering between clusters."""
    model = synthetic_model()
    plat = hikey970()
    g = conv_descriptor("c", 28, 128, 3, 128).gemm_dims()
    t_b = {h: model.predict(g, h, plat.speed("B")) for h in range(1, 5)}
    t_s = {h: model.predict(g, h, plat.speed("s")) for h in range(1, 5)}
    assert t_b[4] < t_b[3] < t_b[2] < t_b[1]
    assert t_s[1] > t_b[1]
    assert t_s[4] > t_b[4]


@given(
    st.integers(min_value=8, max_value=4096),
    st.integers(min_value=8, max_value=1024),
    st.integers(min_value=8, max_value=1024),
    st.integers(min_value=1, max_value=4),
)
@settings(max_examples=60, deadline=None)
def test_predict_positive_and_monotone_in_cores(n, k, m, h):
    model = synthetic_model()
    g = GemmDims(n, k, m)
    t_h = model.predict(g, h)
    t_h1 = model.predict(g, h + 1)
    assert t_h > 0
    assert t_h1 <= t_h * 1.001  # more cores never (meaningfully) hurt


# ------------------------------------------------- calibration cache keying
def test_calibration_cache_is_keyed_by_device_kind(tmp_path, monkeypatch):
    """Samples measured on one device kind are never read on another: a
    cache written for "cpu" makes a TPU measure its own grid."""
    import types

    import jax

    import repro.core.calibration as cal

    monkeypatch.setattr(cal, "_CACHE", str(tmp_path / "calibration.json"))
    grid = [d.gemm_dims() for d in microbenchmark_grid()]
    measured = []

    def fake_measure_grid():
        t = 1e-4 * (len(measured) + 1)
        measured.append(t)
        return [({"N": g.N, "K": g.K, "M": g.M}, t * g.macs / 1e6) for g in grid]

    monkeypatch.setattr(cal, "measure_grid", fake_measure_grid)

    def on_kind(kind):
        monkeypatch.setattr(
            jax, "devices", lambda: [types.SimpleNamespace(device_kind=kind)]
        )

    on_kind("cpu")
    cal.calibrate()
    assert len(measured) == 1
    cal.calibrate()  # cache hit for the same kind
    assert len(measured) == 1
    on_kind("TPU v5 lite")
    assert cal.cached_samples("TPU v5 lite") is None
    cal.calibrate()
    assert len(measured) == 2  # measured anew, not read from the cpu entry
    assert cal.cached_samples("cpu") != cal.cached_samples("TPU v5 lite")
