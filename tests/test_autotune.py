"""Autotuner cache behaviour and its calibration hand-off.

Acceptance (ISSUE 3): the cache round-trips — a second tuner on the same
JSON file reproduces the identical plan with ZERO re-timing — and
`LayerTimePredictor` consumes autotuner measurements without API breaks.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cnn import MODELS
from repro.core.calibration import synthetic_model
from repro.core.descriptors import conv_descriptor
from repro.core.perfmodel import LayerTimePredictor
from repro.core.platform import hikey970
from repro.kernels.autotune import (
    BlockConfig,
    ConvAutotuner,
    candidate_blocks,
    descriptor_key,
)
from repro.kernels.backend import measure_graph_routes, resolve_backend
from repro.serving.planner import AutoPlanner

TINY = conv_descriptor("tiny", 8, 4, 3, 8, stride=1)


@pytest.fixture(autouse=True)
def _hermetic_interpret_env(monkeypatch):
    """Keep route measurement on the resolved (XLA) route regardless of a
    user-set REPRO_PALLAS_INTERPRET; sweeps opt in via sweep=True."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_SWEEP", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)


def test_descriptor_key_is_geometry_not_name():
    a = conv_descriptor("conv1_1", 14, 256, 3, 512)
    b = conv_descriptor("conv1_2", 14, 256, 3, 512)
    c = conv_descriptor("conv1_3", 14, 256, 3, 256)
    assert descriptor_key(a) == descriptor_key(b)
    assert descriptor_key(a) != descriptor_key(c)


def test_candidate_blocks_clipped_to_dims():
    for cfg in candidate_blocks(ow=14, cout=48, cin=20):
        assert cfg.bm <= 14 and cfg.bn <= 48 and cfg.bk <= 20
    assert len(candidate_blocks(14, 48, 20)) >= 2  # something to sweep


@pytest.mark.parametrize("name", sorted(MODELS))
def test_candidate_blocks_tile_legal_for_zoo(name):
    """Every block the sweep offers obeys the TPU's (8, 128) tiling rule:
    bm a multiple of 8 or the whole output row, bn/bk multiples of 128
    or the whole cout/cin."""
    for d in MODELS[name]().descriptors():
        if d.kind != "conv" or d.groups != 1:
            continue
        ow = d.output_shape()[0]
        for c in candidate_blocks(ow, d.ofm, d.i_d):
            assert c.bm % 8 == 0 or c.bm == ow, (d.name, c)
            assert c.bn % 128 == 0 or c.bn == d.ofm, (d.name, c)
            assert c.bk % 128 == 0 or c.bk == d.i_d, (d.name, c)


def test_sweep_candidate_failure_raises(tmp_path, monkeypatch):
    """A candidate that fails to compile or run stops the sweep; it is
    not dropped, and nothing is recorded as swept."""
    import repro.kernels.conv_fused as conv_fused

    def refused(*args, **kwargs):
        raise RuntimeError("refused by the compiler")

    monkeypatch.setattr(conv_fused, "conv2d_fused", refused)
    t = ConvAutotuner(cache_path=str(tmp_path / "tune.json"), sweep=True,
                      repeats=1, proxy_rows=2)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        t.tune(TINY)
    assert t.entry(TINY) is None


def test_cache_is_keyed_by_device_kind(tmp_path):
    """Times written for one device kind are never read on another."""
    cache = str(tmp_path / "tune.json")
    cpu = ConvAutotuner(cache_path=cache, device_kind="cpu", sweep=False, repeats=1)
    cpu.measure_route(TINY, lambda: None, route="xla")
    again = ConvAutotuner(cache_path=cache, device_kind="cpu", sweep=False)
    assert again.measured_route(TINY, "xla") is not None
    tpu = ConvAutotuner(cache_path=cache, device_kind="TPU v5 lite", sweep=False)
    assert tpu.entry(TINY) is None
    assert ConvAutotuner(cache_path=cache, sweep=False).device_kind == (
        jax.devices()[0].device_kind
    )


def test_sweep_cache_round_trip_zero_retiming(tmp_path):
    cache = str(tmp_path / "tune.json")
    t1 = ConvAutotuner(cache_path=cache, sweep=True, repeats=1, proxy_rows=2)
    cfg1 = t1.tune(TINY)
    assert t1.timings_run > 0
    assert isinstance(cfg1, BlockConfig)
    entry = t1.entry(TINY)
    assert entry["swept"] and entry["candidates"] > 0

    # fresh tuner, same cache file: identical plan, zero re-timing
    t2 = ConvAutotuner(cache_path=cache, sweep=True, repeats=1, proxy_rows=2)
    cfg2 = t2.tune(TINY)
    assert cfg2 == cfg1
    assert t2.timings_run == 0

    with open(cache) as f:
        data = json.load(f)
    assert data["version"] == 1
    assert descriptor_key(TINY) in data["platforms"][jax.devices()[0].device_kind]


def test_route_measurement_cached(tmp_path):
    cache = str(tmp_path / "tune.json")
    t = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    calls = []
    t.measure_route(TINY, lambda: calls.append(1))
    assert t.timings_run == 1 and len(calls) == 2  # warm + 1 timed rep
    t.measure_route(TINY, lambda: calls.append(1))
    assert t.timings_run == 1  # cache hit, fn never called again
    assert len(calls) == 2
    t2 = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    assert t2.measure_route(TINY, lambda: (_ for _ in ()).throw(AssertionError)) > 0
    assert t2.timings_run == 0
    assert descriptor_key(TINY) in t2.route_seconds()


def test_route_measurements_are_keyed_per_backend_route(tmp_path):
    """An "xla" measurement must never be served as the "pallas_fused"
    time for the same geometry (they are different kernels)."""
    t = ConvAutotuner(cache_path=str(tmp_path / "tune.json"), sweep=False, repeats=1)
    t.measure_route(TINY, lambda: None, route="xla")
    assert t.measured_route(TINY, "pallas_fused") is None
    t.measure_route(TINY, lambda: None, route="pallas_fused")
    assert t.timings_run == 2  # second route re-times
    assert descriptor_key(TINY) in t.route_seconds("xla")
    assert descriptor_key(TINY) in t.route_seconds("pallas_fused")


def test_route_only_entry_does_not_suppress_block_sweep(tmp_path):
    """measure_route first (no blocks), then tune(): the sweep must still
    run and the merged entry keeps both the routes and the blocks."""
    cache = str(tmp_path / "tune.json")
    t = ConvAutotuner(cache_path=cache, sweep=True, repeats=1, proxy_rows=2)
    t.measure_route(TINY, lambda: None, route="xla")
    before = t.timings_run
    cfg = t.tune(TINY)
    assert t.timings_run > before  # the sweep actually ran
    assert cfg.bm > 0 and cfg.bn > 0 and cfg.bk > 0
    entry = t.entry(TINY)
    assert entry["swept"] and "xla" in entry["routes"]


def test_predictor_consumes_measured_times():
    """Measured route seconds replace the Eq. 5 prior; Eq. 6-8 core
    scaling still applies (predict_from_t1)."""
    model = synthetic_model()
    plat = hikey970()
    desc = conv_descriptor("l0", 14, 64, 3, 64)
    t_meas = 123e-6
    pred = LayerTimePredictor(
        model=model, platform=plat, measured={descriptor_key(desc): t_meas}
    )
    stage = plat.stage_vocabulary()[0]
    got = pred.layer_time(desc, stage)
    want = model.predict_from_t1(
        desc.gemm_dims(), t_meas, cores=stage[1], speed=plat.speed(stage[0])
    )
    assert got == pytest.approx(want)
    # an unmeasured layer keeps the regression prior
    other = conv_descriptor("l1", 28, 32, 5, 96)
    prior = LayerTimePredictor(model=model, platform=plat)
    assert pred.layer_time(other, stage) == pytest.approx(
        prior.layer_time(other, stage)
    )
    # single-core, speed-1 measured layer time equals the measurement's
    # Eq. 6-8 transform of itself with H=1 (sanity: monotone hand-off)
    one = ("B", 1)
    if one in plat.stage_vocabulary():
        assert pred.layer_time(desc, one) == pytest.approx(
            model.predict_from_t1(desc.gemm_dims(), t_meas, 1, plat.speed("B"))
        )


# ----------------------------------------------------- cache robustness
# A damaged or contended cache file must degrade to re-timing, never
# raise: co-serving shares one cache across models, tuners, and processes.

@pytest.mark.parametrize(
    "payload",
    [
        b"",  # empty file
        b"not json at all {{{",  # garbage
        b'{"version": 1, "platforms": {"cpu": {"k": {"bm": 8',  # truncated
        b'[1, 2, 3]',  # valid JSON, wrong top-level type
        b'{"version": 1, "platforms": []}',  # platforms not a dict
        b'{"version": 1, "platforms": {"cpu": 7}}',  # platform not a dict
        b'{"version": 1, "platforms": {"cpu": {"k": 3}}}',  # entry damaged
    ],
    ids=["empty", "garbage", "truncated", "wrong-type", "platforms-list",
         "platform-scalar", "entry-scalar"],
)
def test_corrupt_cache_falls_back_to_retiming(tmp_path, payload):
    cache = tmp_path / "tune.json"
    cache.write_bytes(payload)
    t = ConvAutotuner(cache_path=str(cache), sweep=False, repeats=1)
    assert t.entry(TINY) is None  # damaged content discarded, not raised
    assert t.measure_route(TINY, lambda: None, route="xla") > 0
    assert t.timings_run == 1  # fell back to a real timing
    t.save()
    # the rewritten file is valid again and round-trips
    t2 = ConvAutotuner(cache_path=str(cache), sweep=False, repeats=1)
    assert t2.measured_route(TINY, "xla") is not None
    assert t2.timings_run == 0


def test_damaged_routes_field_inside_healthy_entry(tmp_path):
    """Entry-level damage one level down: a non-dict "routes" value must
    be dropped on load (re-time, never raise) and save() must rebuild a
    valid file even when merging over the damaged original."""
    cache = tmp_path / "tune.json"
    cache.write_text(json.dumps({
        "version": 1,
        "platforms": {jax.devices()[0].device_kind: {descriptor_key(TINY): {
            "swept": False, "candidates": 0, "routes": 7,
        }}},
    }))
    t = ConvAutotuner(cache_path=str(cache), sweep=False, repeats=1)
    assert t.measured_route(TINY, "xla") is None  # damage discarded
    assert t.measure_route(TINY, lambda: None, route="xla") > 0
    assert t.timings_run == 1  # re-timed
    t2 = ConvAutotuner(cache_path=str(cache), sweep=False, repeats=1)
    assert t2.measured_route(TINY, "xla") is not None
    assert sorted(t2.route_seconds()) == [descriptor_key(TINY)]


def test_concurrent_tuner_writers_never_corrupt(tmp_path):
    """Two tuners (one cache file) interleaving saves: no exception, the
    file stays valid JSON, and the union of routes survives the race."""
    import threading

    cache = str(tmp_path / "tune.json")
    descs = [conv_descriptor(f"l{i}", 8 + 2 * i, 4, 3, 8) for i in range(6)]
    tuners = [ConvAutotuner(cache_path=cache, sweep=False, repeats=1) for _ in range(2)]
    errors = []

    def writer(t, mine):
        try:
            for d in mine:
                t.measure_route(d, lambda: None, route="xla")  # save() per call
        except BaseException as e:  # noqa: BLE001 — the test asserts none
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(t, descs[i::2]))
        for i, t in enumerate(tuners)
    ]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert not errors
    with open(cache) as f:
        data = json.load(f)  # whole file is one writer's complete JSON
    assert isinstance(data["platforms"], dict)
    # a lost update costs a re-time, never a crash: a fresh tuner loads
    # whatever survived and re-times the rest without raising
    t3 = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    for d in descs:
        assert t3.measure_route(d, lambda: None, route="xla") > 0
    # save() merges, so after this pass every geometry is persisted
    t4 = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    assert all(t4.measured_route(d, "xla") is not None for d in descs)


def test_save_merges_concurrent_route_entries(tmp_path):
    """Writer B saving after writer A must not clobber A's routes for a
    key B also holds (the multi-model shared-cache contract)."""
    cache = str(tmp_path / "tune.json")
    a = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    b = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)  # loaded empty
    a.measure_route(TINY, lambda: None, route="xla")
    b.measure_route(TINY, lambda: None, route="pallas_fused")  # saves after a
    merged = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    assert merged.measured_route(TINY, "xla") is not None
    assert merged.measured_route(TINY, "pallas_fused") is not None


def test_shared_tuner_across_models_times_geometry_once(tmp_path):
    """Two co-resident graphs sharing conv geometries through ONE tuner:
    the shared shapes are measured once (descriptor keys are geometry,
    not model), which is why serve({...}) threads a single autotuner."""
    from repro.cnn.graph import Graph

    def g1():
        g = Graph("g1", (16, 16, 3))
        a = g.conv("c1", "input", 8, 3)  # shared geometry
        a = g.conv("c2", a, 8, 3)
        a = g.gap("gap", a)
        a = g.fc("fc", a, 10)
        return g

    def g2():
        g = Graph("g2", (16, 16, 3))
        a = g.conv("x1", "input", 8, 3)  # same geometry as g1.c1
        a = g.conv("x2", a, 16, 1)  # unique to g2
        a = g.gap("gap", a)
        a = g.fc("fc", a, 10)
        return g

    tuner = ConvAutotuner(cache_path=str(tmp_path / "tune.json"), sweep=False,
                          repeats=1)
    kb = resolve_backend("xla", tuner=tuner)
    measure_graph_routes(g1(), kb, tuner)
    after_first = tuner.timings_run
    measure_graph_routes(g2(), kb, tuner)
    # g2 re-times only its unique geometries, not the shared conv
    unique_g2 = {
        descriptor_key(d)
        for d in g2().descriptors()
    } - {descriptor_key(d) for d in g1().descriptors()}
    assert tuner.timings_run == after_first + len(unique_g2)


def test_planner_time_matrix_uses_tuner(tmp_path):
    """AutoPlanner(tuner=...) builds T from measured routes (no API break:
    planner without tuner is byte-identical behaviour)."""
    g = MODELS["squeezenet"]()
    cache = str(tmp_path / "tune.json")
    tuner = ConvAutotuner(cache_path=cache, sweep=False, repeats=1)
    kb = resolve_backend("pallas_fused", tuner=tuner)
    measure_graph_routes(g, kb, tuner)
    assert len(tuner.route_seconds()) > 0
    planner = AutoPlanner(mode="merge", source="synthetic", tuner=tuner)
    T = planner.time_matrix(g)
    baseline = AutoPlanner(mode="merge", source="synthetic").time_matrix(g)
    assert len(T) == len(baseline) == len(g.descriptors())
    # at least one layer's row must differ (measured host times vs the
    # synthetic analytical prior) while staying positive and finite
    diff = any(
        not math.isclose(T[l][s], baseline[l][s], rel_tol=1e-6)
        for l in range(len(T))
        for s in T[l]
    )
    assert diff
    for row in T:
        for v in row.values():
            assert v > 0 and math.isfinite(v)
    # second planner run from the same tuner: zero re-timing
    before = tuner.timings_run
    measure_graph_routes(g, kb, tuner)
    planner.time_matrix(g)
    assert tuner.timings_run == before
