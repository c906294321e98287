"""The device's idle time split by the server's spans, on made-up traces
with known overlaps, against a count on a grid, and on the recorded one;
and the rest of what ``bench/span_report.py`` prints of a traced run."""
import os
import random
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import devtrace, harness, idle_split

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "trace_vgg16_offline.json")
READERS = {k: "idle_in_" + k for k, _ in idle_split.CLASSES}
DEV = "/device:TPU:0"

# device busy [0, 10] and [50, 60] of the window [0, 100]: idle 80 ns
OPS = [("conv", 0.0, 10.0), ("fc", 50.0, 10.0)]
HOST = [
    ("bench", devtrace.WINDOW_SPAN, 0.0, 100.0),
    ("python3", "serve.stage1.dispatch", 20.0, 10.0),
    ("python3", "serve.stack", 25.0, 10.0),  # dispatch: idle [20, 35]
    ("python3", "serve.egress", 30.0, 15.0),  # egress: what dispatch left, [35, 45]
    ("python3", "serve.to_device", 5.0, 10.0),  # ingress: [10, 15]
    ("python3", "serve.to_device", 40.0, 15.0),  # and [45, 50]
    ("python3", "serve.gather", 60.0, 30.0),  # gather: [60, 90]
    ("python3", "serve.gather", 120.0, 30.0),  # outside the window
    # waiting, and others' spans, take no idle time of their own
    ("python3", "serve.admit", 0.0, 100.0),
    ("python3", "serve.stage1.take", 10.0, 90.0),
    ("python3", "serve.stage0.wait", 10.0, 90.0),
    ("python3", "serve.stage0.handoff", 10.0, 90.0),
    ("python3", "PjitFunction(stage_1)", 15.0, 5.0),
    ("python3", "bench.submit", 0.0, 100.0),
]
EXPECTED = {"idle": 80.0, "dispatch": 15.0, "egress": 10.0, "ingress": 10.0,
            "gather": 30.0, "rest": 15.0}  # rest: [15, 20] and [90, 100]


# each class's idle time without the order, and while no other is open
OVERLAP = {"any": {"dispatch": 15.0, "egress": 15.0, "ingress": 15.0, "gather": 30.0},
           "only": {"dispatch": 10.0, "egress": 5.0, "ingress": 10.0, "gather": 30.0}}


def _run(devices, host, window, chips=1):
    return types.SimpleNamespace(devices=devices, host=host, trace_window=window,
                                 cell=types.SimpleNamespace(chips=chips))


def _read(run):
    return {k: harness.reader(name)(run) for k, name in READERS.items()}


def test_known_overlaps_in_precedence_order():
    assert idle_split.split_ns(OPS, HOST, 0.0, 100.0) == pytest.approx(EXPECTED)
    run = _run({DEV: OPS}, HOST, (0.0, 100.0))
    got = _read(run)  # a window of 100 ns: nanoseconds read as percent
    assert got == pytest.approx({k: EXPECTED[k] for k in READERS})
    idle = harness.reader("device_idle")(run)
    assert idle == pytest.approx(80.0)
    rest = idle_split.shares(run)["rest"]
    assert abs(sum(got.values()) + rest - idle) < 1e-9


def test_a_class_earlier_in_the_order_takes_the_shared_time():
    # the same idle stretch [10, 50] under spans from the last class to the first
    host = [("t", "serve.gather", 10.0, 40.0), ("u", "serve.to_device", 10.0, 40.0),
            ("v", "serve.egress", 10.0, 40.0), ("w", "serve.stage3.dispatch", 10.0, 40.0)]
    for i in range(len(host)):
        got = idle_split.split_ns(OPS, host[:i + 1], 0.0, 100.0)
        first = next(k for k, p in idle_split.CLASSES if p.match(host[i][1]))
        assert got[first] == 40.0
        assert sum(got[k] for k, _ in idle_split.CLASSES) == 40.0
        assert got["rest"] == 40.0


def _grid(ops, host, lo, hi):
    """The same split counted nanosecond by nanosecond."""
    n = int(hi - lo)
    busy = np.zeros(n, bool)
    for _, s, d in ops:
        busy[max(int(s - lo), 0):max(int(s + d - lo), 0)] = True
    free = ~busy
    out = {"idle": float(free.sum())}
    for key, pattern in idle_split.CLASSES:
        cover = np.zeros(n, bool)
        for _, name, s, d in host:
            if pattern.match(name):
                cover[max(int(s - lo), 0):max(int(s + d - lo), 0)] = True
        out[key] = float((free & cover).sum())
        free &= ~cover
    out["rest"] = float(free.sum())
    return out


def _grid_overlap(ops, host, lo, hi):
    """``overlap_ns`` counted nanosecond by nanosecond."""
    n = int(hi - lo)
    free = np.ones(n, bool)
    for _, s, d in ops:
        free[max(int(s - lo), 0):max(int(s + d - lo), 0)] = False
    covers = {}
    for key, pattern in idle_split.CLASSES:
        covers[key] = np.zeros(n, bool)
        for _, name, s, d in host:
            if pattern.match(name):
                covers[key][max(int(s - lo), 0):max(int(s + d - lo), 0)] = True
    out = {"any": {}, "only": {}}
    for key, cover in covers.items():
        others = np.zeros(n, bool)
        for k, c in covers.items():
            if k != key:
                others |= c
        out["any"][key] = float((free & cover).sum())
        out["only"][key] = float((free & cover & ~others).sum())
    return out


@pytest.mark.parametrize("seed", range(6))
def test_split_matches_a_grid_count(seed):
    rng = random.Random(seed)
    names = ["serve.stack", "serve.stage0.dispatch", "serve.stage4.dispatch",
             "serve.egress", "serve.to_device", "serve.gather", "serve.admit",
             "serve.stage2.take", "serve.stage2.wait"]
    ops = [("op", float(rng.randrange(-50, 1000)), float(rng.randrange(1, 40)))
           for _ in range(60)]
    host = [("python3", rng.choice(names), float(rng.randrange(-50, 1000)),
             float(rng.randrange(1, 80))) for _ in range(80)]
    lo, hi = 100.0, 900.0
    got = idle_split.split_ns(ops, host, lo, hi)
    assert got == _grid(ops, host, lo, hi)
    assert idle_split.overlap_ns(ops, host, lo, hi) == _grid_overlap(ops, host, lo, hi)
    run = _run({DEV: ops}, host, (lo, hi))
    shares = idle_split.shares(run)
    assert abs(sum(shares[k] for k in READERS) + shares["rest"]
               - harness.reader("device_idle")(run)) < 1e-9


def test_shares_average_over_the_cells_chips():
    other = [("conv", 0.0, 100.0)]  # a second chip busy throughout
    run = _run({DEV: OPS, "/device:TPU:1": other, "/device:TPU:2": []}, HOST,
               (0.0, 100.0), chips=2)
    assert _read(run) == pytest.approx({k: EXPECTED[k] / 2 for k in READERS})


def test_nothing_without_a_device_plane_or_the_servers_spans():
    for devices in (None, {}):
        assert set(_read(_run(devices, HOST, (0.0, 100.0))).values()) == {None}
    assert set(_read(_run({DEV: OPS}, HOST, None)).values()) == {None}
    # a program without the spans: the run leaves the metrics out
    theirs = [h for h in HOST if not h[1].startswith("serve.")]
    assert set(_read(_run({DEV: OPS}, theirs, (0.0, 100.0))).values()) == {None}
    # and so does a trace whose server spans all lie outside the stretch
    late = [("t", "serve.gather", 120.0, 30.0)]
    assert set(_read(_run({DEV: OPS}, late, (0.0, 100.0))).values()) == {None}


def test_recorded_trace():
    """The recorded v5e trace holds no server span: the split puts every
    idle nanosecond in ``rest``, and the readers leave the metrics out.
    With the runtime's per-image broadcast and per-row slice relabelled as
    the spans that now enclose them, the split reads them."""
    devs, host = devtrace.load_fixture(FIXTURE)
    lo, hi = devtrace.window(host)
    ops = devs[DEV]
    got = idle_split.split_ns(ops, host, lo, hi)
    assert all(got[k] == 0.0 for k in READERS)
    assert got["rest"] == got["idle"] > 0
    run = _run(devs, host, (lo, hi))
    assert set(_read(run).values()) == {None}

    rename = {"PjitFunction(broadcast_in_dim)": "serve.to_device",
              "PjitFunction(dynamic_slice)": "serve.egress"}
    run.host = [(t, rename.get(n, n), s, d) for t, n, s, d in host]
    shares = idle_split.shares(run)
    assert shares["dispatch"] == shares["gather"] == 0.0
    assert 0 < shares["egress"] < 10 and 0 < shares["ingress"] < 20
    idle = harness.reader("device_idle")(run)
    assert abs(sum(shares[k] for k in READERS) + shares["rest"] - idle) < 1e-9


def test_overlap_without_the_order_and_alone():
    got = idle_split.overlap_ns(OPS, HOST, 0.0, 100.0)
    for part in OVERLAP:
        assert got[part] == pytest.approx(OVERLAP[part])
    # the order moves time between classes; alone, none can take it
    for key, _ in idle_split.CLASSES:
        assert got["only"][key] <= EXPECTED[key] <= got["any"][key]


def test_occupancy_of_each_server_span():
    host = HOST + [("python3", "serve.resolve", 95.0, 10.0)]
    got = idle_split.occupancy(host, 0.0, 100.0)
    assert set(got) == {h[1] for h in host if h[1].startswith("serve.") and h[2] < 100}
    # two spans, open over [5, 15] and [40, 55]; the gather at 120 starts late
    assert got["serve.to_device"] == pytest.approx(
        {"count": 2, "open_pct": 25.0, "mean_ms": 12.5e-6, "p50_ms": 12.5e-6})
    assert got["serve.gather"]["count"] == 1
    assert got["serve.resolve"]["open_pct"] == pytest.approx(5.0)  # cut at the end
    assert got["serve.admit"]["open_pct"] == pytest.approx(100.0)


def test_report_of_one_traced_run():
    run = _run({DEV: OPS}, HOST, (0.0, 100.0))
    rep = idle_split.report(run)
    assert rep["window_s"] == pytest.approx(100e-9)
    assert rep["split"] == pytest.approx(EXPECTED)
    for part in OVERLAP:  # a window of 100 ns: nanoseconds read as percent
        assert rep["overlap"][part] == pytest.approx(OVERLAP[part])
    assert rep["spans"] == idle_split.occupancy(HOST, 0.0, 100.0)
    # a host trace without a device plane (the CPU) still has its spans
    bare = idle_split.report(_run({}, HOST, (0.0, 100.0)))
    assert bare["split"] is None and bare["overlap"] is None
    assert bare["spans"] == rep["spans"]
    assert idle_split.report(_run({DEV: OPS}, HOST, None)) is None


def test_the_readers_share_one_split_per_run():
    run = _run({DEV: OPS}, HOST, (0.0, 100.0))
    first = idle_split.shares(run)
    assert idle_split.shares(run) is first
    run.host = HOST[:3]  # what was read changed: read it again
    assert idle_split.shares(run)["egress"] == 0.0
    assert idle_split.shares(_run({DEV: OPS}, HOST, (0.0, 100.0))) == first


def test_span_report_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "span_report.py"),
         "--workload", "vgg16.offline", "--seed", "7", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300, cwd=harness.ROOT,
    )
    assert p.returncode != 0
    assert '"result"' not in p.stdout
    assert "accelerator" in p.stderr
