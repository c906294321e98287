"""The harness end to end on the CPU at a small size.

``run_cell(chip=False)`` skips the look for a chip and drives the rest of
a run: the program's ``serve()`` at 32x32 images (every width as
published), a short closed or open loop, the seeded sample and the
comparison with the reference.  A sound run is correct; a run whose
answers are altered where the program produces them is not.
"""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from bench import harness

SEED = 2**31 + 17


def _small(cell_name, **traffic):
    cell = harness.Cell.find(cell_name)
    cell.config = dict(cell.config, input_shape=[32, 32, 3])
    cell.config["check"] = dict(cell.config["check"], sample=6, block=6)
    cell.traffic = dict(cell.traffic, **traffic)
    return cell


def _run(cell, seconds=1.0):
    run = harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                           chip=False, log=lambda s: None)
    return run, harness.result(run, False, jax.devices())


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH_DIR, "run.py"),
         "--workload", "vgg16.offline", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=harness.ROOT,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "accelerator" in p.stderr


def test_sound_closed_loop_is_correct():
    run, res = _run(_small("vgg16.offline", batch_size=4, images=8))
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert run.compiles_in_window == 0
    assert set(res["metrics"]) == {"images_per_s", "setup_s"}
    assert res["metrics"]["images_per_s"]["value"] > 0
    assert list(res)[-1] == "check"  # the compared number comes last
    assert res["check"]["max_logit_gap"]["value"] <= res["check"]["max_logit_gap"]["limit"]
    json.dumps(res)


def test_altered_answer_is_not_correct(monkeypatch):
    """The last stage's answer is altered where it is produced: one class's
    probability of every image is doubled."""
    import repro.serving.server as server_mod

    real = server_mod.build_stage_fns

    def broken(graph, plan, backend=None):
        fns = real(graph, plan, backend=backend)
        last = fns[-1]

        def altered(params, env):
            out = last(params, env)
            return {k: v.at[:, 7].multiply(2.0) for k, v in out.items()}

        return fns[:-1] + [altered]

    monkeypatch.setattr(server_mod, "build_stage_fns", broken)
    run, res = _run(_small("vgg16.offline", batch_size=4, images=8))
    assert res["correct"] is False
    assert res["check"]["max_logit_gap"]["value"] > res["check"]["max_logit_gap"]["limit"]


def test_open_loop_latency_from_due_time():
    cell = _small("resnet50.server", batch_size=2, images=8,
                  arrivals={"process": "poisson", "rate": 20.0})
    # vgg16's graph plans faster; the loop and the readers are the same
    cell.config = dict(_small("vgg16.offline").config)
    run, res = _run(cell, seconds=1.0)
    assert res["correct"] is True
    assert res["attempted"] == 20  # the same count for every seed
    m = res["metrics"]
    assert set(m) == {"latency_p50_ms", "latency_p95_ms", "setup_s"}
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    # every latency is timed from when the request was due, not sent
    for r in run.requests:
        assert r.sent >= r.due and r.done > r.sent
    # the per-layer readers of this cell find what they read
    layer = {x["name"]: harness.reader(x["name"])(run) for x in cell.per_layer}
    assert set(layer) == {"queue_wait_p95_ms", "batch_fill"}
    assert layer["queue_wait_p95_ms"] >= 0
    assert 0 < layer["batch_fill"] <= 100


def test_traced_run_marks_its_window():
    """With the profiler on, the traced stretch is the window's first
    seconds, marked by the benchmark's span on both clocks; on the CPU
    there is no device plane, so the device readers return nothing."""
    cell = _small("vgg16.offline", batch_size=4, images=8)
    run = harness.run_cell(cell, SEED, 1.0, True, time.perf_counter(),
                           chip=False, log=lambda s: None)
    lo, hi = run.trace_window
    assert 0.5e9 < hi - lo < 1.5e9
    assert run.trace_host[0] >= run.t_start and run.trace_host[1] <= run.t_end + 0.1
    res = harness.result(run, True, jax.devices())
    assert res["correct"] is True
    assert res["metrics"] == {}  # no device plane to read, and no peak for a CPU
    assert not os.path.exists(os.path.join(harness.OUT_DIR, "trace", cell.name))
