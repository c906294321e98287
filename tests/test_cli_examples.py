"""End-to-end CLI and example smoke tests (subprocesses, tiny scales)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src") + ":" + REPO)


def _run(args, timeout=420, env=None):
    res = subprocess.run(
        args, env=env or ENV, cwd=REPO, capture_output=True, text=True,
        timeout=timeout,
    )
    assert res.returncode == 0, (res.stdout[-1500:], res.stderr[-1500:])
    return res.stdout


def test_train_cli_smoke():
    out = _run([
        sys.executable, "-m", "repro.launch.train", "--arch", "smollm-360m",
        "--reduced", "--steps", "6", "--batch", "2", "--seq", "32",
        "--log-every", "2",
    ])
    assert "step " in out and "loss" in out


def test_serve_cli_smoke():
    out = _run([
        sys.executable, "-m", "repro.launch.serve", "--arch", "smollm-360m",
        "--reduced", "--batch", "2", "--prompt-len", "8", "--gen", "4",
    ])
    assert "decode:" in out and "tok/s" in out


def test_chip_smoke_refuses_cpu():
    """chip_smoke.py has no CPU fallback: with no TPU it exits non-zero,
    says so, and prints no result line."""
    res = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=dict(ENV, JAX_PLATFORMS="cpu"),
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert "no TPU found" in res.stderr
    assert '"ok"' not in res.stdout


def test_quickstart_example():
    out = _run([sys.executable, "examples/quickstart.py"])
    assert "Pipe-it chose:" in out
    assert "Throughput gain: +" in out


@pytest.mark.slow  # ~47s: a real 60-step training run (CI: -m slow step)
def test_train_example_learns():
    out = _run([sys.executable, "examples/train_smollm.py", "60"])
    assert "LEARNED" in out


def test_multimodel_benchmark_smoke():
    """Tiny-shape co-serving benchmark: the >=1.2x co-vs-timeslice
    acceptance assert runs INSIDE the benchmark; interpret mode is forced
    so any Pallas-routed kernel stays CI-safe."""
    out = _run(
        [sys.executable, "-m", "benchmarks.multimodel_serving", "--tiny",
         "--repeats", "1"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "ratio" in out and "outputs_bitwise_equal=yes" in out
    assert "coserved" in out and "timesliced" in out


def test_serve_multimodel_example():
    out = _run(
        [sys.executable, "examples/serve_multimodel.py", "--tiny"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "partition" in out
    assert "outputs equal each model's single-engine baseline" in out
    assert "no request dropped" in out


def test_serve_power_capped_example():
    out = _run(
        [sys.executable, "examples/serve_power_capped.py", "--tiny"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "capped plan" in out
    assert "re-planned" in out and "thermal throttle" in out
    assert "no request dropped" in out
    assert "outputs still equal the single-stage baseline" in out


def test_serve_fleet_example():
    """Fleet quickstart: three-level DSE + router, a seeded board crash
    with exactly-once re-dispatch, rejoin, and rate-driven scale-in."""
    out = _run(
        [sys.executable, "examples/serve_fleet.py", "--tiny"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "fleet plan" in out and " || " in out
    assert "outputs equal each model's single-engine baseline" in out
    assert "exactly-once, no ticket dropped" in out
    assert "fleet serving again" in out
    assert "every submitted ticket completed exactly once" in out


def test_power_benchmark_smoke():
    """Tiny power benchmark: the >=15% iso-throughput energy cut, the cap
    satisfaction, and the oracle-match asserts run INSIDE the benchmark."""
    out = _run(
        [sys.executable, "-m", "benchmarks.power_aware", "--tiny"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "iso_throughput" in out and "energy_red=" in out
    assert "power_capped" in out and "non_binding_cap" in out
    import json
    with open(os.path.join(REPO, "BENCH_power_tiny.json")) as f:
        data = json.load(f)
    assert data["records"] and all("throughput_per_watt" in r for r in data["records"])


def test_tail_latency_benchmark_smoke():
    """Tiny tail-latency benchmark: the model-accuracy band, the SLO-plan
    simulator check, and the governed-DVFS SLO-hold asserts run INSIDE
    the benchmark (ISSUE 6 acceptance at tiny scale)."""
    out = _run(
        [sys.executable, "-m", "benchmarks.tail_latency", "--tiny"],
        env=dict(ENV, REPRO_PALLAS_INTERPRET="1"),
    )
    assert "model_accuracy" in out and "worst_p99_err=" in out
    assert "slo_planning" in out and "governed_dvfs" in out
    import json
    with open(os.path.join(REPO, "BENCH_tail_tiny.json")) as f:
        data = json.load(f)
    scen = {r["scenario"] for r in data["records"]}
    assert scen == {"model_accuracy", "slo_planning", "governed_dvfs"}
    acc = [r for r in data["records"] if r["scenario"] == "model_accuracy"]
    assert acc and all(
        r["p99_rel_err"] <= data["model_tolerance"] for r in acc
    )
    gov = next(r for r in data["records"] if r["scenario"] == "governed_dvfs")
    assert gov["slo_aware_max_window_p99_s"] <= gov["slo_p99_s"]
    assert gov["unconstrained_max_window_p99_s"] > 2 * gov["slo_p99_s"]


@pytest.mark.slow  # ~6 min: full 10-arch TPU Pipe-it sweep (CI: -m slow step)
def test_pipeit_tpu_example():
    out = _run([sys.executable, "examples/pipeit_tpu.py"], timeout=560)
    assert "gain vs TP16" in out
    # the paper's insight must transfer: every arch gains for train
    lines = [l for l in out.splitlines() if " train_4k " in l]
    assert len(lines) == 10
    assert all("+" in l.split()[-1] for l in lines)
