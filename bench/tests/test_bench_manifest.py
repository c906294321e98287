"""BENCHMARK.json keeps to its contract, and a cell, a configuration or a
metric is added by adding files and manifest entries alone."""
import json
import os
import re
import shutil

import pytest

from bench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_json(harness.ROOT, "BENCHMARK.json")


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["bench"]
    assert manifest["command"] == ["python3", "bench/run.py"]
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(names) == len(set(names))
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"]) and _line(c["source"])
        assert c["file"].startswith("bench/") and c["name"] in used
        cfg = harness.load_json(harness.ROOT, c["file"])
        assert cfg["reduced"] == c["reduced"] == []
        assert cfg["source"] == c["source"]


def test_workloads(manifest):
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(harness.BENCH_DIR, "traffic", w["traffic"] + ".json"))
    assert len({w["name"] for w in manifest["workloads"]}) == len(manifest["workloads"])


def test_metrics(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = manifest["end_to_end"]
    names = [m["name"] for m in e2e + manifest["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25 for m in e2e)
    reports = {c: set() for c in cells}
    for m in e2e:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for c in m.get("workloads", cells):
            reports[c].add(m["name"])
    for m in e2e + manifest["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert callable(harness.reader(m["name"]))
    layered = {c: 0 for c in cells}
    for m in manifest["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert _line(m["layer"])
        for c in m["workloads"]:
            assert m["moves"] in reports[c]
            layered[c] += 1
    for c in cells:
        assert "setup_s" in reports[c] and len(reports[c]) >= 2 and layered[c] >= 1


def test_every_cell_resolves(manifest):
    for w in manifest["workloads"]:
        cell = harness.Cell.find(w["name"])
        assert cell.config["model"] and cell.traffic["batch_size"] >= 1
        assert cell.end_to_end and cell.per_layer


def test_a_cell_and_a_metric_are_added_by_files_alone(tmp_path, manifest):
    """A later change adds a traffic file, a metric reader and manifest
    entries; nothing that is there changes, and the harness finds them."""
    root = tmp_path
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache", "__pycache__"))
    m = json.loads(json.dumps(manifest))
    (root / "bench" / "traffic" / "poisson_low_b8.json").write_text(json.dumps(
        {"loop": "open", "batch_size": 8, "images": 128,
         "arrivals": {"process": "poisson", "rate": 40.0}}))
    (root / "bench" / "metrics" / "mean_latency_ms.py").write_text(
        "def read(run):\n    return 1.0\n")
    m["workloads"].append({"name": "vgg16.server_low", "config": "vgg16",
                           "traffic": "poisson_low_b8", "chips": 1, "why": "added"})
    m["end_to_end"][1]["workloads"].append("vgg16.server_low")
    m["per_layer"].append({"name": "mean_latency_ms", "unit": "ms", "better": "lower",
                           "source": "host_clock", "layer": "stage server",
                           "moves": "latency_p50_ms", "workloads": ["vgg16.server_low"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    cell = harness.Cell.find("vgg16.server_low", root=str(root))
    assert cell.traffic["arrivals"]["rate"] == 40.0
    assert cell.config["model"] == "vgg16"
    assert [x["name"] for x in cell.end_to_end] == ["latency_p50_ms", "setup_s"]
    assert [x["name"] for x in cell.per_layer] == ["mean_latency_ms"]
    assert harness.reader("mean_latency_ms", root=str(root))(None) == 1.0


FAMILY = '''"""A conv and an fc layer."""
from bench.models import Layer


def layers(cfg):
    h, w, c = cfg["input_shape"]
    return [Layer("conv1", "conv", (h, w), c, cfg["width"], 3, 1, 1),
            Layer("fc", "fc", (1, 1), h * w * cfg["width"], cfg["classes"], relu=False)]


def forward(cfg, params, x, ops):
    conv, _ = layers(cfg)
    return ops.fc(ops.relu(ops.conv(x, conv, params["conv1"])), params["fc"])
'''

PROBE = '''import json, sys
sys.path.insert(0, sys.argv[1])
from bench import harness, models, reference
cell = harness.Cell.find("plainnet.offline", root=sys.argv[1])
cfg = cell.config
params = models.make_params(cfg, 3)
probs = reference.run_blocks(cfg, params, models.make_images(cfg, 3, 3), 2)
print(json.dumps({"bench": harness.BENCH_DIR, "flops": models.flops_per_image(cfg),
                  "params": models.param_shapes(cfg), "sums": probs.sum(axis=1).tolist(),
                  "e2e": [m["name"] for m in cell.end_to_end],
                  "per_layer": [m["name"] for m in cell.per_layer]}))
'''


def test_a_configuration_of_a_new_family_is_added_by_files_alone(tmp_path, manifest):
    """A configuration of a family the benchmark has not seen comes with
    its family's file (layers and forward pass) and its configuration
    file; the work counts, the weights and the reference follow."""
    import subprocess
    import sys

    root = tmp_path
    shutil.copytree(harness.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache", "__pycache__"))
    (root / "bench" / "families" / "plainnet.py").write_text(FAMILY)
    (root / "bench" / "configs" / "plainnet.json").write_text(json.dumps(
        {"model": "plainnet", "source": "https://example.org/plainnet", "family": "plainnet",
         "input_shape": [8, 8, 3], "width": 4, "classes": 10, "dtype_bytes": 4,
         "init": {"bias_std": 0.05}, "check": {"limit": 0.13, "sample": 4, "block": 2},
         "reduced": []}))
    m = json.loads(json.dumps(manifest))
    m["configs"].append({"name": "plainnet", "source": "https://example.org/plainnet",
                         "file": "bench/configs/plainnet.json", "reduced": [], "why": "added"})
    m["workloads"].append({"name": "plainnet.offline", "config": "plainnet",
                           "traffic": "closed_b32", "chips": 1, "why": "added"})
    m["end_to_end"][0]["workloads"].append("plainnet.offline")
    m["per_layer"][1]["workloads"].append("plainnet.offline")
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    (root / "probe.py").write_text(PROBE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, str(root / "probe.py"), str(root)], cwd=root,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["bench"] == str(root / "bench")  # the copy, not this checkout
    assert out["flops"] == 2 * 8 * 8 * 9 * 3 * 4 + 2 * 8 * 8 * 4 * 10
    assert out["params"] == {"conv1": {"w": [3, 3, 3, 4], "b": [4]},
                             "fc": {"w": [256, 10], "b": [10]}}
    assert all(abs(s - 1.0) < 1e-5 for s in out["sums"])
    assert out["e2e"] == ["images_per_s", "setup_s"]
    assert out["per_layer"] == [m["per_layer"][1]["name"]]
