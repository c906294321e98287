"""One run of one benchmark cell, found by name.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric it reports is read by
``bench/metrics/<metric>.py``.  A run:

1. makes the weights on the device from the seed and the image pool on
   the host, and builds the server through the program's entry point,
   ``repro.serving.serve()``, which plans, compiles and warms it;
2. warms every batch shape the traffic can form, then measures for
   ``--seconds`` (with ``--trace 1`` the profiler traces its first
   ``TRACE_S`` seconds);
3. waits for every request of the window, reads the device's peak
   memory, frees the server and checks a seeded sample of the answers
   against the plain reference (``bench/reference.py``).

The last line of standard output is the result as one JSON object.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

import numpy as np

from . import devtrace, models, reference, traffic

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
OUT_DIR = os.path.join(BENCH_DIR, "out")
#: fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(BENCH_DIR, ".jax_cache")
#: how long after the window closes a request may still finish
DRAIN_S = 60.0
#: the profiler traces at most the window's first seconds: it writes some
#: 30 MB of host events a second, which take minutes to stop and read
TRACE_S = 4.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @classmethod
    def find(cls, name: str, root: str = ROOT, manifest: Optional[dict] = None) -> "Cell":
        m = manifest if manifest is not None else load_json(root, "BENCHMARK.json")
        by_name = {w["name"]: w for w in m["workloads"]}
        if name not in by_name:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json: {sorted(by_name)}")
        w = by_name[name]
        (cfg_entry,) = [c for c in m["configs"] if c["name"] == w["config"]]
        return cls(
            name=name,
            chips=w["chips"],
            config=load_json(root, cfg_entry["file"]),
            traffic=load_json(root, "bench", "traffic", w["traffic"] + ".json"),
            end_to_end=[x for x in m["end_to_end"] if name in x.get("workloads", [name])],
            per_layer=[x for x in m["per_layer"] if name in x["workloads"]],
        )


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read from one run."""

    cell: Cell
    seed: int
    batch_size: int
    setup_s: float = math.nan
    t_start: float = math.nan
    t_end: float = math.nan
    requests: List[traffic.Request] = dataclasses.field(default_factory=list)
    stage0_start: tuple = (0, 0)  # (items, padded_items) at the window's start
    stage0_end: tuple = (0, 0)
    compiles_in_window: int = 0
    peak: Optional[dict] = None  # this device kind's row of peaks.json
    devices: Optional[Dict[str, list]] = None  # traced device ops
    host: Optional[list] = None  # traced host spans
    trace_window: Optional[tuple] = None  # traced stretch, (start_ns, end_ns)
    trace_host: Optional[tuple] = None  # the same stretch on the host clock
    memory_peak_bytes: Optional[int] = None
    sample: Optional[np.ndarray] = None  # indices into requests
    reading: float = math.nan  # the compared number
    served: Optional[np.ndarray] = None  # sampled answers
    expected: Optional[np.ndarray] = None  # reference on the same images

    # ---------------------------------------------------- derived counts
    def completed_between(self, lo: float, hi: float) -> List[traffic.Request]:
        return [r for r in self.requests if r.error is None and lo <= r.done <= hi]

    def completed_in_window(self) -> List[traffic.Request]:
        return self.completed_between(self.t_start, self.t_end)

    def failed(self) -> List[traffic.Request]:
        return [r for r in self.requests if r.error is not None or math.isnan(r.done)]


# ------------------------------------------------------------ peaks, chip
def peak_for(kind: str) -> dict:
    """The row of ``bench/peaks.json`` for a ``device_kind``; an unknown
    kind is an error, never a default."""
    table = load_json(BENCH_DIR, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def require_chips(n: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(
            f"need {n} accelerator chip(s); JAX found {len(devs)} "
            f"{devs[0].platform!r} device(s)"
        )
    return devs


def use_cache() -> None:
    """JAX's persistent compilation cache in the checkout, for every
    program, however quick to compile."""
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's traces and backend compiles by the time they happened."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.stamps: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if name in self.EVENTS:
            self.stamps.append((name, time.perf_counter()))

    def between(self, lo: float, hi: float) -> Dict[str, int]:
        out = {n.rsplit("/", 1)[-1]: 0 for n in self.EVENTS}
        for name, t in list(self.stamps):
            if lo <= t <= hi:
                out[name.rsplit("/", 1)[-1]] += 1
        return out


class GcPauses:
    """Python's garbage collections by the time they ran, as
    ``(start, seconds, generation)``; every thread waits while one runs."""

    def __init__(self):
        self.events: List[tuple] = []
        self._t = math.nan
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif not math.isnan(self._t):
            self.events.append((self._t, now - self._t, info["generation"]))
            self._t = math.nan

    def close(self) -> None:
        gc.callbacks.remove(self._on)

    def summary(self, lo: float, hi: float) -> str:
        inside = [e for e in self.events if lo <= e[0] <= hi]
        gens = [sum(1 for e in inside if e[2] == g) for g in range(3)]
        if not inside:
            return "gc in window: none"
        t, secs, g = max(inside, key=lambda e: e[1])
        return (f"gc in window: by generation {gens}, total {sum(e[1] for e in inside) * 1e3:.3f}ms, "
                f"longest {secs * 1e3:.3f}ms (generation {g}) at {t - lo:.3f}s")


# ------------------------------------------------------------------- run
def build_server(cell: Cell, params):
    from repro.cnn.models import MODELS
    from repro.serving import serve

    cfg, tr = cell.config, cell.traffic
    graph = MODELS[cfg["model"]]()
    graph.input_shape = tuple(cfg["input_shape"])
    kw = dict(cfg["serve"])
    if "flush_timeout_s" in tr:
        kw["flush_timeout_s"] = tr["flush_timeout_s"]
    return serve(graph, params=params, batch_size=tr["batch_size"], **kw)


def warm_batches(server, images) -> None:
    """Every shape the window can form: one full batch through the server
    (submit, stages, the per-row split), and stage 0's stacking and
    padding of every partial fill."""
    import jax
    import jax.numpy as jnp
    from repro.serving.batching import stack_envs

    b = server.batch_size
    tickets = [server.submit(images[i % len(images)]) for i in range(b)]
    for t in tickets:
        t.result(timeout=600)
    one = jnp.asarray(images[0], jnp.float32)[None]
    for k in range(1, b + 1):
        jax.block_until_ready(stack_envs([{"input": one}] * k, pad_to=b))


def logit_gap(served: np.ndarray, expected: np.ndarray) -> np.ndarray:
    """Per answer, in nats: the largest gap over the classes between the
    served and the reference log-probabilities, once their common shift
    (the softmax's normaliser) is taken out as the median gap.  A rounding
    of every logit by a relative step reads as that step times the
    logits' spread, on every class alike, so the number is steady from
    answer to answer."""
    tiny = np.float64(1e-30)
    d = (np.log(np.maximum(served.astype(np.float64), tiny))
         - np.log(np.maximum(expected.astype(np.float64), tiny)))
    return np.abs(d - np.median(d, axis=1, keepdims=True)).max(axis=1)


def run_cell(
    cell: Cell, seed: int, seconds: float, trace: bool, t0: float,
    *, chip: bool = True, log=print,
) -> Run:
    """One run of the cell; ``chip=False`` skips the look for a chip (tests)."""
    import jax

    devs = require_chips(cell.chips) if chip else jax.devices()
    cfg, tr = cell.config, cell.traffic
    run = Run(cell=cell, seed=seed, batch_size=tr["batch_size"])
    kind = devs[0].device_kind
    run.peak = peak_for(kind) if chip else None
    counter = CompileCounter()

    params = models.make_params(cfg, seed)
    jax.block_until_ready(params)
    images = models.make_images(cfg, seed, tr["images"])
    server = build_server(cell, params)
    try:
        log(f"plan: {server.plan.pipeline.notation()} allocation="
            f"{[list(s) for s in server.plan.allocation]} stages={len(server._stage_fns)}")
        warm_batches(server, images)
        log(f"phase=warmup attempted={server.batch_size} failed=0")
        offsets = (
            traffic.arrival_offsets(tr["arrivals"], seconds, seed)
            if tr["loop"] == "open" else None
        )
        tracer = devtrace.WindowTrace(
            os.path.join(OUT_DIR, "trace", cell.name), min(seconds, TRACE_S)
        ) if trace else None
        st0 = server.metrics.stages[0]
        run.stage0_start = (st0.items, st0.padded_items)
        # what set-up made lives on: later collections need not walk it
        gc.collect()
        gc.freeze()
        pauses = GcPauses()
        if tracer:
            tracer.start()
        try:
            run.t_start = time.perf_counter()
            run.setup_s = run.t_start - t0
            if tracer:
                tracer.open(run.t_start)
            if tr["loop"] == "closed":
                run.requests = traffic.closed_loop(server, images, seconds, run.t_start)
            else:
                run.requests = traffic.open_loop(server, images, offsets, run.t_start)
            run.t_end = max(time.perf_counter(), run.t_start + seconds)
        finally:
            pauses.close()
            gc.unfreeze()
            if tracer:
                tracer.close()
                log(f"trace stopped {time.perf_counter() - run.t_end:.1f}s after the window")
        run.stage0_end = (st0.items, st0.padded_items)
        missing = traffic.wait_all(run.requests, run.t_end + DRAIN_S)
        counts = counter.between(run.t_start, run.t_end)
        run.compiles_in_window = sum(counts.values())
        log(f"compiles in window: {counts}")
        log(pauses.summary(run.t_start, run.t_end))
        late = traffic.lateness_s(run.requests)
        if offsets is not None and len(late):
            worst = int(late.argmax())
            log(f"generator lateness: p50={np.percentile(late, 50) * 1e3:.3f}ms "
                f"p99={np.percentile(late, 99) * 1e3:.3f}ms max={late.max() * 1e3:.3f}ms "
                f"(due at {offsets[worst]:.3f}s) offered={len(offsets)}")
        log(f"phase=window attempted={len(run.requests)} failed={len(run.failed())} "
            f"unresolved={missing} completed_in_window={len(run.completed_in_window())}")
        if chip:
            stats = devs[0].memory_stats() or {}
            run.memory_peak_bytes = stats.get("peak_bytes_in_use")
        done = [i for i, r in enumerate(run.requests) if r.error is None and not math.isnan(r.done)]
        rng = np.random.default_rng(models.seed_words(seed, "sample"))
        n = min(cfg["check"]["sample"], len(done))
        run.sample = np.sort(rng.choice(done, size=n, replace=False)) if n else np.zeros(0, int)
        run.served = np.stack([
            np.asarray(run.requests[i].ticket.result(timeout=0)).reshape(-1)
            for i in run.sample
        ]) if n else np.zeros((0, 0), np.float32)
    finally:
        server.stop()
    for r in run.requests:
        r.ticket = None
    del server, params
    gc.collect()

    if tracer:
        t = time.perf_counter()
        path = devtrace.xplane_path(tracer.log_dir)
        if path is not None:
            size = os.path.getsize(path)
            run.devices, run.host = devtrace.load(path)
            run.trace_window = devtrace.window(run.host)
            run.trace_host = tracer.host_window
            log(f"trace: {size} bytes, {sum(map(len, run.devices.values()))} device ops, "
                f"{len(run.host)} host spans, read in {time.perf_counter() - t:.1f}s")
        shutil.rmtree(tracer.log_dir, ignore_errors=True)  # the disk keeps every block
    t = time.perf_counter()
    check(run, images)
    log(f"reference: {time.perf_counter() - t:.1f}s")
    log(f"phase=check attempted={len(run.sample)} failed="
        f"{int((logit_gap(run.served, run.expected) > cfg['check']['limit']).sum()) if len(run.sample) else 0}")
    return run


def check(run: Run, images: np.ndarray) -> None:
    """The reference over the sampled answers' images, with weights made
    anew from the seed; sets ``run.expected`` and ``run.reading``."""
    cfg = run.cell.config
    if not len(run.sample):
        return
    params = models.make_params(cfg, run.seed)
    idx = np.array([run.requests[i].image for i in run.sample])
    run.expected = reference.run_blocks(cfg, params, images[idx], cfg["check"]["block"])
    del params
    ok = np.isfinite(run.served).all() and run.served.shape == run.expected.shape
    run.reading = float(logit_gap(run.served, run.expected).max()) if ok else math.inf


# -------------------------------------------------------------- metrics
def reader(name: str, root: str = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def result(run: Run, trace: bool, devs) -> dict:
    cell = run.cell
    lim = cell.config["check"]["limit"]
    # an answer that never came, or says the wrong thing, is not correct
    correct = bool(len(run.sample)) and run.reading <= lim and not run.failed()
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    out = {
        "correct": correct,
        "attempted": len(run.requests),
        "failed": len(run.failed()),
        "metrics": metrics,
        "device": device,
    }
    if trace and run.devices and run.trace_window:
        lo, hi = run.trace_window
        used = sorted(run.devices)[:cell.chips]
        busy = [devtrace.busy_ns(run.devices[d], lo, hi) for d in used]
        device["busy_s"] = sum(busy) / len(busy) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        ops = run.devices[used[0]]
        out["breakdown"] = {
            "device_ops": devtrace.top_ops(ops, lo, hi),
            "idle_gaps": devtrace.idle_gaps(ops, run.host, lo, hi),
        }
    out["check"] = {"max_logit_gap": {"value": run.reading, "limit": lim}}
    return out


def main(argv=None, t0: Optional[float] = None) -> int:
    import argparse

    t0 = time.perf_counter() if t0 is None else t0
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)

    cell = Cell.find(a.workload)
    try:
        devs = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_cache()
    print(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    run = run_cell(cell, a.seed, a.seconds, bool(a.trace), t0,
                   log=lambda s: print(s, flush=True))
    t = time.perf_counter()
    res = result(run, bool(a.trace), devs)
    print(f"metrics read in {time.perf_counter() - t:.1f}s", flush=True)
    print(f"setup_s={run.setup_s} window_s={run.t_end - run.t_start} "
          f"run_s={time.perf_counter() - t0}", flush=True)
    for name, c in res["check"].items():
        print(f"check: {name}={c['value']} limit={c['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(res), flush=True)
    return 0
