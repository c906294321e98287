"""One-shot serving engines — the kernel-level baseline and the original
per-image pipelined engine.

Each pipeline stage owns (a) a contiguous node range of the CNN graph
(from a Pipe-it layer allocation, Eq. 10: the stage's service time is the
sum of its layers' times) and (b) a jit-compiled stage function.  Stages
run on their own host threads connected by bounded queues; an image
stream enters stage 0 and classified outputs leave the last stage.  This
is the one-thread-per-stage analogue of the paper's one-thread-per-core
ARM-CL scheduler: stage k processes image z while stage k+1 processes
image z-1 (paper Fig. 2, Layer-level), so steady-state throughput is set
by the slowest stage (Eq. 12).

These engines build their worker threads per ``run()`` call and move one
image at a time; the production runtime with persistent workers,
micro-batching and metrics lives in :mod:`repro.serving.server`
(``PipelineServer``).  ``SingleStageEngine`` stays as the kernel-level
baseline (whole graph, all cores on one kernel at a time — the execution
model the paper's Fig. 3 shows collapsing across clusters).

On this container every stage shares one CPU device, so the throughput
gain over single-stage execution comes from XLA inter-op parallelism
across host cores — the measured numbers are reported as such
(DESIGN.md §2).
"""
from __future__ import annotations

import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..cnn.graph import Graph
from ..core.pipeline import PipelinePlan

StageFn = Callable[..., Dict[str, jnp.ndarray]]


def build_stage_fns(
    graph: Graph, plan: PipelinePlan, backend=None
) -> List[StageFn]:
    """One jitted function per pipeline stage, named ``stage_{k}``.

    Each function executes the stage's contiguous node range against a
    live-tensor env and returns the pruned env that crosses the stage
    boundary (the activation transfer the platform's CCI/ICI model
    charges for).  The functions are shape-polymorphic over the batch
    dimension — XLA compiles one executable per distinct batch size.

    ``backend`` selects the kernel route of the stage's major layers
    (``repro.kernels.backend``: "xla", the reference and the default,
    "pallas_fused", or a resolved ``KernelBackend``).  It is resolved
    ONCE here so tuner state and fallback bookkeeping are shared across
    stages.
    """
    from ..kernels.backend import resolve_backend

    kb = resolve_backend(backend)
    return [
        _stage_fn(graph, k, start, stop, kb)
        for k, (start, stop) in enumerate(graph.stage_slices(plan.allocation))
    ]


def _stage_fn(graph: Graph, k: int, start: int, stop: int, kb) -> StageFn:
    """Stage ``k``'s program, jitted under the name ``stage_{k}``: the
    profiler's host spans read ``PjitFunction(stage_{k})`` and the device
    plane's module ``jit_stage_{k}``."""

    def stage(p, env):
        return graph.apply_range(p, env, start, stop, backend=kb)

    stage.__name__ = stage.__qualname__ = f"stage_{k}"
    return jax.jit(stage)


class SingleStageEngine:
    """Baseline: the whole graph as one jitted function (kernel-level)."""

    def __init__(self, graph: Graph, params, backend=None):
        from ..kernels.backend import resolve_backend

        kb = resolve_backend(backend)
        self.graph = graph
        self.params = params
        self._fn = jax.jit(lambda p, x: graph.apply(p, x, backend=kb))

    def warmup(self, x):
        self._fn(self.params, x).block_until_ready()

    def run(self, images: Sequence[np.ndarray]) -> Dict[str, Any]:
        outs = []
        t0 = time.perf_counter()
        for img in images:
            outs.append(self._fn(self.params, img))
        jax.block_until_ready(outs)
        dt = time.perf_counter() - t0
        return {"outputs": outs, "seconds": dt, "throughput": len(images) / dt}


class PipelinedGraphEngine:
    """Layer-level pipelined execution of a CNN graph per a PipelinePlan.

    ``stage_fn_builder`` mirrors the PipelineServer hook: a
    ``(graph, plan) -> [stage_fn]`` factory replacing the default jitted
    executables (fake-stage benchmarks inject scripted delays here).
    """

    def __init__(
        self, graph: Graph, params, plan: PipelinePlan,
        queue_depth: int = 4, backend=None, stage_fn_builder=None,
    ):
        self.graph = graph
        self.params = params
        self.plan = plan
        self.queue_depth = queue_depth
        if stage_fn_builder is None:
            self._stage_fns = build_stage_fns(graph, plan, backend=backend)
        else:
            self._stage_fns = stage_fn_builder(graph, plan)

    def warmup(self, x):
        env = {"input": x}
        for fn in self._stage_fns:
            env = fn(self.params, env)
        jax.block_until_ready(env)
        return env

    def run(self, images: Sequence[np.ndarray]) -> Dict[str, Any]:
        n_stages = len(self._stage_fns)
        qs: List[queue.Queue] = [
            queue.Queue(maxsize=self.queue_depth) for _ in range(n_stages + 1)
        ]
        results: List[Optional[Any]] = [None] * len(images)
        errors: List[BaseException] = []

        def stage_worker(si: int):
            fn = self._stage_fns[si]
            try:
                while True:
                    item = qs[si].get()
                    if item is None:
                        qs[si + 1].put(None)
                        return
                    idx, env = item
                    out_env = fn(self.params, env)
                    # materialize before handing off: the stage boundary is
                    # where the activation crosses clusters in the paper
                    jax.block_until_ready(out_env)
                    qs[si + 1].put((idx, out_env))
            except BaseException as e:  # pragma: no cover
                errors.append(e)
                qs[si + 1].put(None)

        threads = [
            threading.Thread(target=stage_worker, args=(si,), daemon=True)
            for si in range(n_stages)
        ]
        for t in threads:
            t.start()

        t0 = time.perf_counter()
        feeder_done = threading.Event()

        def feeder():
            for i, img in enumerate(images):
                qs[0].put((i, {"input": img}))
            qs[0].put(None)
            feeder_done.set()

        threading.Thread(target=feeder, daemon=True).start()

        done = 0
        while done < len(images):
            item = qs[-1].get()
            if item is None:
                break
            idx, env = item
            results[idx] = next(iter(env.values()))
            done += 1
        dt = time.perf_counter() - t0
        for t in threads:
            t.join(timeout=5)
        if errors:
            raise errors[0]
        return {
            "outputs": results,
            "seconds": dt,
            "throughput": done / dt,
            "stages": self.plan.pipeline.notation(),
        }


class TimeSlicedEngine:
    """Multi-model baseline: ONE full-width machine, time-sliced per model.

    A :class:`PipelineServer`/:class:`PipelinedGraphEngine` executes one
    graph; a single full-width deployment serving several CNNs must
    therefore *alternate* — run a slice of model A's stream, drain the
    pipeline, switch graphs, run a slice of model B's, and so on.  Every
    switch pays the pipeline fill/drain term of Eq. 11 again, and the
    slice quantum cannot grow without bound because the co-resident
    model's requests age for a whole foreign slice (the quantum-vs-latency
    trade PICO 2206.08662 §III describes).  This engine measures exactly
    that: round-robin slices of ``quantum`` images through per-model
    full-width engines, strictly serialized.

    The co-serving alternative (``MultiModelServer`` on a
    :func:`~repro.core.dse.partition_search` cluster partition) keeps one
    always-full pipeline per model instead; ``benchmarks/
    multimodel_serving.py`` compares the two.
    """

    def __init__(self, engines: Dict[str, PipelinedGraphEngine], quantum: int = 4):
        if quantum < 1:
            raise ValueError("quantum must be >= 1")
        if not engines:
            raise ValueError("need >= 1 engine")
        self.engines = dict(engines)
        self.quantum = quantum

    def warmup(self, images: Dict[str, Any]) -> None:
        for name, eng in self.engines.items():
            eng.warmup(images[name])

    def run(self, streams: Dict[str, Sequence[Any]]) -> Dict[str, Any]:
        """Serve every per-model stream to completion, one slice at a time.

        Returns per-model ordered outputs plus the aggregate wall-clock
        throughput (total images / total serialized seconds)."""
        cursors = {name: 0 for name in streams}
        outputs: Dict[str, List[Any]] = {name: [] for name in streams}
        slices = 0
        t0 = time.perf_counter()
        while True:
            progressed = False
            for name, images in streams.items():
                lo = cursors[name]
                if lo >= len(images):
                    continue
                hi = min(lo + self.quantum, len(images))
                # each slice fills AND drains the pipeline: run() spawns
                # workers, streams the slice, and joins them
                res = self.engines[name].run(images[lo:hi])
                outputs[name].extend(res["outputs"])
                cursors[name] = hi
                slices += 1
                progressed = True
            if not progressed:
                break
        dt = time.perf_counter() - t0
        total = sum(len(v) for v in streams.values())
        return {
            "outputs": outputs,
            "seconds": dt,
            "throughput": total / dt,
            "slices": slices,
            "quantum": self.quantum,
        }
