"""End-to-end MEASURED pipelined serving on this host: a SqueezeNet image
stream through (1) single-stage kernel-level execution, (2) the original
per-image pipelined engine on the simulated-board plan, and (3) the
production PipelineServer (persistent workers + micro-batching + bounded
queues), auto-planned by the full Pipe-it chain against *this host*:
calibrated Eq. 5/8 model -> time matrix -> Algorithms 1-3 -> runtime.

The server run repeats once per kernel execution backend
(``--backend``; default compares "xla" vs "pallas_fused" — see
repro.kernels.backend), pinning the fused route's end-to-end serving
gain: the fused backend executes every conv without materializing the
im2col patch matrix and with bias/ReLU fused into the kernel epilogue.

This is the paper's methodology transplanted: measure the deployment
target, fit the model, let the DSE balance the stages (here the "clusters"
are XLA inter-op thread groups on one shared CPU — DESIGN.md §2), then
serve continuously.  Gains come from stage overlap plus batched-dispatch
amortisation.

    PYTHONPATH=src python -m benchmarks.serving_pipeline --backend pallas_fused
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.cnn import MODELS
from repro.serving import (
    AutoPlanner,
    PipelinedGraphEngine,
    SingleStageEngine,
    host_platform,
)

from .common import PLAT, fmt_row, predicted_time_matrix

N_IMAGES = 24
BATCH = 2  # measured sweet spot on this host (EXPERIMENTS.md §Serving)
REPEATS = 3  # best-of-N: wall-clock throughput on a shared host is noisy
DEFAULT_BACKENDS = ("xla", "pallas_fused")


def _best_run(engine, images):
    """Best-of-REPEATS pass; returns the highest-throughput result."""
    best = None
    for _ in range(REPEATS):
        res = engine.run(images)
        if best is None or res["throughput"] > best["throughput"]:
            best = res
    return best


def run(backends=DEFAULT_BACKENDS):
    graph = MODELS["squeezenet"]()
    params = graph.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    images = [
        jnp.asarray(rng.standard_normal((1, *graph.input_shape)), jnp.float32)
        for _ in range(N_IMAGES)
    ]

    single = SingleStageEngine(graph, params)
    single.warmup(images[0])
    res_single = _best_run(single, images)

    # the pre-PipelineServer status quo: per-image engine, board-planned
    board_plan = AutoPlanner(platform=PLAT, mode="best").plan(
        graph, predicted_time_matrix(graph.descriptors())
    )
    engine = PipelinedGraphEngine(graph, params, board_plan)
    engine.warmup(images[0])
    res_pipe = _best_run(engine, images)

    # production path: host-calibrated model -> DSE -> batched server,
    # once per kernel execution backend
    res_srv = {}
    for backend in backends:
        planner = AutoPlanner(
            platform=host_platform(2), mode="best", source="calibrated",
            backend=backend,
        )
        server = planner.build(
            graph, params, batch_size=BATCH, flush_timeout_s=0.02, queue_depth=4
        )
        server.run(images[: 4 * BATCH])  # settle: workers warm, caches hot
        res_srv[backend] = _best_run(server, images)
        server.stop()
        # outputs must equal the kernel-level baseline on every backend
        for a, b in zip(res_single["outputs"], res_srv[backend]["outputs"]):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )

    rows = []
    ref_backend = backends[0]
    for backend in backends:
        res = res_srv[backend]
        occ = max(s["occupancy"] for s in res["metrics"]["stages"])
        p95 = res["metrics"]["e2e_p95_s"]
        gain = res["throughput"] / res_single["throughput"] - 1
        vs_ref = res["throughput"] / res_srv[ref_backend]["throughput"]
        rows.append(
            fmt_row(
                f"serving_pipeline_squeezenet_{backend}",
                1e6 / res["throughput"],
                f"single={res_single['throughput']:.2f}img/s "
                f"pipelined[{res_pipe['stages']}]={res_pipe['throughput']:.2f}img/s "
                f"server[{res['stages']},b={BATCH}]={res['throughput']:.2f}img/s "
                f"gain={gain*100:+.1f}% vs_{ref_backend}={vs_ref:.2f}x "
                f"bottleneck_occ={occ:.2f} e2e_p95={p95*1e3:.0f}ms "
                f"outputs_equal=yes (one shared CPU device; DESIGN.md §2)",
            )
        )
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--backend",
        action="append",
        choices=("xla", "pallas_fused"),
        help="kernel execution backend for the server run (repeatable); "
        "default compares xla and pallas_fused",
    )
    args = ap.parse_args()
    print("name,us_per_call,derived")
    for row in run(tuple(args.backend) if args.backend else DEFAULT_BACKENDS):
        print(row)


if __name__ == "__main__":
    main()
