#!/usr/bin/env python3
"""Find the highest Poisson rate an open-loop cell's server sustains.

    python3 bench/sweep.py --workload resnet50.server --seed 5 --seconds 8 \
        --rates 120 160 200 240

Builds the cell's server once, then offers each rate for ``--seconds``
from the cell's own traffic mix with only the rate changed.  Per rate it
prints the offered and completed rates, the latency from the due time
(p50, p95) over the first and the last third of the window, and how late
the generator ran.  A rate is sustained when nearly all requests finish
inside the window and the last third's p95 is not far above the first
third's: no backlog grows.  The benchmark's own runs never run this;
the rate a cell offers is written into its traffic file.
"""
import os
import sys
import time

T0 = time.perf_counter()
_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_BENCH, ".jax_cache")
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def main() -> int:
    import argparse
    import json
    import math

    import jax
    import numpy as np

    from bench import harness, models, traffic

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    a = ap.parse_args()

    cell = harness.Cell.find(a.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    harness.use_cache()
    params = models.make_params(cell.config, a.seed)
    jax.block_until_ready(params)
    images = models.make_images(cell.config, a.seed, cell.traffic["images"])
    server = harness.build_server(cell, params)
    try:
        harness.warm_batches(server, images)
        for rate in a.rates:
            arrivals = dict(cell.traffic["arrivals"], rate=rate)
            offsets = traffic.arrival_offsets(arrivals, a.seconds, a.seed)
            t_start = time.perf_counter()
            reqs = traffic.open_loop(server, images, offsets, t_start)
            t_end = t_start + a.seconds
            traffic.wait_all(reqs, time.perf_counter() + harness.DRAIN_S)
            lat = [(r.done - r.due) * 1e3 if r.error is None else math.inf for r in reqs]
            third = len(lat) // 3
            late = traffic.lateness_s(reqs) * 1e3
            print(json.dumps({
                "rate": rate,
                "offered": len(reqs),
                "completed_in_window_per_s": sum(
                    1 for r in reqs if r.error is None and r.done <= t_end) / a.seconds,
                "p50_ms": traffic.percentile(lat, 50),
                "p95_ms": traffic.percentile(lat, 95),
                "p95_first_third_ms": traffic.percentile(lat[:third], 95),
                "p95_last_third_ms": traffic.percentile(lat[-third:], 95),
                "lateness_p99_ms": float(np.percentile(late, 99)),
                "failed": sum(1 for r in reqs if r.error is not None),
            }), flush=True)
            time.sleep(1.0)  # let the queues drain between rates
    finally:
        server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
