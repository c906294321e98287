#!/usr/bin/env python3
"""Readings that a cell's check limit is set from, in one process.

    python3 bench/limits.py --workload resnet50.offline --seconds 2 \
        --seeds 11 12 ... --control-seeds 11 12 13 [--controls int8 bf16]

For each seed, one run of the cell as ``bench/run.py`` makes it (its own
traffic and batch, a short window) gives the program's reading: the
widest log-probability gap of its sampled answers to the reference.  For
each control seed, the reference at each lower precision of
``--controls`` (``bench/reference.py``) on the same sampled images gives
that control's reading.  The limit lies between the largest program
reading and the smallest reading of the control the configuration's
precision calls for.  The benchmark's own runs never run this.
"""
import os
import sys
import time

T0 = time.perf_counter()
_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_BENCH, ".jax_cache")
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]


def control_reading(cell, run, quant: str) -> float:
    from bench import harness, models, reference

    cfg = cell.config
    params = models.make_params(cfg, run.seed)
    images = models.make_images(cfg, run.seed, cell.traffic["images"])
    idx = [run.requests[i].image for i in run.sample]
    ctl = reference.run_blocks(cfg, params, images[idx], cfg["check"]["block"], quant=quant)
    return float(harness.logit_gap(ctl, run.expected).max())


def main() -> int:
    import argparse
    import json

    from bench import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="+", default=["int8"], choices=["int8", "bf16"])
    a = ap.parse_args()

    cell = harness.Cell.find(a.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"limits: {e}", file=sys.stderr)
        return 2
    harness.use_cache()
    program, control = [], {q: [] for q in a.controls}
    for seed in a.seeds:
        run = harness.run_cell(cell, seed, a.seconds, False, time.perf_counter(),
                               log=lambda s: None)
        row = {"seed": seed, "program": run.reading, "answers": len(run.sample),
               "failed": len(run.failed())}
        program.append(run.reading)
        if seed in a.control_seeds:
            for q in a.controls:
                row[q] = control_reading(cell, run, q)
                control[q].append(row[q])
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": a.workload,
        "lower": max(program),
        "upper": {q: min(v) for q, v in control.items() if v},
        "program": program,
        "control": control,
        "elapsed_s": time.perf_counter() - T0,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
