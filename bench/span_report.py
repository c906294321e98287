#!/usr/bin/env python3
"""One traced run of one benchmark cell, with what its result line leaves
out: the device's idle time against the server's spans in full
(``idle_split.report``: the ordered split, each class without the order
and alone, each span's count, length and open share), and images/s over
the window, inside the traced stretch and after it, which is what tracing
costs while it is on.

    python3 bench/span_report.py --workload vgg16.offline --seed 7 --seconds 20

Prints the harness's log, then one JSON object with ``result`` (as
``bench/run.py --trace 1`` prints it), ``report`` and ``images_per_s``.
Exits non-zero, printing no result, when JAX finds no TPU.
"""
import time

T0 = time.perf_counter()  # set-up is timed from here, as in run.py

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_BENCH)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_BENCH, ".jax_cache")
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness, idle_split  # noqa: E402


def images_per_s(run) -> dict:
    """Completed images a second over the window, and inside and after
    the traced stretch (on the host's clock)."""
    def rate(lo, hi):
        return len(run.completed_between(lo, hi)) / (hi - lo) if hi > lo else None

    out = {"window": rate(run.t_start, run.t_end)}
    if run.trace_host:
        lo, hi = run.trace_host
        out["traced"] = rate(lo, hi)
        out["after_trace"] = rate(hi, run.t_end)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)

    cell = harness.Cell.find(a.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    harness.use_cache()
    run = harness.run_cell(cell, a.seed, a.seconds, True, T0,
                           log=lambda s: print(s, flush=True))
    out = {
        "result": harness.result(run, True, devs),
        "report": idle_split.report(run),
        "images_per_s": images_per_s(run),
    }
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
