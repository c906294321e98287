"""The device's idle time split by what the stage server's threads were
doing, from the program's host spans set against the device's ops.

Both lie on the profiler's one clock (``devtrace``).  Over the traced
stretch, every nanosecond in which the device ran no op (the complement of
``devtrace.busy_intervals``, as ``device_idle`` counts it) goes to the
first of these classes that has a span open then, on any thread:

1. ``dispatch``: ``serve.stack`` and ``serve.stage<k>.dispatch``, the host
   building a micro-batch and calling a stage program;
2. ``egress``: ``serve.egress``, splitting the last stage's output into rows
   and resolving the tickets;
3. ``ingress``: ``serve.to_device``, a submitted image made a device array;
4. ``gather``: ``serve.gather``, stage 0 waiting for images and its flush
   window.

What no class covers is ``rest``: idle time in which the server's threads
were only waiting (``serve.admit``, ``.take``, ``.wait``, ``.handoff``),
resolving tickets and running their clients' callbacks (``serve.resolve``),
or ran no span at all: the runtime's transfers, launch latency, the GIL, a
collection.  The classes never share a nanosecond, so the four shares and
``rest`` add up to the idle share.  The span names are kept here, not
imported from the program, so that the yardstick does not move with it.

The order decides only where classes overlap, and on the chip they do most
of the time (a span counts wall time, GIL waits included).  So ``report``
gives, beside the ordered split, each class's idle time without the order
(``any``) and while no other class is open (``only``), and each span's
count, length and open share of the stretch; ``bench/span_report.py``
prints it for one traced run.
"""
from __future__ import annotations

import math
import re
import statistics
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import devtrace

PREFIX = "serve."
#: in precedence order: an idle nanosecond goes to the first that covers it
CLASSES: Tuple[Tuple[str, "re.Pattern"], ...] = (
    ("dispatch", re.compile(r"serve\.(stack|stage\d+\.dispatch)$")),
    ("egress", re.compile(r"serve\.egress$")),
    ("ingress", re.compile(r"serve\.to_device$")),
    ("gather", re.compile(r"serve\.gather$")),
)

Interval = Tuple[float, float]


def _minus(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """``a`` less ``b``; both sorted, disjoint intervals."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, t = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < hi:
            out.append((t, hi))
    return out


def _length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def _union(*lists: Sequence[Interval]) -> List[Interval]:
    """The merged union of interval lists."""
    return devtrace.busy_intervals(
        [("", a, b - a) for x in lists for a, b in x], -math.inf, math.inf
    )


def _covers(host: Sequence[devtrace.Span], lo: float, hi: float) -> Dict[str, List[Interval]]:
    """Each class's spans on any thread, merged as the device's ops are."""
    return {
        key: devtrace.busy_intervals(
            [(n, s, d) for _, n, s, d in host if pattern.match(n)], lo, hi
        )
        for key, pattern in CLASSES
    }


def split_ns(
    ops: Sequence[devtrace.Op], host: Sequence[devtrace.Span], lo: float, hi: float
) -> Dict[str, float]:
    """Nanoseconds of ``[lo, hi]`` in which the device idled, as ``idle``,
    and their split into the classes and ``rest``."""
    idle = _minus([(lo, hi)], devtrace.busy_intervals(ops, lo, hi))
    out = {"idle": _length(idle)}
    for key, cover in _covers(host, lo, hi).items():
        left = _minus(idle, cover)
        out[key] = _length(idle) - _length(left)
        idle = left
    out["rest"] = _length(idle)
    return out


def overlap_ns(
    ops: Sequence[devtrace.Op], host: Sequence[devtrace.Span], lo: float, hi: float
) -> Dict[str, Dict[str, float]]:
    """Per class, the device's idle nanoseconds of ``[lo, hi]`` in which a
    span of it was open, whatever else was (``any``), and in which it was
    the only class open (``only``)."""
    idle = _minus([(lo, hi)], devtrace.busy_intervals(ops, lo, hi))
    covers = _covers(host, lo, hi)
    out: Dict[str, Dict[str, float]] = {"any": {}, "only": {}}
    for key, cover in covers.items():
        out["any"][key] = _length(idle) - _length(_minus(idle, cover))
        alone = _minus(idle, _union(*(c for k, c in covers.items() if k != key)))
        out["only"][key] = _length(alone) - _length(_minus(alone, cover))
    return out


def occupancy(host: Sequence[devtrace.Span], lo: float, hi: float) -> Dict[str, dict]:
    """For each server span name: how many start in ``[lo, hi)``, their
    mean and median length in ms, and the share of ``[lo, hi]``, in
    percent, in which one was open on any thread."""
    by_name: Dict[str, List[devtrace.Op]] = {}
    for _, n, s, d in host:
        if n.startswith(PREFIX) and lo <= s < hi:
            by_name.setdefault(n, []).append((n, s, d))
    return {
        n: {
            "count": len(spans),
            "open_pct": 100.0 * devtrace.busy_ns(spans, lo, hi) / (hi - lo),
            "mean_ms": statistics.fmean(d for _, _, d in spans) * 1e-6,
            "p50_ms": statistics.median(d for _, _, d in spans) * 1e-6,
        }
        for n, spans in sorted(by_name.items())
    }


def _server_spans(run) -> List[devtrace.Span]:
    lo, hi = run.trace_window
    return [h for h in run.host if h[1].startswith(PREFIX) and h[2] < hi and h[2] + h[3] > lo]


def _per_chip(run, host, fn) -> Dict:
    """``fn(ops, host, lo, hi)`` as percent of the traced stretch, averaged
    over the chips the cell uses; nested dicts alike."""
    lo, hi = run.trace_window
    used = sorted(run.devices)[:run.cell.chips]

    def add(total, part):
        for k, v in part.items():
            if isinstance(v, dict):
                add(total.setdefault(k, {}), v)
            else:
                total[k] = total.get(k, 0.0) + 100.0 * v / len(used) / (hi - lo)
        return total

    total: Dict = {}
    for d in used:
        add(total, fn(run.devices[d], host, lo, hi))
    return total


#: the last run read, by the identity of what was read, and its shares:
#: each of the four readers asks for the same split of one run
_memo: tuple = (None, None, None, None, None)


def shares(run) -> Optional[Dict[str, float]]:
    """``split_ns`` as percent of the traced stretch, averaged over the
    chips the cell uses.  None where the run has no device plane, or no
    span of the server inside the stretch (a program without them)."""
    global _memo
    key = (run.host, run.devices, run.trace_window, run.cell.chips)
    if key[0] is _memo[0] and key[1] is _memo[1] and key[2:] == _memo[2:4]:
        return _memo[-1]
    out = None
    if run.devices and run.trace_window:
        host = _server_spans(run)
        out = _per_chip(run, host, split_ns) if host else None
    _memo = (*key, out)
    return out


def share(run, key: str) -> Optional[float]:
    """One class's share, in percent, or None as ``shares`` gives."""
    s = shares(run)
    return None if s is None else s[key]


def report(run) -> Optional[dict]:
    """Everything this module reads from one traced run: the ordered split
    (``shares``), ``overlap_ns`` as percent of the stretch, and the server
    spans' ``occupancy``.  The device's parts are None where the run has no
    device plane; the whole is None where it has no traced stretch."""
    if run.host is None or not run.trace_window:
        return None
    lo, hi = run.trace_window
    host = _server_spans(run)
    on_device = bool(run.devices) and bool(host)
    return {
        "window_s": (hi - lo) * 1e-9,
        "split": shares(run),
        "overlap": _per_chip(run, host, overlap_ns) if on_device else None,
        "spans": occupancy(host, lo, hi),
    }
