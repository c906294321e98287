"""Profiler trace of the measured window, reduced to numbers.

``WindowTrace`` profiles the window's first seconds with the Python
tracer off, and marks the traced stretch with a ``bench.window`` span.
``load``
reads the ``.xplane.pb`` the profiler wrote into plain tuples:

* device ops: ``(name, start_ns, dur_ns)`` per device, from each device
  plane's ``XLA Ops`` line;
* host spans: ``(thread, name, start_ns, dur_ns)`` from the host plane.

Both are on the profiler's one clock.  The functions below work on those
tuples only, so a small recorded fixture checks them without a chip.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import threading
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

DEVICE_OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"

Op = Tuple[str, float, float]  # name, start_ns, dur_ns
Span = Tuple[str, str, float, float]  # thread, name, start_ns, dur_ns


class WindowTrace:
    """Profiles the first ``seconds`` of the measured window into
    ``log_dir``: ``start`` before the window, ``open`` at its first
    request; a thread of its own marks the traced stretch with the
    ``bench.window`` span and stops the profiler when it ends, so that a
    long window does not make a trace too large to read.  ``host_window``
    holds the stretch on ``time.perf_counter``'s clock."""

    def __init__(self, log_dir: str, seconds: float):
        self.log_dir = log_dir
        self.seconds = seconds
        self.host_window: Optional[Tuple[float, float]] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def open(self, t_start: float) -> None:
        self._thread = threading.Thread(
            target=self._trace, args=(t_start + self.seconds,), name="bench-trace"
        )
        self._thread.start()

    def _trace(self, until: float) -> None:
        import jax

        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                lo = time.perf_counter()
                time.sleep(max(until - lo, 0.0))
                hi = time.perf_counter()
            self.host_window = (lo, hi)
        finally:
            jax.profiler.stop_trace()

    def close(self) -> None:
        """Wait until the profiler has stopped and written its trace."""
        if self._thread is not None:
            self._thread.join()
        else:  # the window never opened
            import jax

            jax.profiler.stop_trace()


def xplane_path(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device ops per device plane, and host spans, from one xplane file."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Span] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    devices[plane.name] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (line.name, e.name, float(e.start_ns), float(e.duration_ns))
                    for e in line.events
                )
    return devices, host


# -------------------------------------------------------------- reduction
def window(host: Sequence[Span]) -> Optional[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of the benchmark's window span."""
    for _, name, start, dur in host:
        if name == WINDOW_SPAN:
            return start, start + dur
    return None


def _clip(ops: Iterable[Op], lo: float, hi: float) -> List[Tuple[float, float, str]]:
    out = []
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b, name))
    out.sort()
    return out


def busy_intervals(ops: Sequence[Op], lo: float, hi: float) -> List[Tuple[float, float]]:
    """The union of the ops' intervals inside [lo, hi], merged and sorted."""
    merged: List[List[float]] = []
    for a, b, _ in _clip(ops, lo, hi):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(ops: Sequence[Op], lo: float, hi: float) -> float:
    return sum(b - a for a, b in busy_intervals(ops, lo, hi))


def op_ns(ops: Sequence[Op], lo: float, hi: float, match) -> float:
    """Device time inside [lo, hi] of the ops whose name ``match`` accepts."""
    return sum(b - a for a, b, name in _clip(ops, lo, hi) if match(name))


def short_name(name: str) -> str:
    """An op's HLO text cut to its instruction name and result shape:
    ``%_conv_fused_call.10 = f32[32,224,256,64]``."""
    return name.split("{", 1)[0].strip()


def top_ops(ops: Sequence[Op], lo: float, hi: float, n: int = 10) -> List[list]:
    """``[[name, seconds], ...]``: the ops that took most device time."""
    tot: Dict[str, float] = {}
    for a, b, name in _clip(ops, lo, hi):
        k = short_name(name)
        tot[k] = tot.get(k, 0.0) + (b - a)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(
    ops: Sequence[Op], host: Sequence[Span], lo: float, hi: float, n: int = 10
) -> List[list]:
    """``[[label, seconds], ...]``: the longest idle stretches of the device
    inside the window, each labelled by the host span that overlaps it
    most (``thread/name``; ``-`` where no span does) and by its offset
    from the window's start."""
    busy = busy_intervals(ops, lo, hi)
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [s for s in host if s[1] != WINDOW_SPAN]
    starts = np.array([s[2] for s in spans], dtype=np.float64)
    ends = starts + np.array([s[3] for s in spans], dtype=np.float64)
    out = []
    for a, b in gaps[:n]:
        best = "-"
        if len(spans):
            ov = np.minimum(b, ends) - np.maximum(a, starts)
            i = int(np.argmax(ov))
            if ov[i] > 0:
                best = f"{spans[i][0]}/{spans[i][1]}"
        out.append([f"{best} @{(a - lo) * 1e-9:.3f}s", (b - a) * 1e-9])
    return out


# ---------------------------------------------------------------- fixture
def load_fixture(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device ops and host spans saved as JSON:
    ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[thread, name, start_ns, dur_ns], ...]}``."""
    with open(path) as f:
        d = json.load(f)
    devices = {k: [tuple(e) for e in v] for k, v in d["devices"].items()}
    return devices, [tuple(s) for s in d["host"]]
