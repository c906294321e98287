"""The one traffic generator: closed and open loops from a data file.

A traffic mix is ``bench/traffic/<name>.json``.  Its keys:

``loop``        ``"closed"``: one client keeps the server's bounded ingress
                full with blocking submits (MLPerf Offline).  ``"open"``:
                requests are due on an arrival schedule, whether or not the
                server kept up (MLPerf Server).
``batch_size``  the micro-batch ``serve()`` is built with.
``flush_timeout_s``  optional; ``serve()``'s default when absent.
``images``      how many distinct seeded images the requests cycle through.
``arrivals``    open loop only: ``{"process": "poisson", "rate": r}`` or
                ``{"process": "mmpp", "calm_rate", "burst_rate", "calm_s",
                "burst_s"}`` (rates in requests per second).

An open loop times each request from when it was due, so a generator or
an ingress that falls behind shows as latency (no coordinated omission),
and reports how late the generator ran.  Times are ``time.perf_counter``.
"""
from __future__ import annotations

import dataclasses
import math
import random
import time
from typing import List, Optional, Sequence

import numpy as np


# ------------------------------------------------------------ statistics
def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank: the value at 1-based rank ``ceil(q/100 * N)`` of the
    sorted samples, clamped to [1, N]; 0.0 on empty input.  (A copy of the
    rule in ``repro.core.queueing.empirical_percentile``, kept here so the
    yardstick cannot move with the program.)"""
    if not values:
        return 0.0
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[min(rank, len(xs)) - 1]


# -------------------------------------------------------------- arrivals
def poisson_offsets(rate: float, duration_s: float, seed: int) -> List[float]:
    """Poisson arrivals with the same work for every seed.

    ``round(rate * duration_s)`` arrivals whose gaps are the exponential
    distribution's quantiles at (i + 1/2)/N, in an order drawn from the
    seed: every seed offers the same gaps and the same count, so runs
    differ in arrival order only, and the offsets end near ``duration_s``.
    """
    if rate <= 0.0 or duration_s <= 0.0:
        raise ValueError(f"need rate > 0 and duration > 0, got {rate}, {duration_s}")
    n = max(1, round(rate * duration_s))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    random.Random(seed).shuffle(gaps)
    scale = duration_s / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


def mmpp_offsets(
    calm_rate: float, burst_rate: float, duration_s: float,
    calm_s: float, burst_s: float, seed: int,
) -> List[float]:
    """Two-state Markov-modulated Poisson arrivals: calm and burst phases
    with exponential dwells (means ``calm_s``, ``burst_s``), thinned from
    the envelope rate.  Copied from ``repro.serving.loadgen.mmpp_trace``;
    its count varies with the seed."""
    if min(calm_rate, burst_rate) <= 0.0:
        raise ValueError("rates must be > 0")
    if min(calm_s, burst_s, duration_s) <= 0.0:
        raise ValueError("durations must be > 0")
    rng = random.Random(seed)
    phases = []
    t, calm = 0.0, True
    while t < duration_s:
        dwell = rng.expovariate(1.0 / (calm_s if calm else burst_s))
        end = min(t + dwell, duration_s)
        phases.append((t, end, calm_rate if calm else burst_rate))
        t, calm = end, not calm
    envelope = max(calm_rate, burst_rate)

    def rate_at(when: float) -> float:
        for s, e, r in phases:
            if s <= when < e:
                return r
        return phases[-1][2]

    times = []
    t = 0.0
    while True:
        t += rng.expovariate(envelope)
        if t > duration_s:
            break
        if rng.random() < rate_at(t) / envelope:
            times.append(t)
    return times


def arrival_offsets(arrivals: dict, duration_s: float, seed: int) -> List[float]:
    kind = arrivals["process"]
    if kind == "poisson":
        return poisson_offsets(arrivals["rate"], duration_s, seed)
    if kind == "mmpp":
        return mmpp_offsets(
            arrivals["calm_rate"], arrivals["burst_rate"], duration_s,
            arrivals["calm_s"], arrivals["burst_s"], seed,
        )
    raise ValueError(f"unknown arrival process {kind!r}")


# ---------------------------------------------------------------- driver
@dataclasses.dataclass
class Request:
    """One request as the benchmark saw it."""

    image: int  # index into the image pool
    due: float  # when it was due (open loop) or submitted (closed loop)
    sent: float = math.nan  # when submit() was called
    done: float = math.nan  # when its ticket resolved or failed
    submitted: float = math.nan  # the program's own stamps on the ticket
    dequeued: Optional[float] = None
    ticket: object = None
    error: Optional[BaseException] = None


def _submit(server, images, req: Request) -> None:
    import jax

    req.sent = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.submit"):
        try:
            t = server.submit(images[req.image])
        except Exception as e:  # noqa: BLE001 — a refused request is a failure
            req.error, req.done = e, time.perf_counter()
            return
    req.ticket = t

    def finish(tk, r=req):
        done = time.perf_counter()
        r.submitted, r.dequeued = tk.submitted_at, tk.dequeued_at
        try:
            tk.result(timeout=0)
        except Exception as e:  # noqa: BLE001 — recorded, counted as failed
            r.error = e
        r.done = done  # last: wait_all() reads it as "callback finished"

    t.add_done_callback(finish)


def closed_loop(server, images, seconds: float, t_start: float) -> List[Request]:
    """Blocking submits from one client from ``t_start`` until the window
    closes; images cycle through the pool."""
    reqs: List[Request] = []
    t_end = t_start + seconds
    i = 0
    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        r = Request(image=i % len(images), due=now)
        reqs.append(r)
        _submit(server, images, r)
        i += 1
    return reqs


def open_loop(server, images, offsets: Sequence[float], t_start: float) -> List[Request]:
    """Submit request z when it is due, ``t_start + offsets[z]``, never
    earlier; a late generator does not shift later requests."""
    import jax

    reqs: List[Request] = []
    for z, off in enumerate(offsets):
        due = t_start + off
        delay = due - time.perf_counter()
        if delay > 0:
            with jax.profiler.TraceAnnotation("bench.wait_due"):
                time.sleep(delay)
        r = Request(image=z % len(images), due=due)
        reqs.append(r)
        _submit(server, images, r)
    return reqs


def wait_all(reqs: Sequence[Request], deadline: float) -> int:
    """Wait for every request's ticket up to ``deadline``; returns how
    many never resolved."""
    missing = 0
    for r in reqs:
        if r.ticket is None:
            continue
        try:
            r.ticket.result(timeout=max(deadline - time.perf_counter(), 0.0))
        except TimeoutError:
            missing += 1
        except Exception:  # noqa: BLE001 — failed: its callback records it
            pass
        # the done-callback runs just after the ticket's event is set
        while math.isnan(r.done) and time.perf_counter() < deadline + 1.0:
            time.sleep(1e-4)
    return missing


def lateness_s(reqs: Sequence[Request]) -> np.ndarray:
    return np.array([r.sent - r.due for r in reqs], dtype=np.float64)
