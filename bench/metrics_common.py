"""Arithmetic that several metric readers share."""
import math


def due_latencies_ms(run):
    """Per request due in the window: milliseconds from when it was due to
    when its answer arrived; infinite for one that failed or never came."""
    return [
        math.inf if (r.error is not None or math.isnan(r.done)) else (r.done - r.due) * 1e3
        for r in run.requests
    ]
