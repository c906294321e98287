"""Median latency over every request due in the window, from when it was
due to when its ticket resolved; a failed request counts as infinite."""
from bench.metrics_common import due_latencies_ms
from bench.traffic import percentile


def read(run):
    lat = due_latencies_ms(run)
    return percentile(lat, 50) if lat else None
