"""95th percentile of the latencies ``latency_p50_ms`` takes its median of."""
from bench.metrics_common import due_latencies_ms
from bench.traffic import percentile


def read(run):
    lat = due_latencies_ms(run)
    return percentile(lat, 95) if lat else None
