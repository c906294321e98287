"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
chips the cell uses, in percent."""
from bench import devtrace


def read(run):
    if not run.devices or not run.trace_window:
        return None
    lo, hi = run.trace_window
    used = sorted(run.devices)[:run.cell.chips]
    busy = sum(devtrace.busy_ns(run.devices[d], lo, hi) for d in used) / len(used)
    return 100.0 * (1.0 - busy / (hi - lo))
