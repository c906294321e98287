"""The whole model step's share of the chip's peak, in percent: the FLOPs
that the images answered inside the window require (from the layer
shapes, ``bench/models.py``), over the window's length times the peak
FLOP/s of ``bench/peaks.json``.  Every stage program, and all that runs
between them, counts against it; a kernel taken off the path leaves it
standing."""
from bench import models


def read(run):
    if run.peak is None:
        return None
    n = len(run.completed_in_window())
    if n == 0:
        return None
    work = n * models.flops_per_image(run.cell.config)
    window = run.t_end - run.t_start
    return 100.0 * work / (window * run.peak["flops_per_s"] * run.cell.chips)
