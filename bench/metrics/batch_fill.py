"""Share of stage 0's micro-batch rows that held an image rather than
padding over the window, from the stage's own ``items`` and
``padded_items`` counters, in percent."""


def read(run):
    items = run.stage0_end[0] - run.stage0_start[0]
    padded = run.stage0_end[1] - run.stage0_start[1]
    if items + padded == 0:
        return None
    return 100.0 * items / (items + padded)
