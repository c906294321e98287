"""Images whose answer arrived inside the window, over the window's length
(host clock): the closed loop's throughput."""


def read(run):
    return len(run.completed_in_window()) / (run.t_end - run.t_start)
