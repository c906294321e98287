"""Share of the traced stretch in which the device idled while stage 0
waited for images or its flush window (``serve.gather``), and no dispatch,
egress or ingress span was open (``bench/idle_split.py``), in percent."""
from bench import idle_split


def read(run):
    return idle_split.share(run, "gather")
