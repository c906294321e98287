"""Share of the traced stretch in which the device idled while the egress
worker split outputs into rows and resolved tickets (``serve.egress``),
and no dispatch span was open (``bench/idle_split.py``), in percent."""
from bench import idle_split


def read(run):
    return idle_split.share(run, "egress")
