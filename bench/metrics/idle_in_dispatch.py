"""Share of the traced stretch in which the device idled while a thread of
the server stacked a micro-batch or called a stage program
(``serve.stack``, ``serve.stage<k>.dispatch``; ``bench/idle_split.py``),
in percent."""
from bench import idle_split


def read(run):
    return idle_split.share(run, "dispatch")
