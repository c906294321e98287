"""Share of the traced stretch in which the device idled while ``submit()``
made an image a device array (``serve.to_device``), and no dispatch or
egress span was open (``bench/idle_split.py``), in percent."""
from bench import idle_split


def read(run):
    return idle_split.share(run, "ingress")
