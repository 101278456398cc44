"""Share of the traced window in which the device was idle while the
client blocked on a dispatched program: a program due but not running, e.g.
its input copy unfinished (layer: device program + kernels; the ``block``
spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "block")
