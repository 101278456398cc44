"""Share of the traced window in which the device was idle while the
client waited on a partition's transfer (layer: H2D transfer; the ``h2d_wait``
spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "h2d_wait")
