"""Share of the traced window in which the device was idle while the
client dispatched a partition program (layer: device program + kernels; the
``dispatch`` spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "dispatch")
