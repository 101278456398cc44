"""Share of the traced window in which the device was idle while the
client fetched a partial to the host (layer: D2H of partials; the ``d2h``
spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "d2h")
