"""Share of the traced window in which the device was idle while the
client finalized the folded partials (layer: host fold; the ``finalize``
spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "finalize")
