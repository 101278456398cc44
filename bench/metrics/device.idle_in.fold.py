"""Share of the traced window in which the device was idle while the
client folded a fetched partial (layer: host fold; the ``fold`` spans' self
time, their ``d2h`` children excluded, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "fold")
