"""Share of the traced window in which the device was idle under no
engine stage: between queries, or in the ``query`` span's own time (layer:
device; ``bench/stages.py``). Small when the stages cover the host's work."""
from stages import UNTRACED, idle_share


def read(run):
    return idle_share(run, UNTRACED)
