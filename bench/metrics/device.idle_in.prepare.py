"""Share of the traced window in which the device was idle while the
client prepared the query's inputs or pruned partitions by zone maps
(layer: plan + preparation; the ``prepare`` and ``prune`` spans,
``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "prepare", "prune")
