"""Share of the traced window in which the device was idle while the
client handed partitions to the transfer thread; on a fresh pool that
includes waiting for the interpreter while the new thread makes its first
copy (layer: H2D transfer; the ``h2d_issue`` spans, ``bench/stages.py``)."""
from stages import idle_share


def read(run):
    return idle_share(run, "h2d_issue")
