"""Stored bytes per row of the fact table (layer: storage / compression):
the encoded, packed buffers of every partition over the table's rows."""
from roofline import stored_bytes


def read(run):
    t = run.table
    if not t.nrows:
        return None
    return sum(stored_bytes(p.table.columns) for p in t.partitions) / t.nrows
