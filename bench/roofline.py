"""Peaks of each device and the bytes a partition program has to read.

``program_roofline`` divides the stored bytes of the columns a query
references, in the partitions it executed, by the device's HBM bandwidth
and by the device time of its partition programs. The bytes are counted
from the stored buffers here, not taken from the engine, and they are not
the H2D bytes: the streamed path ships every column of a partition, while
the program reads only the referenced ones.
"""
from __future__ import annotations

import json
import os
from typing import Iterable

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
PROGRAM = "jit_wrapped"  # XLA module name of a partition program


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"bench/peaks.json has no device {device_kind!r}")
    return table[device_kind]


def stored_bytes(tree) -> int:
    """Bytes of a column's stored buffers (0-d metadata leaves excluded)."""
    import jax

    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(tree)
               if getattr(leaf, "ndim", 0) > 0)


def referenced_bytes(table, columns: Iterable[str], parts: Iterable[int]
                     ) -> int:
    """Stored bytes of ``columns`` summed over partitions ``parts``."""
    cols = tuple(columns)
    return sum(stored_bytes(table.partitions[p].table.columns[c])
               for p in parts for c in cols)


def program_seconds(module_s: dict) -> float:
    """Device seconds of the partition programs among the trace's modules."""
    return sum(v for k, v in module_s.items() if k.startswith(PROGRAM))
