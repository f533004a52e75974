"""The least bytes the XLA row gather of an ALS job moves, counted from
the shapes of the run (beside ``lib/rooflines.py``; imports nothing of
the program). A bucket gathers in XLA (scope ``als.gather``) unless the
fused kernel takes it: on the Pallas solve path with the fused Gramian,
a bucket at least as wide as the rank gathers inside ``gramian_fused``
and has no gather of its own.
"""

from __future__ import annotations

from typing import Dict


def gather_bytes_per_iteration(bucket_shapes: Dict, rank: int, levers: Dict) -> float:
    """One iteration, both sides: every padded slot of a gathering bucket
    reads one padded factor row and writes it into the gathered block.
    ``bucket_shapes``: side -> [[padded rows, width], ...]. The row is
    padded to a multiple of 8 floats on the Pallas solve path (56 at rank
    50), and is 2 bytes an element with ``gather_dtype`` bf16."""
    pallas = levers["solve_mode"] == "pallas"
    row = ((rank + 7) // 8 * 8 if pallas else rank) * (
        2.0 if levers["gather_dtype"] == "bf16" else 4.0)
    slots = 0.0
    for side in bucket_shapes.values():
        for rows, width in side:
            if not (pallas and levers["fused_gather"] and width >= rank):
                slots += float(rows) * width
    return slots * row * 2.0
