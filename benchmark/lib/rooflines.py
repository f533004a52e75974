"""The least work an algorithm needs, counted by the benchmark from the
shapes of a run: bytes to and from HBM and floating-point operations. The
counts take the algorithm's minimum and never more, so a share of the
roofline above 100 % is a wrong count here, not a fast kernel.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def least_time(flops: float, hbm_bytes: float, peaks: Dict) -> Tuple[float, str]:
    """Seconds the chip needs at its peaks, and which peak binds."""
    t_flops = flops / peaks["flops_per_s"]
    t_bytes = hbm_bytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")


def als_half_iteration(
    degrees: Sequence[int], n_cols: int, rank: int, index_bytes: int
) -> Tuple[float, float]:
    """One side's solve from the true (unpadded, untruncated-by-padding)
    rating counts of its rows.

    Bytes: each rating's column index and value read once; each gathered
    factor row read once (rank f32); the opposite table is at least read
    once even if no rating names a row; the solved table written once.
    Operations: per rating a rank x rank outer-product update of the
    Gramian (symmetric half would do, so rank*(rank+1) flops) and a rank
    update of the right-hand side (2*rank); per row one Cholesky
    (rank**3 / 3) and two triangular solves (2 * rank**2)."""
    nnz = float(sum(degrees))
    rows = float(len(degrees))
    hbm = nnz * (index_bytes + 4.0) + nnz * rank * 4.0 + rows * rank * 4.0
    flops = nnz * (rank * (rank + 1.0) + 2.0 * rank) + rows * (
        rank**3 / 3.0 + 2.0 * rank**2
    )
    return flops, hbm


def als_iteration(shape: Dict) -> Tuple[float, float]:
    """Both halves of one iteration. ``shape`` holds the degree lists as
    the program solves them (after its own truncation of rows longer than
    its widest bucket), ``n_users``, ``n_items`` and ``rank``."""
    fu, bu = als_half_iteration(
        shape["user_degrees"], shape["n_items"], shape["rank"],
        2 if shape["n_items"] <= 0xFFFF else 4,
    )
    fi, bi = als_half_iteration(
        shape["item_degrees"], shape["n_users"], shape["rank"],
        2 if shape["n_users"] <= 0xFFFF else 4,
    )
    return fu + fi, bu + bi


def topk(batch: int, n_items: int, rank: int, k: int, path: str) -> Tuple[float, float]:
    """One top-k program at its padded batch. Both paths read the item
    table once and the gathered query rows once, and write [batch, k]
    scores and ids. The dense path also writes the [batch, n_items] score
    matrix and reads it back for the selection; the streaming path keeps
    scores in VMEM. Operations: the score product alone (selection is
    comparisons)."""
    hbm = n_items * rank * 4.0 + batch * rank * 4.0 + batch * k * 8.0
    if path == "dense":
        hbm += 2.0 * batch * n_items * 4.0
    elif path != "streaming":
        raise ValueError(f"unknown top-k path {path!r}")
    return 2.0 * batch * n_items * rank, hbm
