"""What the algorithm needs, computed from shapes — the benchmark's own
operation and byte counts, never the program's cost models.

Counts are the least a correct implementation must do, so a roofline share
built on them cannot pass 100 %: recomputation, padding beyond the tile the
hardware forces, and the top-k merge's compares are not counted.
"""

from __future__ import annotations


def score_topk(batch_rows: int, n_items: int, rank: int, k: int,
               factor_bytes: int = 4) -> dict:
    """One dispatch of gather -> dot -> top-k over the whole catalog:
    ``batch_rows`` user rows (the rung, padding included: the program
    computes them) against ``n_items`` item rows of ``rank``.

    flops: one multiply-add per (row, item, rank).  bytes: every item row is
    read once, the gathered user rows once, the (rows, k) values and indices
    written once.
    """
    return {
        "flops": 2.0 * batch_rows * n_items * rank,
        "bytes": float(n_items * rank * factor_bytes
                       + batch_rows * rank * factor_bytes
                       + batch_rows * k * 8),
    }


def least_seconds(cost: dict, peaks: dict, flops_key: str) -> tuple:
    """(least time, which bound) for a cost on a device."""
    t_flops = cost["flops"] / peaks[flops_key]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "hbm")
