"""What the parallel state-space / attention model's scan needs, computed
from shapes and from the program's counters — the benchmark's own operation
and byte counts.

Counts are the least a correct implementation must do, so a roofline share
built on them cannot pass 100 %.  For the scan that is the work of the
RECURRENCE, not of one chunking of it (``costs_gdn.py``'s rule): padded
tokens, a chunk's ``C B^T`` tile and its masked half, a state written back
between chunks and the f32 side inputs of the chunked form are not counted,
so the share reads the same whatever implements the scan.
"""

from __future__ import annotations


def state_space_scan(tokens: float, rows: float, heads: int, groups: int,
                     d_head: int, d_state: int, act_bytes: int = 2) -> dict:
    """The gated state-space recurrence of one dispatch, all layers:
    ``tokens`` real (token, layer) pairs, ``rows`` (history, layer) pairs —
    one state a head each.

    flops per token and head, ``5 d_head d_state``: decaying the state (1),
    the rank-one write ``h += dt x (x) B`` (2) and the read ``h C`` (2).
    bytes: x read and y written once per token and head at the activations'
    width, B and C once per token and GROUP, dt as f32 per head; one f32
    state per row and head made and dropped (it never has to cross HBM: not
    counted).
    """
    per_token = (heads * 2 * d_head * act_bytes
                 + groups * 2 * d_state * act_bytes + heads * 4)
    return {
        "flops": 5.0 * tokens * heads * d_head * d_state,
        "bytes": float(tokens * per_token),
        "states": float(rows * heads),
    }
