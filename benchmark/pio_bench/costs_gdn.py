"""What the hybrid sequence model's kernels need, computed from shapes and
from the program's counters — the benchmark's own operation and byte
counts.

Counts are the least a correct implementation must do, so a roofline share
built on them cannot pass 100 %.  For the scan that is the work of the
RECURRENCE, not of one chunking of it: padded tokens, a chunk's triangular
inverse, the masked half of its ``C x C`` tiles, a state written back
between chunks and the f32 side inputs of the chunked form are not counted,
so the share reads the same whatever implements the scan.
"""

from __future__ import annotations


def gated_delta_scan(tokens: float, rows: float, heads: int, d_k: int,
                     d_v: int, act_bytes: int = 2) -> dict:
    """The gated delta rule of one dispatch, all linear layers: ``tokens``
    real (token, layer) pairs, ``rows`` (history, layer) pairs — one state
    each.

    flops per token and head, ``7 d_k d_v``: decaying the state (1),
    ``S^T k`` (2), the rank-one write ``S += k u^T`` (2) and the read ``S^T
    q`` (2).  bytes: q, k, v read and o written once per token and head at
    the activations' width, g and beta as f32; one f32 state per row and
    head made and dropped (it never has to cross HBM: not counted).
    """
    per_token = heads * ((2 * d_k + 2 * d_v) * act_bytes + 2 * 4)
    return {
        "flops": 7.0 * tokens * heads * d_k * d_v,
        "bytes": float(tokens * per_token),
        "states": float(rows * heads),
    }


def causal_attention(causal_pairs: float, tokens: float, layers: int,
                     heads: int, d_head: int, act_bytes: int = 2) -> dict:
    """Plain multi-head causal attention of one dispatch, all full layers:
    ``causal_pairs`` = sum over histories of n(n+1)/2, ``tokens`` real
    tokens.  flops: per pair and head one q.k and one p.v over ``d_head``.
    bytes: q, k, v and the output once per token and head."""
    return {
        "flops": 2.0 * layers * heads * causal_pairs * 2 * d_head,
        "bytes": float(layers * tokens * heads * 4 * d_head * act_bytes),
    }
