"""What the window/global-attention model's attention kernels need,
computed from shapes and from the program's counters — the benchmark's own
operation and byte counts.

Counts are the least a correct implementation must do, so a roofline share
built on them cannot pass 100 %: padded tokens, the masked part of a
diagonal or window-edge block, a key block read again for another query
block and the rotary embedding are not counted, so the share reads the same
whatever implements the attention.
"""

from __future__ import annotations


def windowed_attention(pairs: float, tokens: float, layers: int,
                       q_heads: int, kv_heads: int, d_head: int,
                       act_bytes: int = 2) -> dict:
    """Grouped-query attention of one dispatch over ``layers`` layers of
    one kind: ``pairs`` the (query, key) pairs the mask shows, summed over
    those layers (``min(position + 1, window)`` a real token on a window
    layer, ``position + 1`` on a global one), ``tokens`` real tokens.

    flops: per pair and QUERY head one q.k and one p.v over ``d_head``.
    bytes: q read and o written once per query head, k and v read once per
    KEY/VALUE head — the grouped heads share them."""
    return {
        "flops": 2.0 * q_heads * pairs * 2 * d_head,
        "bytes": float(layers * tokens * (2 * q_heads + 2 * kv_heads)
                       * d_head * act_bytes),
    }
