"""What the sequence model's kernels need, computed from shapes and from
the program's counters — the benchmark's own operation and byte counts.

Counts are the least a correct implementation must do, so a roofline share
built on them cannot pass 100 %: padded tokens, the masked half of a
diagonal attention block, a weight tile read again for a second row tile and
the sort that groups rows by expert are not counted.
"""

from __future__ import annotations


def expert_products(assignments: float, experts_touched: float, hidden: int,
                    width: int, weight_bytes: int = 2) -> dict:
    """The routed experts' SwiGLUs of one dispatch (all sparse layers):
    ``assignments`` (token, expert) pairs, ``experts_touched`` distinct
    (layer, expert) pairs that received a token.

    flops: three ``hidden x width`` products per assignment.  bytes: each
    touched expert's three matrices cross HBM once; each assignment's row is
    read once and written once at the weights' width.
    """
    return {
        "flops": 2.0 * 3 * hidden * width * assignments,
        "bytes": float(experts_touched * 3 * hidden * width * weight_bytes
                       + assignments * 2 * hidden * weight_bytes),
    }


def latent_attention(causal_pairs: float, tokens: float, layers: int,
                     heads: int, d_nope: int, d_rope: int, d_v: int,
                     act_bytes: int = 2) -> dict:
    """The attention kernel of one dispatch, all layers: ``causal_pairs`` =
    sum over histories of n(n+1)/2 (query, key) pairs, ``tokens`` real
    tokens.

    flops: per pair and head one q.k product over d_nope + d_rope and one
    p.v over d_v.  bytes: q, k_nope, v and the output once per head, the
    shared rotary key once.
    """
    per_token = (heads * (d_nope + d_rope + d_nope + d_v + d_v) + d_rope)
    return {
        "flops": 2.0 * layers * heads * causal_pairs * (d_nope + d_rope + d_v),
        "bytes": float(layers * tokens * per_token * act_bytes),
    }
