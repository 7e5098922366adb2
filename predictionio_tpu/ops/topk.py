"""Top-k selection with masking — the serving-side ranking primitive.

:func:`gather_score_topk` is the ONE public entrypoint for the serving
score path; everything (fastpath, tests, bench) calls through it.  It
dispatches between two backends behind a single seam:

* ``reference`` — plain XLA: gather, dot, ``lax.top_k`` as separate ops
  (the (B, n_items) score matrix exists as an XLA intermediate in HBM).
* ``fused`` — the Pallas kernel (``ops/score_kernel.py``): gather, dot,
  and a masked running top-k in one kernel, factors staying in VMEM
  between stages.  Off-TPU the same kernel runs in interpret mode.

Selection: the ``backend=`` argument (``fused`` | ``reference`` | ``auto``,
default ``auto``).  ``auto`` picks the fused kernel ONLY on TPU — it never silently selects the TPU kernel
on CPU, where interpret mode would lose badly; forcing ``fused`` off-TPU
is explicit opt-in (that is how the CPU equivalence tests run the real
kernel).  ``PIO_NATIVE=0`` (the repo-wide native kill switch) forces
``reference`` regardless.

Quantized factors (bf16 / int8 + per-row scales, ``ops/quantize.py``) are
accepted by both backends: the reference path dequantizes in XLA before
the matmul, the fused path dequantizes in VMEM after the HBM stream —
identical math, so the equivalence suite can compare them bit-for-bit.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.ops.quantize import contraction_precision

NEG_INF = jnp.float32(-1e30)

BACKENDS = ("fused", "reference", "auto")
# jax.named_scope around the serving score program (gather -> score ->
# top-k): on a TPU the Pallas call's device op is named after it
SCORE_SCOPE = "pio.score_topk"


def resolve_backend(requested: Optional[str] = None) -> str:
    """Resolve the score-path backend: ``"fused"`` or ``"reference"``.

    ``requested`` is a caller's ``backend=``; ``auto`` (the default)
    takes the fused kernel only on TPU.  ``PIO_NATIVE=0`` forces the
    reference path — the same kill switch that disables every other
    native kernel in the repo.
    """
    req = (requested or "auto").strip().lower()
    if req not in BACKENDS:
        raise ValueError(
            f"backend= must be one of {BACKENDS}, got {req!r}"
        )
    if os.environ.get("PIO_NATIVE", "1") == "0":
        return "reference"
    if req == "auto":
        from predictionio_tpu.ops import score_kernel

        return "fused" if score_kernel.use_fused_default() else "reference"
    return req


def top_k_with_mask(scores: jax.Array, k: int, mask: jax.Array | None = None):
    """(values, indices) of the k best scores; masked slots never win.

    ``mask`` is True for EXCLUDED entries (seen items, blacklist, padding).
    """
    if mask is not None:
        scores = jnp.where(mask, NEG_INF, scores)
    return jax.lax.top_k(scores, k)


def merge_topk(
    values: jax.Array, indices: jax.Array, k: int
) -> tuple[jax.Array, jax.Array]:
    """Merge per-shard leaderboards into a global top-k.

    ``values``/``indices`` are ``(B, M)`` candidate rows — the
    concatenation of every shard's local ``(B, local_k)`` leaderboard,
    carrying GLOBAL item indices.  Rows are re-ranked by
    ``(value desc, index asc)`` via a two-key stable sort, which is
    exactly ``lax.top_k``'s tie order (smallest index wins), so a merge
    over any shard partition returns bit-identical winners to a single
    ``top_k`` over the full score row — including ties that span shards.
    Returns ``(values (B, k), indices (B, k))``.
    """
    neg_vals, idx = jax.lax.sort(
        (-values, indices.astype(jnp.int32)), num_keys=2
    )
    return -neg_vals[:, :k], idx[:, :k]


def two_tier_merge_topk(
    values: jax.Array,
    indices: jax.Array,
    k: int,
    *,
    group_axis: str,
    host_axis: str,
) -> tuple[jax.Array, jax.Array]:
    """Pod-mesh leaderboard merge: on-host gather+merge, then one small
    cross-host gather+merge.  Called INSIDE ``shard_map`` over a 2-D
    ``(host_axis, group_axis)`` mesh.

    ``values``/``indices`` are this shard's local ``(B, local_k)``
    leaderboard (global item ids).  Tier 1 all-gathers the G on-host
    shards over ``group_axis`` — a device collective inside the host row,
    ICI on a real pod — and merges them to one per-host ``(B, k)``
    leaderboard.  Tier 2 all-gathers the H host leaderboards over
    ``host_axis`` and merges again; that ``H·B·k·8``-byte gather is the
    ONLY cross-host traffic, ``S/H × local_k/k`` smaller than the flat
    ``(S, B, local_k)`` all-gather it replaces (byte derivation in
    docs/perf_roofline.md).  Both tiers rerank with :func:`merge_topk`'s
    two-key ``(value desc, id asc)`` sort — exactly ``lax.top_k``'s tie
    order — so tiering the merge cannot change a single winner: the
    result is bit-identical to one ``top_k`` over the full score row.
    Returns replicated ``(values (B, k), indices (B, k))``.
    """
    b = values.shape[0]
    gv = jax.lax.all_gather(values, group_axis)  # (G, B, local_k)
    gg = jax.lax.all_gather(indices, group_axis)
    g, lk = gv.shape[0], gv.shape[2]
    host_v, host_g = merge_topk(
        jnp.swapaxes(gv, 0, 1).reshape(b, g * lk),
        jnp.swapaxes(gg, 0, 1).reshape(b, g * lk),
        min(k, g * lk),
    )
    cv = jax.lax.all_gather(host_v, host_axis)  # (H, B, k) — the DCN hop
    cg = jax.lax.all_gather(host_g, host_axis)
    h, hk = cv.shape[0], cv.shape[2]
    return merge_topk(
        jnp.swapaxes(cv, 0, 1).reshape(b, h * hk),
        jnp.swapaxes(cg, 0, 1).reshape(b, h * hk),
        k,
    )


def _dequantize(F: jax.Array, scale: Optional[jax.Array]) -> jax.Array:
    """XLA-side dequantize: the f32 math the fused kernel does in VMEM."""
    if F.dtype != jnp.float32:
        F = F.astype(jnp.float32)
    if scale is not None:
        F = F * scale
    return F


def gather_score_topk(
    U: jax.Array, V: jax.Array, u_idx: jax.Array, k: int,
    item_mask: jax.Array | None = None,
    *,
    u_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    backend: Optional[str] = None,
    interpret: Optional[bool] = None,
    with_stats: bool = False,
):
    """Fused gather→score→top-k: the serving fast-path device program.

    ``U[u_idx] @ V.T`` then masked top-k — as one Pallas kernel on the
    fused backend, or separate XLA ops on the reference backend (see the
    module docstring for the dispatch rules).  ``item_mask`` is True for
    slots that must never win (padded item tail, blacklists): a bool
    ``(n_items,)`` vector or the int32 ``(1, n_items)`` lane row of
    ``score_kernel.item_mask_row``; it broadcasts over the batch.  ``u_scale``/``v_scale`` are the per-row
    int8 scales from :mod:`ops.quantize`.  Returns
    ``(values (B, k), indices (B, k))``; ``with_stats`` appends the fused
    kernel's merge counters (int32 ``(2,)``: passes, blocks) and is an
    error on the reference backend, which has no merge to count.
    """
    be = resolve_backend(backend)
    if with_stats and be != "fused":
        raise ValueError("with_stats needs the fused backend")
    # the stable name a device trace finds this program's ops by, whatever
    # the jitted function around it is called
    with jax.named_scope(SCORE_SCOPE):
        if be == "fused":
            from predictionio_tpu.ops import score_kernel

            return score_kernel.fused_gather_score_topk(
                U, V, u_idx, k, item_mask,
                u_scale=u_scale, v_scale=v_scale, interpret=interpret,
                with_stats=with_stats,
            )
        Uf = _dequantize(U, u_scale)
        # item scale applies AFTER the matmul (scores scale per item
        # column) — the same op order as the fused kernel, so the two
        # backends round identically and the equivalence suite can compare
        # them exactly
        Vf = _dequantize(V, None)
        scores = jnp.matmul(  # (B, rank) @ (rank, n_items_pad)
            Uf[u_idx], Vf.T, precision=contraction_precision(V.dtype)
        )
        if v_scale is not None:
            scores = scores * v_scale.reshape(1, -1)
        # a bool (n,) vector, or the fused kernel's int32 (1, n) lane row
        mask = (
            item_mask.reshape(1, -1) != 0 if item_mask is not None else None
        )
        return top_k_with_mask(scores, k, mask=mask)
