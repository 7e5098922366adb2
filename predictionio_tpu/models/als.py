"""Mesh-sharded Alternating Least Squares (explicit + implicit feedback).

Capability parity with the MLlib ALS the reference templates call
(``examples/scala-parallel-recommendation/blacklist-items/src/main/scala/
ALSAlgorithm.scala:76`` explicit; ``examples/scala-parallel-similarproduct/
multi-events-multi-algos/src/main/scala/ALSAlgorithm.scala:121`` implicit
``ALS.trainImplicit``), designed TPU-first rather than translated:

* Spark ALS block-partitions factors across executors and exchanges them by
  shuffle each half-iteration.  Here the rating triples are **pre-blocked on
  the host by entity range** — all ratings of user block *p* land on mesh
  shard *p* — so each half-step's normal-equation accumulation
  (Σ vᵢvᵢᵀ, Σ rᵤᵢvᵢ) is a purely local ``segment_sum`` under ``shard_map``,
  and the only communication is the all-gather of the *opposite* factor
  matrix (XLA lays it on ICI).  This is the shuffle→collective translation of
  SURVEY.md §2.7.
* Solves are batched k×k Cholesky factorizations on device
  (:func:`_batched_spd_solve` over the whole entity block at once).
* Static shapes throughout: id spaces and per-shard rating counts are padded,
  masked entries contribute zero.  Regularization is λ·n_u (ALS-WR), matching
  MLlib's scaling.

Implicit feedback follows Hu-Koren-Volinsky: confidence c=1+αr, preference
p=1; the global Gram matrix VᵀV is computed once per half-step (a k×k
``psum``) and the per-user correction uses only that user's ratings.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import threading
import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.ops.quantize import contraction_precision
from predictionio_tpu.ops.segment import segment_sum
from predictionio_tpu.parallel.mesh import (
    DATA_AXIS,
    MeshContext,
    device_get_global,
    pad_to_multiple,
    pcast_varying,
    shard_map,
)

logger = logging.getLogger(__name__)

# f32 factor contractions state their precision: a TPU's default would run
# them in one bf16 pass (ops/quantize.contraction_precision has the reason)
_F32_PRECISION = contraction_precision(jnp.float32)


@dataclasses.dataclass
class ALSConfig:
    rank: int = 10
    iterations: int = 10
    reg: float = 0.01  # lambda (per-rating, ALS-WR scaled)
    implicit: bool = False
    alpha: float = 1.0  # implicit confidence scale
    seed: int = 3
    # mid-training checkpoint/resume (orbax; SURVEY.md §5): factors + step
    # saved every checkpoint_interval iterations under checkpoint_dir;
    # training resumes from the latest step found there
    checkpoint_dir: Optional[str] = None
    checkpoint_interval: int = 5
    # Compute dtype for the GATHERED opposite factors ("f32" | "bf16" |
    # "int8"): bf16 stores/gathers the opposite matrix in bfloat16 (halves
    # the gather + all-gather HBM traffic), int8 quantizes it per half-step
    # with per-row scales (quarter the one-pass V read on the fused
    # kernel); every contraction accumulates f32.  None → the
    # PIO_ALS_COMPUTE_DTYPE env knob (default "f32"), resolved at
    # construction time like `solver`.
    compute_dtype: Optional[str] = None
    # Relabel entities by rating count (round-robin hot entities across
    # shards) before range-blocking, so Zipf-skewed catalogs don't pad
    # every shard to the hottest block's length. Pure host-side; factors
    # are returned in original id order either way.
    rebalance: bool = True
    # Normal-equation accumulation strategy:
    #   "dense"   — degree-bucketed batched einsum (the TPU path): entities
    #               are relabeled so each shard holds them in descending
    #               rating-count order, split into power-of-two degree
    #               buckets, and each bucket's Σ v vᵀ / Σ r v reduces as one
    #               gather + batched matmul — MXU work, ZERO scatter.
    #   "segment" — rating-stream segment_sum (scatter-add) accumulation;
    #               the strict fallback (the native.py discipline) and the
    #               reference-shaped formulation.
    # PIO_ALS_SOLVER overrides the default for benchmarking A/B.  Resolved
    # at CONSTRUCTION time (None → env), not import time, so an in-process
    # sweep toggling the env var between configs takes effect.
    solver: Optional[str] = None
    # Training-kernel backend ("fused" | "reference" | "auto"): the
    # dispatch seam for ops/train_kernel.py, as ops/topk.resolve_backend
    # is the score kernel's.
    # "auto" takes the Pallas path only on real TPU; PIO_NATIVE=0 forces
    # "reference" at resolution time.  None → the PIO_TRAIN_KERNEL env
    # knob (default "auto"), resolved at construction time.
    train_kernel: Optional[str] = None

    def __post_init__(self):
        if self.solver is None:
            self.solver = os.environ.get("PIO_ALS_SOLVER", "dense")
        if self.compute_dtype is None:
            self.compute_dtype = os.environ.get(
                "PIO_ALS_COMPUTE_DTYPE", "f32"
            )
        if self.train_kernel is None:
            self.train_kernel = os.environ.get("PIO_TRAIN_KERNEL", "auto")
        if self.compute_dtype not in ("f32", "bf16", "int8"):
            raise ValueError(
                "compute_dtype must be 'f32', 'bf16', or 'int8', "
                f"got {self.compute_dtype!r}"
            )
        from predictionio_tpu.ops import train_kernel as _train_kernel

        if self.train_kernel not in _train_kernel.BACKENDS:
            raise ValueError(
                f"train_kernel must be one of {_train_kernel.BACKENDS}, "
                f"got {self.train_kernel!r}"
            )
        if self.solver not in ("dense", "segment"):
            raise ValueError(
                f"solver must be 'dense' or 'segment', got {self.solver!r}"
            )


@dataclasses.dataclass
class ALSModel:
    """Trained factors + id tables (host form; place on device to serve)."""

    user_factors: np.ndarray  # (n_users, rank) float32
    item_factors: np.ndarray  # (n_items, rank) float32
    user_map: BiMap
    item_map: BiMap
    config: ALSConfig = None
    # quantized serving variant (ops/quantize.py), produced at publish and
    # accuracy-gated there; "f32" means the variant is absent and serving
    # uses the float32 factors above. The fp32 factors are ALWAYS kept —
    # exact scoring, evaluation, and quantization rollback need them.
    factor_dtype: str = "f32"
    user_factors_q: Optional[np.ndarray] = None
    user_scale: Optional[np.ndarray] = None
    item_factors_q: Optional[np.ndarray] = None
    item_scale: Optional[np.ndarray] = None
    # publish-time ShardingPlan (serving/sharding.py), declared when the
    # PIO_SHARD_* knobs ask for item-factor partitioning; None serves
    # replicated. Travels inside the sealed MODELDATA pickle (auto mode)
    # or as its own sealed plan.blob (checkpoint mode).
    sharding_plan: Optional[object] = None
    # publish-time IVF coarse-retrieval index (ops/ivf.py), declared when
    # PIO_IVF_NLIST asks for an approximate scan; None serves exact.
    # Recall-gated at publish and sealed as ivf.blob (checkpoint mode).
    ivf_index: Optional[object] = None

    def predict_rating(self, user_idx: int, item_idx: int) -> float:
        return float(self.user_factors[user_idx] @ self.item_factors[item_idx])


# ---------------------------------------------------------------------------
# Host-side blocking: ratings of entity block p → mesh shard p
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Blocks:
    """Flattened per-shard rating arrays, ready for shard_map over 'data'."""

    local: np.ndarray  # (n_shards*L,) int32 entity index local to shard
    other: np.ndarray  # (n_shards*L,) int32 global opposite-entity index
    rating: np.ndarray  # (n_shards*L,) float32
    mask: np.ndarray  # (n_shards*L,) float32 1=real 0=padding
    per_shard: int  # entities per shard
    length: int  # L = ratings per shard (padded)


def _balance_permutation(
    entity: np.ndarray, n_entity_pad: int, n_shards: int
) -> np.ndarray:
    """Old-id → new-id relabeling that balances per-shard rating counts.

    Range-blocking pads every shard to the hottest block's rating count
    (`_make_blocks`); under a Zipf catalog the hot entities cluster in a few
    id ranges and the other shards burn idle FLOPs on padding.  LPT-style
    fix: order entities by descending count and deal them round-robin
    across shards, so each shard holds an equal slice of the popularity
    curve.  Returns ``perm`` with ``perm[old_id] = new_id`` (a bijection on
    ``[0, n_entity_pad)``); blocking then uses ``perm[entity]``.
    """
    import heapq

    counts = np.bincount(entity, minlength=n_entity_pad)
    order = np.argsort(-counts, kind="stable")  # hottest first
    per_shard = n_entity_pad // n_shards
    perm = np.empty(n_entity_pad, np.int64)
    # LPT greedy with capacity: hottest entity → lightest shard with a free
    # slot. Guarantees max load ≤ mean + hottest single entity; the heap is
    # (load, shard) so ties break deterministically by shard index.
    heap = [(0, p) for p in range(n_shards)]
    used = np.zeros(n_shards, np.int64)
    for o in order:
        load, p = heapq.heappop(heap)
        perm[o] = p * per_shard + used[p]
        used[p] += 1
        if used[p] < per_shard:  # full shards leave the heap; capacities sum
            heapq.heappush(heap, (load + int(counts[o]), p))  # to n_entity_pad
    return perm


def _make_blocks(
    entity: np.ndarray,
    other: np.ndarray,
    rating: np.ndarray,
    n_entity_pad: int,
    n_shards: int,
) -> _Blocks:
    per_shard = n_entity_pad // n_shards
    if n_shards == 1:
        counts = np.array([len(entity)])
        shard = None
    else:
        shard = entity // per_shard
        order = np.argsort(shard, kind="stable")
        entity, other, rating, shard = (
            entity[order],
            other[order],
            rating[order],
            shard[order],
        )
        counts = np.bincount(shard, minlength=n_shards)
    length = pad_to_multiple(int(counts.max()) if len(counts) else 1, 8)
    if length > _CHUNK:
        length = pad_to_multiple(length, _CHUNK)  # scan needs equal chunks
    local_b = np.zeros((n_shards, length), np.int32)
    other_b = np.zeros((n_shards, length), np.int32)
    rating_b = np.zeros((n_shards, length), np.float32)
    mask_b = np.zeros((n_shards, length), np.float32)
    offsets = np.concatenate([[0], np.cumsum(counts)])
    for p in range(n_shards):
        s, e = offsets[p], offsets[p + 1]
        n = e - s
        local_b[p, :n] = entity[s:e] - p * per_shard
        other_b[p, :n] = other[s:e]
        rating_b[p, :n] = rating[s:e]
        mask_b[p, :n] = 1.0
    return _Blocks(
        local=local_b.reshape(-1),
        other=other_b.reshape(-1),
        rating=rating_b.reshape(-1),
        mask=mask_b.reshape(-1),
        per_shard=per_shard,
        length=length,
    )


# ---------------------------------------------------------------------------
# Dense (degree-bucketed) blocking: the scatter-free TPU formulation
# ---------------------------------------------------------------------------


# Upper bound on elements per bucket gather intermediate (n_b·D_b); bounds
# the (n_b, D_b, k) gathered-factor tensor to ~chunk·k·4 bytes of HBM peak.
_DENSE_CHUNK = int(os.environ.get("PIO_ALS_DENSE_CHUNK", 4_194_304))


@dataclasses.dataclass
class _DenseBlocks:
    """Per-bucket dense rating matrices, ready for shard_map over 'data'.

    Bucket b covers the contiguous local-entity range [starts[b], ends[b])
    (IDENTICAL across shards — shard_map runs one program) with row width
    widths[b] ≥ every member entity's rating count.  For each bucket:
    ``idx``/``rat``/``msk`` are (n_shards, n_entities_b, width_b); padding
    slots carry idx 0 and msk 0, contributing exactly zero.
    """

    idx: list  # of (n_shards, n_b, D_b) int32 — global opposite-entity ids
    rat: list  # of (n_shards, n_b, D_b) float32
    msk: list  # of (n_shards, n_b, D_b) float32
    widths: list  # of int
    per_shard: int
    padded_ratings: int  # Σ shards·n_b·D_b — the real device workload size


def _degree_sort_permutation(
    entity: np.ndarray, n_entity_pad: int, n_shards: int
) -> np.ndarray:
    """Within each shard's id range, relabel entities by descending rating
    count (shard membership unchanged). The dense solver needs monotone
    per-shard degrees so contiguous local ranges form degree buckets; when
    LPT rebalancing is on its permutation already guarantees this, this is
    the rebalance=False companion."""
    counts = np.bincount(entity, minlength=n_entity_pad)
    per_shard = n_entity_pad // n_shards
    perm = np.empty(n_entity_pad, np.int64)
    for p in range(n_shards):
        lo = p * per_shard
        order = np.argsort(-counts[lo : lo + per_shard], kind="stable")
        perm[lo + order] = lo + np.arange(per_shard)
    return perm


def _sharded_balance_permutation(
    counts: np.ndarray,
    owner: np.ndarray,
    n_hosts: int,
    d_local: int,
    per_shard: int,
) -> np.ndarray:
    """Global old-id → blocked-id relabeling for sharded multi-host ingest.

    Entity e's rows live only on host ``owner[e]`` (the DAO shard hash), so
    its factor row must land in one of that host's ``d_local`` device
    shards. Within each host: LPT over its shards (descending global count
    → lightest shard with a free slot), giving per-shard-monotone degrees —
    the dense-bucketing precondition. Slots left over (padding ids) fill
    deterministically so the result is a bijection on [0, n_pad).
    Every host computes the identical permutation from the exchanged
    global counts; no further communication.
    """
    import heapq

    n_entities = len(counts)
    n_shards = n_hosts * d_local
    n_pad = per_shard * n_shards
    perm = np.empty(n_pad, np.int64)
    free_slots: list[int] = []
    for q in range(n_hosts):
        ids = np.flatnonzero(owner == q)
        order = ids[np.argsort(-counts[ids], kind="stable")]
        if len(order) > d_local * per_shard:
            raise ValueError(
                f"host {q} owns {len(order)} entities > capacity "
                f"{d_local * per_shard}"
            )
        heap = [(0, d) for d in range(d_local)]
        used = np.zeros(d_local, np.int64)
        for o in order:
            load, d = heapq.heappop(heap)
            perm[o] = (q * d_local + d) * per_shard + used[d]
            used[d] += 1
            if used[d] < per_shard:
                heapq.heappush(heap, (load + int(counts[o]), d))
        for d in range(d_local):
            base_slot = (q * d_local + d) * per_shard
            free_slots.extend(range(base_slot + used[d], base_slot + per_shard))
    perm[n_entities:] = np.sort(np.array(free_slots, np.int64))
    return perm


def _bucket_boundaries(dmax: np.ndarray, chunk_budget: int) -> list:
    """Split a non-increasing per-local-id max-degree curve into
    (start, end, width) buckets: width = next multiple of 8 ≥ the bucket's
    top degree, members keep degree ≥ width/2 (≤2× padding waste), and
    n·width ≤ chunk_budget bounds each gather intermediate."""
    per_shard = len(dmax)
    out = []
    j = 0
    while j < per_shard:
        width = max(8, int(-8 * (-int(dmax[j]) // 8)))  # pad8, floor 8
        cap = max(1, chunk_budget // width)
        j1 = j + 1
        while (
            j1 < per_shard
            and (j1 - j) < cap
            and (width == 8 or int(dmax[j1]) >= width // 2)
        ):
            j1 += 1
        out.append((j, j1, width))
        j = j1
    return out


def _make_dense_blocks(
    entity: np.ndarray,
    other: np.ndarray,
    rating: np.ndarray,
    n_entity_pad: int,
    n_shards: int,
    chunk_budget: int = None,
    shard_range: tuple = None,
    deg_global: np.ndarray = None,
) -> _DenseBlocks:
    """Build degree-bucketed dense rating matrices (host side).

    Requires per-shard-monotone degrees (apply the LPT or degree-sort
    permutation first).  All ratings of one entity land in one row of one
    bucket; the device half-step then needs no scatter at all.

    Multi-host: ``shard_range=(s0, s1)`` fills matrices only for shards
    [s0, s1) from THIS host's rows (the 1/N ingest path), with bucket
    boundaries cut from ``deg_global`` — the full (n_shards, per_shard)
    degree matrix every host derives from the exchanged global counts —
    so all hosts compile the same program over different data.
    """
    chunk_budget = chunk_budget or _DENSE_CHUNK
    per_shard = n_entity_pad // n_shards
    local_deg = np.bincount(entity, minlength=n_entity_pad)
    deg = (
        deg_global
        if deg_global is not None
        else local_deg.reshape(n_shards, per_shard)
    )
    bounds = _bucket_boundaries(deg.max(axis=0), chunk_budget)
    s0, s1 = shard_range if shard_range is not None else (0, n_shards)

    # sort triples by (shard, local id): each (shard, bucket) is then one
    # contiguous slice, and column position = rank within the entity
    order = np.argsort(entity, kind="stable")
    entity_s, other_s, rating_s = entity[order], other[order], rating[order]
    offsets = np.concatenate(
        [[0], np.cumsum(local_deg)]
    )  # by global blocked id, over THIS host's rows
    pos = np.arange(len(entity_s)) - offsets[entity_s]

    idx_l, rat_l, msk_l, widths = [], [], [], []
    padded = 0
    for j0, j1, width in bounds:
        n_b = j1 - j0
        idx_b = np.zeros((s1 - s0, n_b, width), np.int32)
        rat_b = np.zeros((s1 - s0, n_b, width), np.float32)
        msk_b = np.zeros((s1 - s0, n_b, width), np.float32)
        for p in range(s0, s1):
            s = offsets[p * per_shard + j0]
            e = offsets[p * per_shard + j1]
            rows = entity_s[s:e] - (p * per_shard + j0)
            cols = pos[s:e]
            idx_b[p - s0, rows, cols] = other_s[s:e]
            rat_b[p - s0, rows, cols] = rating_s[s:e]
            msk_b[p - s0, rows, cols] = 1.0
        idx_l.append(idx_b)
        rat_l.append(rat_b)
        msk_l.append(msk_b)
        widths.append(width)
        padded += (s1 - s0) * n_b * width
    return _DenseBlocks(
        idx=idx_l, rat=rat_l, msk=msk_l, widths=widths,
        per_shard=per_shard, padded_ratings=padded,
    )


# ---------------------------------------------------------------------------
# Device-side half-step: solve one side's factors from the other's
# ---------------------------------------------------------------------------


# Ratings processed per scan step: bounds the (chunk, k, k) outer-product
# intermediate so HBM peak stays flat however many ratings a shard holds.
# PIO_ALS_CHUNK overrides for hardware tuning (benchmarked, not guessed).
_CHUNK = int(os.environ.get("PIO_ALS_CHUNK", 65536))


def _half_step_local(
    local, other, rating, mask, opp_full, gram, per_shard, rank, reg, implicit,
    alpha, compute_dtype="f32", backend="reference", interpret=None,
):
    """Runs per shard: normal equations + batched Cholesky for one block.

    opp_full: the full opposite factor matrix (replicated into the shard).
    gram: VᵀV (k,k) for implicit mode, zeros otherwise.
    Accumulates A/b over rating chunks with lax.scan — peak memory is
    O(chunk·k² + per_shard·k²) instead of O(L·k²).
    ``compute_dtype`` narrows the stored/gathered opposite factors (bf16
    downcast / per-row int8); all arithmetic runs in f32 after the gather.
    ``backend="fused"`` routes the per-chunk gather through the Pallas
    gather kernel (``ops/train_kernel.py:fused_gather_rows``) — the rows
    fetch against a VMEM-resident V instead of paying XLA's per-row
    sector read; the dequantized values are identical, so the rest of the
    chunk body (and the trained factors) match bit-for-bit.
    """
    from predictionio_tpu.ops import train_kernel as _train_kernel
    from predictionio_tpu.ops.quantize import quantize_factors_jax

    L = local.shape[0]
    chunk = min(L, _CHUNK)
    n_chunks = L // chunk
    opp_q, opp_scale = quantize_factors_jax(opp_full, compute_dtype)
    if backend != "fused":
        # reference dequantizes in XLA before the gather — the same values
        # the fused kernel reconstructs in VMEM after it (per-row scale:
        # gather and dequantize commute exactly)
        opp_full = (
            opp_q if opp_scale is None
            else opp_q.astype(jnp.float32) * opp_scale
        )

    def body(carry, xs):
        A, b, cnt = carry
        lo, ot, rt, w = xs
        if backend == "fused":
            vs = _train_kernel.fused_gather_rows(
                opp_q, ot, opp_scale, interpret=interpret
            )  # (chunk, k) f32, gathered against VMEM
        else:
            vs = opp_full[ot].astype(jnp.float32)  # (chunk, k) gather
        if implicit:
            # A_u += Σ α·r · v vᵀ ;  b_u += Σ (1+α·r) · v   (p=1, c=1+αr)
            cw = alpha * rt * w
            outer = vs[:, :, None] * (vs * cw[:, None])[:, None, :]
            A = A + segment_sum(outer, lo, per_shard)
            b = b + segment_sum(vs * ((1.0 + alpha * rt) * w)[:, None], lo, per_shard)
        else:
            vsw = vs * w[:, None]
            outer = vsw[:, :, None] * vsw[:, None, :]
            A = A + segment_sum(outer, lo, per_shard)
            cnt = cnt + segment_sum(w, lo, per_shard)
            b = b + segment_sum(vsw * rt[:, None], lo, per_shard)
        return (A, b, cnt), None

    # carries differ per shard → mark them varying over the mesh axis
    init = jax.tree.map(
        lambda z: pcast_varying(z, DATA_AXIS),
        (
            jnp.zeros((per_shard, rank, rank), jnp.float32),
            jnp.zeros((per_shard, rank), jnp.float32),
            jnp.zeros((per_shard,), jnp.float32),
        ),
    )
    xs = tuple(
        a.reshape(n_chunks, chunk, *a.shape[1:])
        for a in (local, other, rating, mask)
    )
    (A, b, cnt), _ = jax.lax.scan(body, init, xs)
    return _solve_normal_equations(A, b, cnt, gram, rank, reg, implicit)


def _solve_normal_equations(A, b, cnt, gram, rank, reg, implicit):
    """Ridge + batched k×k Cholesky, shared by both accumulation paths."""
    eye = jnp.eye(rank, dtype=jnp.float32)
    if implicit:
        A = A + gram[None, :, :] + reg * eye[None, :, :]
    else:
        # λ·n_u ridge (ALS-WR, matches MLlib); +εI keeps empty rows solvable
        A = A + (reg * cnt + 1e-6)[:, None, None] * eye[None, :, :]
    return _batched_spd_solve(A, b)


def _batched_spd_solve(A, b):
    """Solve ``A[e] x[e] = b[e]`` for a batch of small SPD systems:
    ``A (n, k, k)``, ``b (n, k)`` → ``x (n, k)`` float32.

    A right-looking Cholesky and the two triangular substitutions, written
    out in elementwise ops and unrolled over the (static, small) rank —
    NOT ``jax.scipy.linalg.cho_factor``/``cho_solve``: with the batch
    spread over more than one TPU chip XLA's Cholesky/triangular-solve
    expansion returns wrong factors (measured on a four-chip v5e host,
    libtpu 0.0.34: errors of O(10) against float64, inside ``shard_map``
    and under plain SPMD alike, while one chip and every CPU mesh are
    exact), and training then diverges to NaN.  Elementwise ops have no
    such path.  The batch rides the LAST axis so every intermediate is
    lane-dense on a TPU; each system is solved independently and in a
    fixed order, so the result does not depend on how the batch is split.
    """
    k = A.shape[-1]
    S = jnp.moveaxis(A.astype(jnp.float32), 0, -1)  # (k, k, n), updated in place
    r = jnp.moveaxis(b.astype(jnp.float32), 0, -1)  # (k, n)
    below = jnp.arange(k)[:, None]
    cols = []  # cols[j]: column j of L as (k, n), zero above the diagonal
    for j in range(k):
        col = jnp.where(below >= j, S[:, j, :] / jnp.sqrt(S[j, j, :]), 0.0)
        cols.append(col)
        S = S - col[:, None, :] * col[None, :, :]
    y = []
    for i in range(k):  # L y = b, one column of L per step
        y.append(r[i] / cols[i][i])
        r = r - cols[i] * y[i]
    L = jnp.stack(cols, axis=1)  # (k, k, n): L[p, i] = L_pi
    x = [None] * k
    r = jnp.stack(y)
    for i in reversed(range(k)):  # Lᵀ x = y, one row of Lᵀ per step
        x[i] = r[i] / L[i, i]
        r = r - L[i] * x[i]
    return jnp.moveaxis(jnp.stack(x), 0, -1)


def _fold_in_dtype(compute_dtype: str):
    if compute_dtype == "f64":
        return np.float64
    if compute_dtype == "bf16":
        try:
            import ml_dtypes
            return ml_dtypes.bfloat16
        except ImportError:
            return np.float32
    return np.float32


def fold_in_users(item_factors, interactions, *, rank, reg,
                  implicit=False, alpha=1.0, compute_dtype="f32"):
    """Streaming user-side fold-in: re-solve user rows against FIXED items.

    The micro-generation delta pipeline (``core/delta.py``) calls this
    with each user's accumulated ``[(item_idx, rating), ...]`` history to
    produce replacement user-factor rows without touching the item side —
    the same normal equations one ALS half-step solves, restricted to the
    affected users and evaluated host-side (batches are small; a device
    round-trip or recompile would cost more than the solve).

    ``compute_dtype`` degrades the gathered item rows exactly like the
    training kernel's knob ("f32" | "bf16"; "f64" is the full-fidelity
    reference the publish gate compares against); the accumulation and
    solve always run in at least float32.

    Returns an (n_users, rank) float32 array ordered by sorted user index.
    """
    V = np.asarray(item_factors, dtype=np.float32)
    acc_dt = np.float64 if compute_dtype == "f64" else np.float32
    gather_dt = _fold_in_dtype(compute_dtype)
    eye = np.eye(rank, dtype=acc_dt)
    gram = None
    if implicit:
        Vg = V.astype(gather_dt).astype(acc_dt)
        gram = Vg.T @ Vg
    rows = np.zeros((len(interactions), rank), dtype=np.float32)
    for j, uidx in enumerate(sorted(interactions)):
        pairs = interactions[uidx]
        idx = np.array([i for i, _ in pairs], dtype=np.int64)
        r = np.array([x for _, x in pairs], dtype=acc_dt)
        Vu = V[idx].astype(gather_dt).astype(acc_dt)
        if implicit:
            # confidence c = 1 + alpha*r: A = VᵀV + Vuᵀ diag(alpha·r) Vu
            # + reg·I, b = Vuᵀ c  (Hu-Koren-Volinsky fold-in)
            A = gram + (Vu * (alpha * r)[:, None]).T @ Vu + reg * eye
            b = Vu.T @ (1.0 + alpha * r)
        else:
            # λ·n_u ridge, matching _solve_normal_equations' explicit path
            A = Vu.T @ Vu + (reg * len(pairs) + 1e-6) * eye
            b = Vu.T @ r
        rows[j] = np.linalg.solve(A, b).astype(np.float32)
    return rows


def _dense_half_step_local(
    *args, n_buckets, rank, reg, implicit, alpha, compute_dtype="f32",
    backend="reference", interpret=None,
):
    """Scatter-free half-step: per degree bucket, one gather + batched
    einsum accumulates the normal equations — contraction rides the MXU,
    padding slots multiply by zero, and because bucket rows ARE the local
    entity order the per-bucket results simply concatenate (no scatter).
    ``compute_dtype`` narrows the gathered side: bf16 factors gather and
    multiply in bfloat16 while the einsum accumulates f32
    (``preferred_element_type``), the MXU-native mode; int8 gathers the
    quantized rows + per-row scales and dequantizes before the multiply.
    ``backend="fused"`` replaces the per-bucket gather + einsum with ONE
    ``pallas_call`` (``ops/train_kernel.py``): the opposite factors sit
    VMEM-resident, the gather runs against VMEM (no sector
    amplification), and the contraction is the identical batched
    dot_general — the reference path below IS the kernel's math, operand
    order and all, so the two backends solve bit-identical factors.
    """
    from predictionio_tpu.ops import train_kernel as _train_kernel
    from predictionio_tpu.ops.quantize import quantize_factors_jax

    bufs = args[: 3 * n_buckets]
    opp_full, gram = args[3 * n_buckets], args[3 * n_buckets + 1]
    opp_q, opp_scale = quantize_factors_jax(opp_full, compute_dtype)
    f32 = jnp.float32
    opp = (
        opp_q if opp_scale is None else opp_q.astype(f32) * opp_scale
    )  # reference compute copy (f32 or bf16; int8 dequantized in XLA)
    As, bs, cnts = [], [], []
    for i in range(n_buckets):
        # shard_map blocks keep the leading mesh dim: (1, n_b, D_b) → [0]
        idx = bufs[3 * i][0]
        rat = bufs[3 * i + 1][0]
        msk = bufs[3 * i + 2][0]
        if backend == "fused":
            A, bv, cnt = _train_kernel.fused_train_normal_eq(
                idx, rat, msk, opp_q, opp_scale,
                implicit=implicit, alpha=alpha, interpret=interpret,
            )
            As.append(A)
            bs.append(bv)
            cnts.append(cnt)
            continue
        Vg = opp[idx]  # (n_b, D_b, k) gather in compute dtype
        w = msk.astype(Vg.dtype)
        prec = contraction_precision(Vg.dtype)
        if implicit:
            # A_u += Σ α·r · v vᵀ ;  b_u += Σ (1+α·r) · v   (p=1, c=1+αr)
            cw = (alpha * rat).astype(Vg.dtype) * w
            A = jnp.einsum(
                "edk,edl->ekl", Vg * cw[:, :, None], Vg,
                preferred_element_type=f32, precision=prec,
            )
            bv = jnp.einsum(
                "edk,ed->ek", Vg, (1.0 + alpha * rat).astype(Vg.dtype) * w,
                preferred_element_type=f32, precision=prec,
            )
            cnt = jnp.zeros(idx.shape[0], f32)
        else:
            W = Vg * w[:, :, None]
            A = jnp.einsum(
                "edk,edl->ekl", W, W,
                preferred_element_type=f32, precision=prec,
            )
            bv = jnp.einsum(
                "edk,ed->ek", W, rat.astype(Vg.dtype),
                preferred_element_type=f32, precision=prec,
            )
            cnt = msk.sum(-1)
        As.append(A)
        bs.append(bv)
        cnts.append(cnt)
    A = jnp.concatenate(As)
    b = jnp.concatenate(bs)
    cnt = jnp.concatenate(cnts)
    return _solve_normal_equations(A, b, cnt, gram, rank, reg, implicit)


def _resolve_side_backend(cfg: ALSConfig, n_opp: int) -> str:
    """The per-side training-kernel dispatch for a half-step that gathers
    ``n_opp`` opposite factor rows: the static rule of
    ``ops/train_kernel.resolve_backend`` (request, platform, VMEM budget in
    padded tiles).  An explicit ``fused`` that cannot fit raises there."""
    from predictionio_tpu.ops import train_kernel as _train_kernel

    return _train_kernel.resolve_backend(
        cfg.train_kernel, n_opp=n_opp, rank=cfg.rank,
        compute_dtype=cfg.compute_dtype,
    )


def _check_vma(backend: str) -> bool:
    """Whether shard_map's vma checker can stay on for a half-step.

    A ``pallas_call``'s out_shapes carry no vma, and the interpret-mode
    kernel (how CPU tests run it) trips the checker inside its own scan
    whatever the out_shapes say; JAX's message names ``check_vma=False``
    as the workaround.  The half-step has no collective for the checker to
    guard — the only cross-shard traffic is the all-gather XLA inserts for
    the replicated opposite factors — so the fused side runs unchecked.
    """
    return backend != "fused"


def _record_train_kernel_stats(
    cfg: ALSConfig, u_backend: str, v_backend: str,
    n_users_pad: int, n_items_pad: int,
) -> None:
    """Publish the resolved per-side dispatch to the train-kernel stats the
    /metrics bridge exports (``pio_train_kernel_*``): ``backend_u_solve``
    gathers item factors, ``backend_v_solve`` user factors; ``backend`` is
    their common value, or ``mixed``."""
    from predictionio_tpu.ops import train_kernel as _train_kernel

    _train_kernel.record_stats(
        backend=u_backend if u_backend == v_backend else "mixed",
        backend_u_solve=u_backend,
        backend_v_solve=v_backend,
        compute_dtype=cfg.compute_dtype,
        resident_bytes=_train_kernel.resident_bytes(
            max(n_users_pad, n_items_pad), cfg.rank, cfg.compute_dtype
        ),
    )


def _make_dense_step(mesh, ub: _DenseBlocks, ib: _DenseBlocks, cfg: ALSConfig):
    """Build the jitted full ALS iteration over the mesh (dense solver)."""
    rank, reg, alpha, implicit = cfg.rank, cfg.reg, cfg.alpha, cfg.implicit
    n_shards = mesh.shape[DATA_AXIS]
    n_users_pad = ub.per_shard * n_shards
    n_items_pad = ib.per_shard * n_shards

    def one_side(blocks: _DenseBlocks, backend: str):
        nb = len(blocks.widths)
        kernel = partial(
            _dense_half_step_local,
            n_buckets=nb,
            rank=rank,
            reg=reg,
            implicit=implicit,
            alpha=alpha,
            compute_dtype=cfg.compute_dtype,
            backend=backend,
        )
        specs = tuple(P(DATA_AXIS) for _ in range(3 * nb)) + (P(), P())
        return shard_map(
            kernel, mesh=mesh, in_specs=specs, out_specs=P(DATA_AXIS, None),
            check_vma=_check_vma(backend),
        )

    # u-solve gathers ITEM factors, v-solve gathers USER factors
    u_backend = _resolve_side_backend(cfg, n_items_pad)
    v_backend = _resolve_side_backend(cfg, n_users_pad)
    u_solve = one_side(ub, u_backend)
    v_solve = one_side(ib, v_backend)
    _record_train_kernel_stats(
        cfg, u_backend, v_backend, n_users_pad, n_items_pad
    )

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(U, V, u_bufs, i_bufs):
        zero_gram = jnp.zeros((rank, rank), jnp.float32)
        # the stable name a device trace finds the two half-steps' ops by
        with jax.named_scope("pio.als_half_step"):
            if implicit:
                # (k,k); XLA reduces across shards (psum on ICI)
                gram_v = jnp.matmul(V.T, V, precision=_F32_PRECISION)
                U = u_solve(*u_bufs, V, gram_v)
                gram_u = jnp.matmul(U.T, U, precision=_F32_PRECISION)
                V = v_solve(*i_bufs, U, gram_u)
            else:
                U = u_solve(*u_bufs, V, zero_gram)
                V = v_solve(*i_bufs, U, zero_gram)
        return U, V

    return step


def _make_step(mesh, ub: _Blocks, ib: _Blocks, cfg: ALSConfig):
    """Build the jitted full ALS iteration over the mesh."""
    rank, reg, alpha, implicit = cfg.rank, cfg.reg, cfg.alpha, cfg.implicit
    n_shards = mesh.shape[DATA_AXIS]
    n_users_pad = ub.per_shard * n_shards
    n_items_pad = ib.per_shard * n_shards

    def one_side(blocks: _Blocks, backend: str):
        kernel = partial(
            _half_step_local,
            per_shard=blocks.per_shard,
            rank=rank,
            reg=reg,
            implicit=implicit,
            alpha=alpha,
            compute_dtype=cfg.compute_dtype,
            backend=backend,
        )
        return shard_map(
            kernel,
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(), P()),
            out_specs=P(DATA_AXIS, None),
            check_vma=_check_vma(backend),
        )

    # u-solve gathers ITEM factors, v-solve gathers USER factors
    u_backend = _resolve_side_backend(cfg, n_items_pad)
    v_backend = _resolve_side_backend(cfg, n_users_pad)
    u_solve = one_side(ub, u_backend)
    v_solve = one_side(ib, v_backend)
    _record_train_kernel_stats(
        cfg, u_backend, v_backend, n_users_pad, n_items_pad
    )

    @partial(jax.jit, donate_argnums=(0, 1))
    def step(U, V, u_blocks, i_blocks):
        ul, uo, ur, um = u_blocks
        il, io, ir, im = i_blocks
        zero_gram = jnp.zeros((rank, rank), jnp.float32)
        if implicit:
            # (k,k); XLA reduces across shards (psum on ICI)
            gram_v = jnp.matmul(V.T, V, precision=_F32_PRECISION)
            U = u_solve(ul, uo, ur, um, V, gram_v)
            gram_u = jnp.matmul(U.T, U, precision=_F32_PRECISION)
            V = v_solve(il, io, ir, im, U, gram_u)
        else:
            U = u_solve(ul, uo, ur, um, V, zero_gram)
            V = v_solve(il, io, ir, im, U, zero_gram)
        return U, V

    return step


def _train_devprof(cfg: "ALSConfig", n_ratings: int, n_users: int,
                   n_items: int, n_devices: int):
    """Cost-annotate the process-global train accountant for this run.

    Returns ``(accountant, dispatch_key)``; each training step records
    its blocked wall against the analytic per-device iteration cost, so
    ``pio train`` exposes the same utilization families serving does
    (read via :func:`obs.devprof.train_snapshot`).
    """
    from predictionio_tpu.obs import devprof
    from predictionio_tpu.ops import train_kernel as _train_kernel

    acc = devprof.train_recorder(device_kind=jax.devices()[0].device_kind)
    # the step builder recorded the per-side decision just before this
    if _train_kernel.stats().get("backend") == "fused":
        # fused cost model: no gather amplification, V streamed once per
        # half-step at the compute dtype (obs/devprof.fused_train_cost)
        flops, nbytes = devprof.fused_train_cost(
            n_ratings, n_users, n_items, cfg.rank, cfg.compute_dtype
        )
    else:
        flops, nbytes = devprof.als_train_cost(
            n_ratings, n_users, n_items, cfg.rank, cfg.compute_dtype
        )
    _train_kernel.record_stats(
        intensity_flop_per_byte=(flops / nbytes) if nbytes else None
    )
    n = max(1, int(n_devices))
    key = f"als_iter_r{cfg.rank}"
    acc.set_cost(key, flops / n, nbytes / n, source="analytic")
    return acc, key


def _log_step_utilization(acc, it: int, total: int) -> None:
    snap = acc.snapshot()
    if not snap:
        return
    mfu = snap.get("mfu")
    logger.info(
        "als iter %d/%d utilization: busy=%.3f gflops=%.2f hbm_gbps=%.2f"
        " mfu=%s",
        it + 1, total, snap["busy_fraction"], snap["flops_per_s"] / 1e9,
        snap["hbm_gbps"], "n/a" if mfu is None else f"{mfu:.6f}",
    )


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def train_als(
    ctx: MeshContext, interactions, config: Optional[ALSConfig] = None
) -> ALSModel:
    """Train factors over the mesh; returns a host-form ALSModel.

    ``interactions`` is either a full :class:`Interactions` (every host
    holds all rows — the single-host path) or a
    :class:`~predictionio_tpu.parallel.ingest.ShardedInteractions` (each
    host read 1/N — the multi-host partitioned-ingest path).
    """
    from predictionio_tpu.parallel.ingest import ShardedInteractions

    if isinstance(interactions, ShardedInteractions):
        return _train_als_sharded(ctx, interactions, config or ALSConfig())
    cfg = config or ALSConfig()
    n_shards = ctx.axis_size(DATA_AXIS)
    n_users = interactions.n_users
    n_items = interactions.n_items
    n_users_pad = pad_to_multiple(n_users, n_shards)
    n_items_pad = pad_to_multiple(n_items, n_shards)

    user = interactions.user.astype(np.int64)
    item = interactions.item.astype(np.int64)
    rating = interactions.rating.astype(np.float32)

    dense = cfg.solver == "dense"
    if dense:
        ub, ib, u_perm, i_perm = _dense_blocks_for(
            interactions, cfg, n_shards
        )
    else:
        u_perm = i_perm = None
        if cfg.rebalance and n_shards > 1:
            u_perm = _balance_permutation(user, n_users_pad, n_shards)
            i_perm = _balance_permutation(item, n_items_pad, n_shards)
        user_blk = u_perm[user] if u_perm is not None else user
        item_blk = i_perm[item] if i_perm is not None else item
        ub = _make_blocks(user_blk, item_blk, rating, n_users_pad, n_shards)
        ib = _make_blocks(item_blk, user_blk, rating, n_items_pad, n_shards)

    key = jax.random.PRNGKey(cfg.seed)
    ku, kv = jax.random.split(key)
    scale = 1.0 / np.sqrt(cfg.rank)
    sharding = ctx.sharding(DATA_AXIS, None)

    def init_factors(k, n_pad, perm):
        # row e of the BASE draw belongs to ORIGINAL entity e; placing it at
        # blocked position perm[e] makes the effective per-entity init (and
        # thus the trained model) invariant to relabeling — solver/rebalance
        # choices change layout, never the optimization trajectory's start
        base = jax.random.normal(k, (n_pad, cfg.rank), jnp.float32) * scale
        if perm is not None:
            base = base[np.argsort(perm)]
        return jax.device_put(base, sharding)

    U = init_factors(ku, n_users_pad, u_perm)
    V = init_factors(kv, n_items_pad, i_perm)

    sh_rows = ctx.sharding(DATA_AXIS)

    def put(b: _Blocks):
        return tuple(
            jax.device_put(jnp.asarray(a), sh_rows)
            for a in (b.local, b.other, b.rating, b.mask)
        )

    def put_dense(b: _DenseBlocks):
        bufs = []
        for i in range(len(b.widths)):
            for a in (b.idx[i], b.rat[i], b.msk[i]):
                bufs.append(jax.device_put(jnp.asarray(a), sh_rows))
        return tuple(bufs)

    if dense:
        u_blocks, i_blocks = put_dense(ub), put_dense(ib)
        step = _make_dense_step(ctx.mesh, ub, ib, cfg)
    else:
        u_blocks, i_blocks = put(ub), put(ib)
        step = _make_step(ctx.mesh, ub, ib, cfg)

    start_iter = 0
    manager = None
    if cfg.checkpoint_dir:
        from predictionio_tpu.core.checkpoint import (
            CheckpointManager,
            dataset_digest,
            save_due,
            validate_interval,
        )

        validate_interval(cfg.checkpoint_interval)
        manager = CheckpointManager(cfg.checkpoint_dir)
        # fingerprint ties checkpoints to THIS config + dataset: a stale or
        # foreign checkpoint is ignored (fresh start), never silently loaded
        fingerprint = np.array(
            [
                n_users_pad,
                n_items_pad,
                len(rating),
                cfg.rank,
                int(cfg.implicit),
                cfg.seed,
                # order-sensitive: a permuted dataset with equal element
                # sums must NOT resume from a foreign checkpoint
                dataset_digest(user, item, rating),
                float(cfg.reg),
                float(cfg.alpha),
                # rebalance + solver + shard count determine the on-disk
                # row order of U/V (the permutation is a function of all
                # three — the dense solver relabels even when rebalance is
                # off): a checkpoint from any other layout must not resume
                int(cfg.rebalance),
                int(dense),
                n_shards,
            ],
            dtype=np.float64,
        )
        from predictionio_tpu.core.checkpoint import resume_from

        start_iter, state = resume_from(manager, fingerprint, cfg.iterations)
        if state is not None:
            U = jax.device_put(np.asarray(state["U"]), sharding)
            V = jax.device_put(np.asarray(state["V"]), sharding)

    # per-step utilization: the step is blocked to completion inside the
    # timing (steps are data-dependent, so there is no cross-step device
    # overlap to lose — the only cost is one dispatch round-trip per iter)
    util_acc, util_key = _train_devprof(
        cfg, len(rating), n_users, n_items, n_shards
    )
    if dense and os.environ.get("PIO_TRAIN_XLA_COST") == "1":
        # opt-in second compile: annotate the accountant with the
        # compiler's own cost of the ACTUAL optimized step (fused bytes
        # included), so MFU divides by what the hardware will really do
        try:
            ca = dense_step_cost_analysis(ctx, interactions, cfg)
            if ca.get("flops_per_iter_per_device"):
                util_acc.set_cost(
                    util_key,
                    ca["flops_per_iter_per_device"],
                    ca.get("bytes_per_iter_per_device"),
                    source="xla",
                )
        except Exception as e:  # cost annotation must never kill a train
            logger.warning("PIO_TRAIN_XLA_COST annotation failed: %s", e)
    for it in range(start_iter, cfg.iterations):
        t_step = time.perf_counter()
        U, V = step(U, V, u_blocks, i_blocks)
        # measured fence: the step wall feeds the utilization accountant;
        # steps are data-dependent, so no cross-step overlap is lost
        jax.block_until_ready(U)  # pio: ignore[hotpath-block-sync]
        util_acc.record(util_key, time.perf_counter() - t_step)
        _log_step_utilization(util_acc, it, cfg.iterations)
        if manager is not None and save_due(
            it + 1, cfg.checkpoint_interval, cfg.iterations
        ):
            # gather AND save on every process: both are collectives (the
            # orbax write barriers across hosts and writes once; gating it
            # to the coordinator deadlocks). The checkpoint_dir must be
            # shared across hosts (docs/operations.md multi-host section).
            state = {
                "U": device_get_global(U),
                "V": device_get_global(V),
                "fingerprint": fingerprint,
            }
            manager.save(it + 1, state)
    U_all = device_get_global(U)
    V_all = device_get_global(V)
    # factor row new_id belongs to old entity id o with perm[o] == new_id;
    # return in original id order so the model is permutation-invisible
    U_host = U_all[u_perm[:n_users]] if u_perm is not None else U_all[:n_users]
    V_host = V_all[i_perm[:n_items]] if i_perm is not None else V_all[:n_items]
    return _declare_ivf_partition(_declare_sharding_plan(ALSModel(
        user_factors=U_host,
        item_factors=V_host,
        user_map=interactions.user_map,
        item_map=interactions.item_map,
        config=cfg,
    )))


def _dense_blocks_for(interactions, cfg: ALSConfig, n_shards: int):
    """The single-host dense prep shared by :func:`train_als` and
    :func:`dense_step_cost_analysis` — ONE source of truth so the cost
    analysis always compiles the same program the trainer runs.

    Returns ``(ub, ib, u_perm, i_perm)``; the permutations are never None
    (dense bucketing needs per-shard-monotone degrees: LPT under
    rebalance, degree-sort otherwise).
    """
    n_users_pad = pad_to_multiple(interactions.n_users, n_shards)
    n_items_pad = pad_to_multiple(interactions.n_items, n_shards)
    user = interactions.user.astype(np.int64)
    item = interactions.item.astype(np.int64)
    rating = interactions.rating.astype(np.float32)
    if cfg.rebalance and n_shards > 1:
        u_perm = _balance_permutation(user, n_users_pad, n_shards)
        i_perm = _balance_permutation(item, n_items_pad, n_shards)
    else:
        u_perm = _degree_sort_permutation(user, n_users_pad, n_shards)
        i_perm = _degree_sort_permutation(item, n_items_pad, n_shards)
    ub = _make_dense_blocks(
        u_perm[user], i_perm[item], rating, n_users_pad, n_shards
    )
    ib = _make_dense_blocks(
        i_perm[item], u_perm[user], rating, n_items_pad, n_shards
    )
    return ub, ib, u_perm, i_perm


def dense_step_cost_analysis(
    ctx: MeshContext, interactions, config: Optional[ALSConfig] = None
) -> dict:
    """XLA's own cost analysis of ONE compiled dense ALS iteration.

    ``flops`` / ``bytes_accessed`` come from the compiler's model of the
    ACTUAL optimized per-device HLO — fusion, layout, and gather expansion
    applied — so a hand cost model's error (e.g. unforeseen gather sector
    amplification, ``docs/perf_roofline.md``) shows up as a divergence
    from these numbers instead of staying invisible. Block arrays are
    built on host for their SHAPES only; compilation uses abstract
    ``ShapeDtypeStruct`` args, so no factor matrices are materialized.
    """
    cfg = config or ALSConfig()
    if cfg.solver != "dense":
        raise ValueError("cost analysis models the dense solver")
    n_shards = ctx.axis_size(DATA_AXIS)
    n_users_pad = pad_to_multiple(interactions.n_users, n_shards)
    n_items_pad = pad_to_multiple(interactions.n_items, n_shards)
    ub, ib, _, _ = _dense_blocks_for(interactions, cfg, n_shards)
    step = _make_dense_step(ctx.mesh, ub, ib, cfg)
    rows_repl = ctx.sharding(DATA_AXIS, None)
    sh_rows = ctx.sharding(DATA_AXIS)

    def abstract(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def abstract_blocks(b: _DenseBlocks):
        out = []
        for i in range(len(b.widths)):
            for a in (b.idx[i], b.rat[i], b.msk[i]):
                out.append(abstract(a.shape, a.dtype, sh_rows))
        return tuple(out)

    lowered = step.lower(
        abstract((n_users_pad, cfg.rank), np.float32, rows_repl),
        abstract((n_items_pad, cfg.rank), np.float32, rows_repl),
        abstract_blocks(ub),
        abstract_blocks(ib),
    )
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    ca = ca or {}
    return {
        "flops_per_iter_per_device": ca.get("flops"),
        "bytes_per_iter_per_device": ca.get("bytes accessed"),
    }


def _sharded_blocks_for_host(sh, n_shards: int, pid: int, n_hosts: int):
    """ONE host's dense blocks + layout geometry under sharded ingest.

    Pure host-side function of the exchanged global tables — every host
    computes identical geometry (permutations, pads, bucket widths) and
    only the local block CONTENTS differ. Factored out of
    :func:`_train_als_sharded` so a single process can drive the
    multi-host blocking path for any virtual ``(pid, n_hosts)`` (the
    driver's ``dryrun_multichip`` concatenates per-host blocks instead of
    ``make_array_from_process_local_data``).

    Returns ``(user_blocks, item_blocks, u_geom, i_geom, shard_range)``
    with each geom ``(per_shard, n_pad, perm, deg_blocked)`` and
    ``shard_range`` the half-open device-shard interval this host's
    blocks (and factor rows) cover — the caller must place rows with the
    SAME range the blocks were built with.
    """
    from predictionio_tpu.data.storage.base import PEvents

    d_local = n_shards // n_hosts

    def side(id_map, counts):
        inv = id_map.inverse
        n = len(id_map)
        owner = np.fromiter(
            (PEvents.shard_hash(inv[i]) % n_hosts for i in range(n)),
            np.int64, count=n,
        )
        # capacity: the fullest host's entities must fit its d_local shards
        host_max = int(np.bincount(owner, minlength=n_hosts).max()) if n else 1
        per_shard = max(1, -(-host_max // d_local))
        n_pad = per_shard * n_shards
        perm = _sharded_balance_permutation(
            counts, owner, n_hosts, d_local, per_shard
        )
        deg = np.zeros(n_pad, np.int64)
        deg[perm[:n]] = counts
        return per_shard, n_pad, perm, deg.reshape(n_shards, per_shard)

    u_geom = side(sh.user_map, sh.user_counts)
    i_geom = side(sh.item_map, sh.item_counts)
    per_u, n_users_pad, u_perm, deg_u = u_geom
    per_i, n_items_pad, i_perm, deg_i = i_geom
    my = (pid * d_local, (pid + 1) * d_local)
    ub = _make_dense_blocks(
        u_perm[sh.user_rows.user.astype(np.int64)],
        i_perm[sh.user_rows.item.astype(np.int64)],
        sh.user_rows.rating.astype(np.float32),
        n_users_pad, n_shards, shard_range=my, deg_global=deg_u,
    )
    ib = _make_dense_blocks(
        i_perm[sh.item_rows.item.astype(np.int64)],
        u_perm[sh.item_rows.user.astype(np.int64)],
        sh.item_rows.rating.astype(np.float32),
        n_items_pad, n_shards, shard_range=my, deg_global=deg_i,
    )
    return ub, ib, u_geom, i_geom, my


def _train_als_sharded(ctx: MeshContext, sh, cfg: ALSConfig) -> ALSModel:
    """Multi-host partitioned-ingest training (SURVEY §7 "BiMap at scale").

    Each host arrives with 1/N of the rows (``parallel/ingest.py``: its own
    users' ratings + its own items' ratings, global ids, global degree
    vectors). All relabeling and bucket geometry derive deterministically
    from the exchanged global counts, so every host compiles the SAME
    program and only the data differs; the factor matrices assemble from
    process-local shards via ``jax.make_array_from_process_local_data``.
    The only cross-host data movement is the opposite-factor all-gather
    inside the step — XLA lays it on ICI/DCN (the Spark-shuffle role).
    """
    if cfg.solver != "dense":
        raise ValueError("sharded multi-host training requires solver='dense'")
    n_shards = ctx.axis_size(DATA_AXIS)
    n_hosts = sh.num_processes
    if n_shards % n_hosts:
        raise ValueError(
            f"{n_shards} device shards not divisible by {n_hosts} hosts"
        )
    pid = sh.process_index
    ub, ib, u_geom, i_geom, my = _sharded_blocks_for_host(
        sh, n_shards, pid, n_hosts
    )
    _, n_users_pad, u_perm, _ = u_geom
    _, n_items_pad, i_perm, _ = i_geom

    sh_rows = ctx.sharding(DATA_AXIS)
    sharding = ctx.sharding(DATA_AXIS, None)

    def put_local(b: _DenseBlocks):
        bufs = []
        for i in range(len(b.widths)):
            for a in (b.idx[i], b.rat[i], b.msk[i]):
                bufs.append(
                    jax.make_array_from_process_local_data(sh_rows, a)
                )
        return tuple(bufs)

    u_blocks, i_blocks = put_local(ub), put_local(ib)
    step = _make_dense_step(ctx.mesh, ub, ib, cfg)

    key = jax.random.PRNGKey(cfg.seed)
    ku, kv = jax.random.split(key)
    scale = 1.0 / np.sqrt(cfg.rank)

    def place_rows(full_blocked: np.ndarray):
        local = full_blocked[my[0] * full_blocked.shape[0] // n_shards
                             : my[1] * full_blocked.shape[0] // n_shards]
        return jax.make_array_from_process_local_data(sharding, local)

    def init_factors(k, n_entities, n_pad, perm):
        # drawn over ENTITIES only (not the padded layout) so the effective
        # init — and thus the trained model — is identical for any host
        # count / capacity; padding rows have no ratings, zeros are inert
        base_draw = np.zeros((n_pad, cfg.rank), np.float32)
        base_draw[:n_entities] = np.asarray(
            jax.random.normal(k, (n_entities, cfg.rank), jnp.float32) * scale
        )
        return place_rows(base_draw[np.argsort(perm)])

    U = init_factors(ku, sh.n_users, n_users_pad, u_perm)
    V = init_factors(kv, sh.n_items, n_items_pad, i_perm)

    start_iter = 0
    manager = None
    if cfg.checkpoint_dir:
        from predictionio_tpu.core.checkpoint import (
            CheckpointManager,
            dataset_digest,
            resume_from,
            save_due,
            validate_interval,
        )

        validate_interval(cfg.checkpoint_interval)
        manager = CheckpointManager(cfg.checkpoint_dir)
        # host-independent fingerprint: the global degree vectors stand in
        # for the raw triples (every host computes the same value)
        fingerprint = np.array(
            [
                n_users_pad, n_items_pad, int(sh.user_counts.sum()),
                cfg.rank, int(cfg.implicit), cfg.seed,
                # exchanged row digest (ingest.py): sensitive to pairings
                # and rating VALUES — equal degree histograms with
                # re-rated items must not resume each other's checkpoints
                float(sh.dataset_digest),
                dataset_digest(sh.user_counts, sh.item_counts),
                float(cfg.reg), float(cfg.alpha),
                2.0,  # layout tag: sharded-ingest dense blocking
                n_shards, n_hosts,
            ],
            dtype=np.float64,
        )
        start_iter, state = resume_from(manager, fingerprint, cfg.iterations)
        if state is not None:
            U = place_rows(np.asarray(state["U"]))
            V = place_rows(np.asarray(state["V"]))

    util_acc, util_key = _train_devprof(
        cfg, int(sh.user_counts.sum()), sh.n_users, sh.n_items, n_shards
    )
    for it in range(start_iter, cfg.iterations):
        t_step = time.perf_counter()
        U, V = step(U, V, u_blocks, i_blocks)
        # measured fence: the step wall feeds the utilization accountant;
        # steps are data-dependent, so no cross-step overlap is lost
        jax.block_until_ready(U)  # pio: ignore[hotpath-block-sync]
        util_acc.record(util_key, time.perf_counter() - t_step)
        _log_step_utilization(util_acc, it, cfg.iterations)
        if manager is not None:
            from predictionio_tpu.core.checkpoint import save_due

            if save_due(it + 1, cfg.checkpoint_interval, cfg.iterations):
                # every process gathers AND saves: both are collectives
                # (orbax's write barriers across hosts and writes once)
                state = {
                    "U": device_get_global(U),
                    "V": device_get_global(V),
                    "fingerprint": fingerprint,
                }
                manager.save(it + 1, state)
    U_all = device_get_global(U)
    V_all = device_get_global(V)
    from predictionio_tpu.parallel import distributed

    if sh.cleanup is not None and distributed.should_write_storage():
        # the final gather above is a collective: every host has finished
        # its exchange long ago, so the rendezvous blobs can go
        sh.cleanup()
    n_users, n_items = sh.n_users, sh.n_items
    return _declare_ivf_partition(_declare_sharding_plan(ALSModel(
        user_factors=U_all[u_perm[:n_users]],
        item_factors=V_all[i_perm[:n_items]],
        user_map=sh.user_map,
        item_map=sh.item_map,
        config=cfg,
    )))


def _declare_sharding_plan(model: ALSModel) -> ALSModel:
    """Publish-time sharding declaration (PIO_SHARD_* knobs; no-op unset).

    Weights for the popularity strategy default to the item-factor L2
    norms — the train-time proxy for expected traffic (implicit-ALS
    norms grow with interaction mass); a live deployment can rebalance
    from measured hot-set traffic via ``pio shards rebuild``.
    """
    from predictionio_tpu.serving import sharding as _sharding

    try:
        plan = _sharding.plan_from_env(
            model.item_factors.shape[0],
            weights=np.linalg.norm(model.item_factors, axis=1),
            bytes_per_item=float(model.item_factors.shape[1]) * 4.0,
        )
    except ValueError as e:
        logger.warning(
            "sharding plan declaration failed (%s); publishing unsharded", e
        )
        return model
    if plan is not None:
        model.sharding_plan = plan
        logger.info(
            "declared sharding plan %s: %d shards (%s)",
            plan.fingerprint, plan.n_shards, plan.strategy,
        )
    return model


def _declare_ivf_partition(model: ALSModel) -> ALSModel:
    """Publish-time IVF declaration (PIO_IVF_NLIST knob; no-op unset).

    Trains the k-means coarse partition over the item factors
    (``ops/ivf.py``) and attaches it to the model; the recall gate runs
    at publish (``CheckpointedALSModel._publish_ivf``), not here —
    training declares the intent, publish audits it.  Any declaration
    failure publishes exact-only with a warning: the approximate path is
    an optimization, never a point of failure.
    """
    from predictionio_tpu.ops import ivf as _ivf

    try:
        index = _ivf.index_from_env(model.item_factors)
    except ValueError as e:
        logger.warning(
            "IVF index declaration failed (%s); publishing exact-only", e
        )
        return model
    if index is not None:
        model.ivf_index = index
        logger.info(
            "declared IVF index %s: nlist=%d nprobe=%d",
            index.fingerprint, index.nlist, index.nprobe,
        )
    return model


class CheckpointedALSModel(ALSModel):
    """ALSModel persisted through the PersistentModel protocol via orbax.

    Parity: the reference's mode-2 persistence (``PersistentModel.save`` +
    manifest, ``controller/PersistentModel.scala``) — only a manifest naming
    this class goes into MODELDATA; the factor matrices live as an orbax
    checkpoint (sharded-array friendly), id maps beside it.  Deploy calls
    :meth:`load` to rebuild.
    """

    @staticmethod
    def _dir(instance_id: str) -> str:
        import os

        from predictionio_tpu.utils.fs import pio_base_dir

        base = pio_base_dir()
        return os.path.join(base, "persistent_models", instance_id)

    def save(self, instance_id: str, params) -> bool:
        import os
        import pickle

        from predictionio_tpu.core.checkpoint import save_pytree
        from predictionio_tpu.parallel import distributed

        d = self._dir(instance_id)
        os.makedirs(d, exist_ok=True)
        # collective: every process must reach this call (orbax barriers
        # across hosts and writes once); the plain pickle below is an
        # ordinary file write and stays coordinator-only
        save_pytree(
            os.path.join(d, "factors"),
            {"user_factors": self.user_factors, "item_factors": self.item_factors},
        )
        if distributed.should_write_storage():
            quant_meta = self._publish_quantized(d)
            shard_meta = self._publish_plan(d)
            ivf_meta = self._publish_ivf(d)
            with open(os.path.join(d, "maps.pkl"), "wb") as f:
                pickle.dump(
                    {"user_map": self.user_map, "item_map": self.item_map,
                     "config": self.config, "quant": quant_meta,
                     "sharding": shard_meta, "ivf": ivf_meta},
                    f,
                )
        return True  # manifest mode: MODELDATA stores only the class path

    def _publish_plan(self, d: str) -> dict:
        """Seal the declared ShardingPlan beside the factors (plan.blob).

        The manifest record carries the plan fingerprint so deploy can
        verify the blob it opens is the partition this model generation
        was published with — a rebalance that reseals plan.blob also
        rewrites the record, atomically per artifact.  No plan → record
        ``n_shards: 0`` and serving stays replicated.
        """
        import os

        from predictionio_tpu.serving import sharding as _sharding

        plan = getattr(self, "sharding_plan", None)
        if plan is None:
            return {"n_shards": 0}
        _sharding.save_plan(os.path.join(d, "plan.blob"), plan)
        logger.info(
            "sharding plan sealed: %d shards / %d host groups (%s), "
            "fingerprint %s",
            plan.n_shards, plan.host_groups, plan.strategy,
            plan.fingerprint,
        )
        return {
            "n_shards": plan.n_shards,
            "strategy": plan.strategy,
            "fingerprint": plan.fingerprint,
            "host_groups": plan.host_groups,
        }

    def _publish_quantized(self, d: str) -> dict:
        """Offline quantize step at model publish (PIO_QUANT_DTYPE).

        Produces the bf16/int8 factor variant, measures its top-k overlap
        against fp32 (:func:`core.evaluation.quantized_topk_overlap`), and
        only if the overlap clears ``PIO_QUANT_MIN_OVERLAP`` seals the
        variant through the persistence checksum envelope
        (``quant.blob``).  A refused variant leaves no blob — serving
        keeps the fp32 generation.  Returns the manifest record (always
        written, so the refusal and its measured overlap are auditable).
        """
        import os
        import pickle

        from predictionio_tpu.core import evaluation as _evaluation
        from predictionio_tpu.core import persistence as _persistence
        from predictionio_tpu.ops import quantize as _quantize

        dtype = (os.environ.get("PIO_QUANT_DTYPE") or "auto").strip().lower()
        if dtype in ("auto", "f32", ""):
            return {"dtype": "f32"}
        user_q, user_scale = _quantize.quantize_factors(
            self.user_factors, dtype
        )
        item_q, item_scale = _quantize.quantize_factors(
            self.item_factors, dtype
        )
        k = min(100, self.item_factors.shape[0])
        threshold = float(os.environ.get("PIO_QUANT_MIN_OVERLAP", "0.98"))
        sample = int(os.environ.get("PIO_QUANT_EVAL_USERS", "256") or 256)
        overlap = _evaluation.quantized_topk_overlap(
            self.user_factors, self.item_factors,
            user_q, user_scale, item_q, item_scale,
            k=k, sample=sample,
        )
        if overlap < threshold:
            logger.warning(
                "quantized publish REFUSED: %s top-%d overlap %.4f < %.4f "
                "(PIO_QUANT_MIN_OVERLAP); serving keeps fp32",
                dtype, k, overlap, threshold,
            )
            return {
                "dtype": "f32", "refused": dtype,
                "topk_overlap": overlap, "threshold": threshold, "k": k,
            }
        payload = pickle.dumps(
            {
                "dtype": dtype,
                "user_factors_q": user_q, "user_scale": user_scale,
                "item_factors_q": item_q, "item_scale": item_scale,
            },
            protocol=pickle.HIGHEST_PROTOCOL,
        )
        _persistence.seal_blob_file(os.path.join(d, "quant.blob"), payload)
        logger.info(
            "quantized publish: %s factors sealed (top-%d overlap %.4f >= "
            "%.4f)", dtype, k, overlap, threshold,
        )
        return {
            "dtype": dtype, "topk_overlap": overlap,
            "threshold": threshold, "k": k,
        }

    def _publish_ivf(self, d: str) -> dict:
        """Recall-gate and seal the IVF index at model publish (ivf.blob).

        Measures recall@10 of the IVF-pruned ranking vs the exact one
        (:func:`ops.ivf.measure_recall`, fp32 factors, b=1 probing) and
        only if it clears ``PIO_IVF_MIN_RECALL`` seals the index through
        the persistence checksum envelope — exactly the
        ``PIO_QUANT_MIN_OVERLAP`` contract for quantization.  A refused
        index leaves no blob and serving stays exact; the manifest record
        is always written, so the refusal and its measured recall are
        auditable.  Models built without :func:`train_als` (tests, bulk
        imports) can still declare via ``PIO_IVF_NLIST`` here.
        """
        import os

        from predictionio_tpu.ops import ivf as _ivf

        index = getattr(self, "ivf_index", None)
        if index is None:
            try:
                index = _ivf.index_from_env(self.item_factors)
            except ValueError as e:
                logger.warning(
                    "IVF index declaration failed (%s); publishing "
                    "exact-only", e,
                )
                return {"nlist": 0}
        if index is None:
            return {"nlist": 0}
        k = min(10, self.item_factors.shape[0])
        threshold = float(os.environ.get("PIO_IVF_MIN_RECALL", "0.95"))
        sample = int(os.environ.get("PIO_IVF_EVAL_USERS", "256") or 256)
        recall = _ivf.measure_recall(
            self.user_factors, self.item_factors, index,
            k=k, sample=sample,
        )
        if recall < threshold:
            logger.warning(
                "IVF publish REFUSED: recall@%d %.4f < %.4f "
                "(PIO_IVF_MIN_RECALL); serving stays exact",
                k, recall, threshold,
            )
            self.ivf_index = None
            return {
                "nlist": 0, "refused": index.nlist,
                "recall": recall, "threshold": threshold, "k": k,
            }
        index = dataclasses.replace(
            index, recall_at_publish=recall,
            recall_threshold=threshold, recall_k=k,
        )
        self.ivf_index = index
        _ivf.save_index(os.path.join(d, "ivf.blob"), index)
        logger.info(
            "IVF index sealed: nlist=%d nprobe=%d recall@%d %.4f >= %.4f, "
            "fingerprint %s",
            index.nlist, index.nprobe, k, recall, threshold,
            index.fingerprint,
        )
        return {
            "nlist": index.nlist, "nprobe": index.nprobe,
            "recall": recall, "threshold": threshold, "k": k,
            "fingerprint": index.fingerprint,
        }

    @classmethod
    def load(cls, instance_id: str, params, ctx) -> "CheckpointedALSModel":
        import os
        import pickle

        from predictionio_tpu.core.checkpoint import restore_pytree

        d = cls._dir(instance_id)
        factors = restore_pytree(os.path.join(d, "factors"))
        with open(os.path.join(d, "maps.pkl"), "rb") as f:
            meta = pickle.load(f)
        model = cls(
            user_factors=np.asarray(factors["user_factors"]),
            item_factors=np.asarray(factors["item_factors"]),
            user_map=meta["user_map"],
            item_map=meta["item_map"],
            config=meta["config"],
        )
        cls._load_quantized(model, d, meta.get("quant") or {})
        cls._load_plan(model, d, meta.get("sharding") or {})
        cls._load_ivf(model, d, meta.get("ivf") or {})
        return model

    @staticmethod
    def _load_ivf(model: "CheckpointedALSModel", d: str, rec: dict) -> None:
        """Attach the published IVF index, degrading on any damage.

        A torn/missing ivf.blob, a checksum mismatch, or a fingerprint
        that disagrees with the manifest all log a warning and leave
        ``ivf_index`` unset — the server cold-starts on the exact scan
        (``PIO_RETRIEVAL=auto`` resolves to exact; the deploy never
        fails).  ``PIO_RETRIEVAL=exact`` is the operator rollback: the
        sealed index is ignored even though present and valid.
        """
        import os
        import pickle

        from predictionio_tpu.core import persistence as _persistence
        from predictionio_tpu.ops import ivf as _ivf

        if not rec or not rec.get("nlist"):
            return
        want = (os.environ.get("PIO_RETRIEVAL") or "auto").strip().lower()
        if want == "exact":
            logger.info(
                "PIO_RETRIEVAL=exact: ignoring sealed IVF index; "
                "serving exact"
            )
            return
        try:
            index = _ivf.load_index(os.path.join(d, "ivf.blob"))
            want_fp = rec.get("fingerprint")
            if want_fp and index.fingerprint != want_fp:
                raise _persistence.ModelIntegrityError(
                    f"IVF fingerprint {index.fingerprint} != manifest "
                    f"{want_fp}"
                )
            model.ivf_index = index
            logger.info(
                "loaded IVF index %s: nlist=%d nprobe=%d (recall@%s %.4f "
                "at publish)",
                index.fingerprint, index.nlist, index.nprobe,
                rec.get("k"), rec.get("recall", -1.0),
            )
        except (
            _persistence.ModelIntegrityError, OSError, KeyError,
            pickle.UnpicklingError, EOFError, ValueError,
        ) as e:
            logger.warning(
                "IVF index unavailable (%s); serving exact", e
            )

    @staticmethod
    def _load_plan(model: "CheckpointedALSModel", d: str, rec: dict) -> None:
        """Attach the published ShardingPlan, degrading on any damage.

        A torn/missing plan.blob, a checksum mismatch, or a fingerprint
        that disagrees with the manifest all log a warning and leave
        ``sharding_plan`` unset — the server cold-starts replicated (the
        LKG machinery never sees a failure), because the plan is an
        optimization, never a single point of failure.
        """
        import os
        import pickle

        from predictionio_tpu.core import persistence as _persistence
        from predictionio_tpu.serving import sharding as _sharding

        if not rec or not rec.get("n_shards"):
            return
        try:
            plan = _sharding.load_plan(os.path.join(d, "plan.blob"))
            want = rec.get("fingerprint")
            if want and plan.fingerprint != want:
                raise _persistence.ModelIntegrityError(
                    f"plan fingerprint {plan.fingerprint} != manifest {want}"
                )
            model.sharding_plan = plan
            logger.info(
                "loaded sharding plan %s: %d shards (%s)",
                plan.fingerprint, plan.n_shards, plan.strategy,
            )
        except (
            _persistence.ModelIntegrityError, OSError, KeyError,
            pickle.UnpicklingError, EOFError, ValueError,
        ) as e:
            logger.warning(
                "sharding plan unavailable (%s); serving replicated", e
            )

    @staticmethod
    def _load_quantized(model: "CheckpointedALSModel", d: str, quant: dict):
        """Attach the published quantized variant, if any and wanted.

        ``PIO_QUANT_DTYPE`` at deploy: ``auto`` (default) serves whatever
        dtype the manifest recorded; ``f32`` is the rollback switch —
        ignore the variant and serve fp32; an explicit ``bf16``/``int8``
        must match the artifact or fp32 is served with a warning.  Any
        failure to open the sealed blob (missing file, checksum mismatch
        → :class:`ModelIntegrityError`) degrades to fp32 — the quantized
        variant is an optimization, never a single point of failure.
        """
        import os
        import pickle

        from predictionio_tpu.core import persistence as _persistence

        recorded = quant.get("dtype", "f32")
        want = (os.environ.get("PIO_QUANT_DTYPE") or "auto").strip().lower()
        effective = recorded if want in ("auto", "") else want
        if effective in ("f32",) or recorded == "f32":
            if want in ("bf16", "int8") and recorded != want:
                logger.warning(
                    "PIO_QUANT_DTYPE=%s but artifact records %s; serving "
                    "fp32", want, recorded,
                )
            return
        if effective != recorded:
            logger.warning(
                "PIO_QUANT_DTYPE=%s but artifact records %s; serving fp32",
                want, recorded,
            )
            return
        try:
            payload = pickle.loads(
                _persistence.open_blob_file(os.path.join(d, "quant.blob"))
            )
            model.factor_dtype = payload["dtype"]
            model.user_factors_q = payload["user_factors_q"]
            model.user_scale = payload["user_scale"]
            model.item_factors_q = payload["item_factors_q"]
            model.item_scale = payload["item_scale"]
            logger.info(
                "loaded %s quantized factors (top-k overlap %.4f at "
                "publish)", payload["dtype"], quant.get("topk_overlap", -1.0),
            )
        except (
            _persistence.ModelIntegrityError, OSError, KeyError,
            pickle.UnpicklingError, EOFError,
        ) as e:
            logger.warning(
                "quantized factors unavailable (%s); serving fp32", e
            )


# PersistentModel registration: dataclass inheritance keeps ALSModel's fields;
# isinstance checks in core/persistence.py look for the protocol
from predictionio_tpu.core.persistence import PersistentModel  # noqa: E402

PersistentModel.register(CheckpointedALSModel)


class ALSScorer:
    """Serving-side top-N ranking with factors resident on device.

    Parity role: ``ALSModel.recommendProductsWithFilter``
    (``examples/scala-parallel-recommendation/blacklist-items/.../ALSModel.scala``)
    — but the score+filter+top-k runs as one jitted program, factors stay in
    HBM between queries, and exclusion/candidate sets travel as small INDEX
    arrays (padded to a few fixed bucket widths), scattered into the score
    mask on device.  A dense per-query (n_items,) host mask would cost MBs
    of upload per query at million-item catalogs over links with a fixed
    readback floor; seen-sets/blacklists are typically hundreds of ids.
    """

    # Below this factor-matrix size, score on host: a few-μs numpy matvec
    # beats a device round trip for single queries (the reference's local
    # P2L models serve on the driver for the same reason).
    HOST_THRESHOLD = 2_000_000  # item_factors elements

    # Filter index arrays are padded up to these widths so jit compiles a
    # handful of variants, not one per distinct set size. Sets larger than
    # the top bucket (rare: a user who has seen >32k items) fall back to
    # the host path.
    FILTER_BUCKETS = (0, 64, 512, 4096, 32768)

    # guards lazy _score_batch creation: concurrent eval/serving threads
    # racing the check-then-set would each trace+compile their own copy
    _batch_init_lock = threading.Lock()

    def __init__(
        self,
        ctx: MeshContext,
        model: ALSModel,
        max_k: int = 100,
        on_device: Optional[bool] = None,
    ):
        self.ctx = ctx
        self.model = model
        self.n_items = model.item_factors.shape[0]
        self._n_items_pad = pad_to_multiple(self.n_items, 8)
        self.max_k = max_k
        if on_device is None:
            on_device = model.item_factors.size >= self.HOST_THRESHOLD
        self.on_device = on_device
        if on_device:
            pad_i = self._n_items_pad - self.n_items
            V = np.pad(model.item_factors, ((0, pad_i), (0, 0)))
            self._V = ctx.replicate(V)
            self._U = ctx.replicate(model.user_factors)
            self._pad_mask = ctx.replicate(
                np.arange(self._n_items_pad) >= self.n_items
            )

            # Compiled ONCE at a fixed k (per-query num is sliced on host):
            # a static per-query k would recompile for every distinct num.
            # All arrays enter as ARGUMENTS: a closure-captured device array
            # is baked into the program as a constant, an argument is
            # dispatched by reference.
            self._k = min(max_k, self.n_items)

            @jax.jit
            def _score(U, V, pad_mask, u_idx, exclude_idx, candidate_idx,
                       use_candidates):
                # (rank,) @ (pad, rank)ᵀ → (pad,)
                scores = jnp.matmul(
                    U[u_idx], V.T, precision=_F32_PRECISION
                )
                # index buckets are padded with n_items_pad (out of range):
                # mode="drop" makes the padding a no-op scatter
                excl = jnp.zeros_like(pad_mask).at[exclude_idx].set(
                    True, mode="drop"
                )
                keep = jnp.zeros_like(pad_mask).at[candidate_idx].set(
                    True, mode="drop"
                )
                cand_excl = jnp.logical_and(~keep, use_candidates)
                scores = jnp.where(pad_mask | excl | cand_excl, -1e30, scores)
                return jax.lax.top_k(scores, self._k)

            self._score = _score

    def enable_fastpath(self, max_k: Optional[int] = None):
        """AOT-compile the bucketed serving fast path (deploy/reload time).

        Builds a :class:`~predictionio_tpu.serving.fastpath.BucketedScorer`
        over this model's factors — every bucket rung compiled up front, so
        no live request ever traces or compiles.  Idempotent and
        thread-safe; built even when ``on_device`` is False (the batched
        serve path amortizes the device round trip that makes single
        queries prefer host).
        """
        fp = getattr(self, "_fastpath", None)
        if fp is None:
            with self._batch_init_lock:
                fp = getattr(self, "_fastpath", None)
                if fp is None:
                    from predictionio_tpu.serving.fastpath import BucketedScorer

                    m = self.model
                    dtype = getattr(m, "factor_dtype", "f32")
                    # publish-time ShardingPlan (if declared) selects the
                    # sharded factor placement per PIO_SERVING_SHARDING;
                    # a published IVF index likewise selects the pruned
                    # retrieval path per PIO_RETRIEVAL
                    plan = getattr(m, "sharding_plan", None)
                    ivf_index = getattr(m, "ivf_index", None)
                    if dtype != "f32" and m.user_factors_q is not None:
                        # published quantized variant: device-resident
                        # narrow factors, dequantized in-kernel
                        fp = BucketedScorer(
                            self.ctx,
                            m.user_factors_q,
                            m.item_factors_q,
                            max_k=max_k or self.max_k,
                            factor_dtype=dtype,
                            user_scale=m.user_scale,
                            item_scale=m.item_scale,
                            plan=plan,
                            ivf_index=ivf_index,
                        )
                    else:
                        fp = BucketedScorer(
                            self.ctx,
                            m.user_factors,
                            m.item_factors,
                            max_k=max_k or self.max_k,
                            plan=plan,
                            ivf_index=ivf_index,
                        )
                    self._fastpath = fp
        return fp

    def fastpath_stats(self) -> Optional[dict]:
        fp = getattr(self, "_fastpath", None)
        return fp.stats() if fp is not None else None

    def recommend_batch(
        self, user_indices: np.ndarray, num: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Unfiltered top-num for MANY users in one pass.

        The evaluation hot loop (MetricEvaluator batch predict) scores
        thousands of queries; one (B, rank)×(rank, n_items) matmul + top-k
        replaces B scalar calls.  Returns (idx (B, k), scores (B, k)).
        """
        users = np.asarray(user_indices, np.int64)
        k = min(max(num, 1), self.n_items)
        fp = getattr(self, "_fastpath", None)
        if fp is not None and k <= fp.k:
            idx, vals = fp.score_topk(users, k)
            return idx, vals
        if self.on_device and k <= self._k:
            if not hasattr(self, "_score_batch"):
                with self._batch_init_lock:
                    if not hasattr(self, "_score_batch"):

                        # lazy one-time compile, double-checked under
                        # _batch_init_lock: only the first query pays it
                        @jax.jit
                        # pio: ignore[hotpath-jit-in-request]
                        def _score_batch(U, V, pad_mask, u_idx):
                            scores = jnp.matmul(  # (B, pad)
                                U[u_idx], V.T, precision=_F32_PRECISION
                            )
                            scores = jnp.where(
                                pad_mask[None, :], -1e30, scores
                            )
                            return jax.lax.top_k(scores, self._k)

                        self._score_batch = _score_batch
            vals, idx = self._score_batch(
                self._U, self._V, self._pad_mask, jnp.asarray(users)
            )
            return np.asarray(idx)[:, :k], np.asarray(vals)[:, :k]
        m = self.model
        scores = m.user_factors[users] @ m.item_factors.T  # (B, n_items)
        idx = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        row_scores = np.take_along_axis(scores, idx, axis=1)
        order = np.argsort(-row_scores, axis=1)
        idx = np.take_along_axis(idx, order, axis=1)
        return idx, np.take_along_axis(row_scores, order, axis=1)

    def _bucketed(self, items: Optional[np.ndarray]) -> Optional[np.ndarray]:
        """Index set → sentinel-padded bucket array, or None if oversized."""
        idx = (
            np.asarray(items, np.int64)
            if items is not None else np.empty(0, np.int64)
        )
        for width in self.FILTER_BUCKETS:
            if len(idx) <= width:
                out = np.full(width, self._n_items_pad, np.int64)
                out[: len(idx)] = idx
                return out
        return None

    def recommend(
        self,
        user_idx: int,
        num: int,
        exclude_items: Optional[np.ndarray] = None,
        candidate_items: Optional[np.ndarray] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(item_indices, scores) of the top ``num`` items for one user."""
        k = min(max(num, 1), self.n_items)
        excl_bucket = self._bucketed(exclude_items)
        cand_bucket = self._bucketed(candidate_items)
        # num beyond the compiled top-k width serves exactly from host
        # rather than silently truncating to max_k; oversized filter sets
        # (bucket overflow) also drop to host instead of a dense upload
        if (
            self.on_device and k <= self._k
            and excl_bucket is not None and cand_bucket is not None
        ):
            vals, idx = self._score(
                self._U, self._V, self._pad_mask, user_idx,
                jnp.asarray(excl_bucket), jnp.asarray(cand_bucket),
                jnp.asarray(candidate_items is not None),
            )
            vals, idx = np.asarray(vals)[:k], np.asarray(idx)[:k]
        elif candidate_items is not None:
            # candidate path on host: gather only the candidate rows and
            # rank those — a pipeline retrieval stage hands us a few
            # hundred ids, and a full-catalog matvec + dense mask would
            # throw the candidate pruning away
            cand = np.asarray(candidate_items, np.int64)
            if exclude_items is not None and len(exclude_items):
                cand = cand[~np.isin(cand, np.asarray(exclude_items, np.int64))]
            m = self.model
            if len(cand) == 0:
                return np.zeros(0, np.int64), np.zeros(0, np.float32)
            sub = m.item_factors[cand] @ m.user_factors[user_idx]
            kk = min(k, len(cand))
            pick = np.argpartition(-sub, kk - 1)[:kk]
            order = np.argsort(-sub[pick])
            pick = pick[order]
            idx = cand[pick]
            vals = sub[pick]
        else:
            mask = np.zeros(self._n_items_pad, bool)
            if exclude_items is not None and len(exclude_items):
                mask[np.asarray(exclude_items, np.int64)] = True
            m = self.model
            scores = m.user_factors[user_idx] @ m.item_factors.T
            scores = np.where(mask[: self.n_items], -1e30, scores)
            idx = np.argpartition(-scores, k - 1)[:k]
            order = np.argsort(-scores[idx])
            idx = idx[order]
            vals = scores[idx]
        real = vals > -1e29
        return idx[real][:num], vals[real][:num]


def rmse(model: ALSModel, interactions: Interactions) -> float:
    """Host-side reconstruction error (test/benchmark helper)."""
    pred = np.einsum(
        "nk,nk->n",
        model.user_factors[interactions.user],
        model.item_factors[interactions.item],
    )
    return float(np.sqrt(np.mean((pred - interactions.rating) ** 2)))
