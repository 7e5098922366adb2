"""Fused Pallas gather→dot→top-k scoring kernel for the serving fast path.

The XLA reference path (``ops/topk.py``) runs gather, dot, and top-k as
separate ops with the (B, n_items) score matrix round-tripping through HBM
between stages; ``docs/perf_roofline.md`` measures that round trip (plus
the ~sector amplification on the row gather) as the reason serving MFU is
effectively nil.  This kernel fuses all three stages on-chip:

* the (B,) user rows are gathered once per dispatch (by XLA, ahead of the
  kernel — the full user matrix never leaves HBM) and sit VMEM-resident
  for the whole item sweep;
* the item-factor matrix streams through VMEM in ``BLOCK_I``-row blocks
  (1-D grid, like the K sweep in ``ops/flash_attention.py``) and is dotted
  against the resident gathered rows on the MXU;
* a masked running top-k accumulator — (B, k) values + global indices —
  lives in VMEM scratch across the whole sweep, so the score matrix is
  never materialized anywhere.

Mosaic has no ``top_k``/``sort`` lowering, so the merge is built from
reductions and selects only: per block, candidates that beat the current
per-row k-th value are extracted one max at a time (smallest global index
first on ties — ``lax.top_k``'s tie order) and inserted into the sorted
accumulator by compare/shift.  One PASS serves every row of the batch at
once (each row places at most one candidate), and the loop over passes
ends at the first one that finds no candidate in any row: tile,
thresholds and accumulator can no longer change, so that is exact.  Two
costs follow, and they are different things:

* inserts: a row places ~k·(ln(n_blocks) + 0.58) entries over a sweep of
  iid scores — O(k·log(n_items/k)), not O(k·n_blocks) — because after the
  first few blocks the per-row thresholds are high;
* trips (each a load of the tile, a compare and a vector→scalar reduce,
  ~0.25 µs on a v5e): a block costs its inserting passes — as many as its
  busiest row has inserts — plus one ending check, so a block that places
  nothing costs exactly one.  At 11,133 blocks of 512, k = 100, on the
  chip: 1,008 / 3,466 / 5,073 / 7,265 / 9,808 passes a dispatch at rungs
  1 / 8 / 16 / 32 / 64, in 551 … 7,879 of the blocks
  (``tools/chip_probes/results/score_sweep.*.json``); a fixed ``k`` trips
  a block, which this loop replaced, were 1,113,300 whatever the rung.
  The worst input (scores ascending in item order) places min(k, block)
  entries in every block: the fixed loop's cost, never more.

``with_stats`` returns the two counters (passes that inserted, blocks
that merged anything) so a deployment can see the exit engage
(``BucketedScorer.stats()``: ``merge_passes`` / ``merge_blocks``).

Quantized factors (``ops/quantize.py``) dequantize IN the kernel: bf16 /
int8 blocks upcast in VMEM after the HBM stream, so the bandwidth win is
real — int8 streams a quarter of the f32 bytes plus one f32 scale per row.

Following the in-repo Pallas idiom (``ops/flash_attention.py``), the
identical kernel runs anywhere via ``interpret=``, defaulting to interpret
mode off-TPU so the CPU test mesh exercises the same code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode
from predictionio_tpu.ops.quantize import contraction_precision

NEG_INF = -1e30  # plain float: jnp constants would be captured as operands
_IDX_SENTINEL = 2**31 - 1

# Item rows streamed per grid step: 4 lane-width multiples deep — one f32
# block is 512×rank×4 B (≤ 512 KB at rank 256), far under VMEM, and the
# (B, 512) score tile stays register/VMEM friendly at every bucket rung.
BLOCK_I = 512


def use_fused_default() -> bool:
    """The one gate policy for 'should scoring take the Pallas path': TPU
    only — interpret-mode fused loses on CPU, so ``auto`` dispatch
    (``ops/topk.py``) must never silently pick it there.  Mirrors
    ``flash_attention.use_flash_default``."""
    return jax.default_backend() == "tpu"


def pad_block_items(n_items: int) -> int:
    """Item-dimension padding the fused kernel needs: one whole block when
    the catalog fits a single block, else a ``BLOCK_I`` multiple."""
    base = -(-n_items // 8) * 8  # sublane multiple, matches the XLA path
    if base <= BLOCK_I:
        return base
    return -(-n_items // BLOCK_I) * BLOCK_I


def _merge_block(s, gidx, s_ref, vals_ref, idxs_ref, *, k: int, batch: int):
    """Fold one (B, block_i) score tile into the running (B, k) top-k.

    Threshold-gated max extraction: each pass pulls at most one candidate
    per row (the remaining max, smallest global index on ties) and inserts
    it into the sorted-descending accumulator by compare/shift — no sort,
    no gather, so every op here has a Mosaic lowering.  The loop ends at
    the first pass that finds no candidate in any row: nothing it reads
    can change after that, so every later pass would be the same no-op.
    Returns the number of passes that inserted (a traced int32 scalar).
    """
    s_ref[...] = s
    col = jax.lax.broadcasted_iota(jnp.int32, (batch, k), 1)

    def extract(carry):
        n_inserted, _ = carry
        sv = s_ref[...]
        rv = vals_ref[...]
        thresh = rv[:, k - 1]
        beat = sv > thresh[:, None]
        found = jnp.any(beat)

        @pl.when(found)
        def _insert():
            m = jnp.max(jnp.where(beat, sv, NEG_INF), axis=1)  # (B,)
            hit = beat & (sv == m[:, None])
            gsel = jnp.min(
                jnp.where(hit, gidx, jnp.int32(_IDX_SENTINEL)), axis=1
            )
            valid = m > thresh  # rows that actually found a candidate
            ri = idxs_ref[...]
            # insertion point AFTER equal incumbents: earlier blocks have
            # smaller global indices, and lax.top_k orders ties that way
            pos = jnp.sum((rv >= m[:, None]).astype(jnp.int32), axis=1)
            if k == 1:  # nothing to shift, and Mosaic has no (B, 0) vector
                sh_v, sh_i = rv, ri
            else:
                sh_v = jnp.concatenate([rv[:, :1], rv[:, :-1]], axis=1)
                sh_i = jnp.concatenate([ri[:, :1], ri[:, :-1]], axis=1)
            nv = jnp.where(
                col < pos[:, None], rv,
                jnp.where(col == pos[:, None], m[:, None], sh_v),
            )
            ni = jnp.where(
                col < pos[:, None], ri,
                jnp.where(col == pos[:, None], gsel[:, None], sh_i),
            )
            vals_ref[...] = jnp.where(valid[:, None], nv, rv)
            idxs_ref[...] = jnp.where(valid[:, None], ni, ri)
            # retire the selected entry so the next pass sees the rest
            s_ref[...] = jnp.where(
                hit & (gidx == gsel[:, None]) & valid[:, None], NEG_INF, sv
            )

        return n_inserted + found.astype(jnp.int32), found

    # a row places at most min(k, block) entries of one block (they come
    # out largest first, so the k-th lifts the threshold past the rest):
    # the bound on the count never cuts an insert off
    n_inserted, _ = jax.lax.while_loop(
        lambda carry: (carry[0] < k) & carry[1],
        extract,
        (jnp.int32(0), jnp.bool_(True)),
    )
    return n_inserted


def _score_topk_kernel(
    *refs, k: int, block_i: int, batch: int,
    has_uscale: bool, has_vscale: bool, with_stats: bool,
):
    """One grid step: dot the resident user rows against this item block,
    merge into the running top-k, emit on the last block."""
    it = iter(refs)
    ug_ref = next(it)
    us_ref = next(it) if has_uscale else None
    v_ref = next(it)
    vs_ref = next(it) if has_vscale else None
    mask_ref = next(it)
    vals_out = next(it)
    idx_out = next(it)
    stats_out = next(it) if with_stats else None
    s_ref = next(it)
    vals_ref = next(it)
    idxs_ref = next(it)

    ii = pl.program_id(0)
    n_i = pl.num_programs(0)

    @pl.when(ii == 0)
    def _init():
        vals_ref[...] = jnp.full_like(vals_ref, NEG_INF)
        idxs_ref[...] = jnp.full_like(idxs_ref, jnp.int32(_IDX_SENTINEL))
        if with_stats:
            stats_out[0] = 0
            stats_out[1] = 0

    # dequantize in VMEM: HBM only ever streamed the narrow bytes
    ug = ug_ref[...].astype(jnp.float32)
    if has_uscale:
        ug = ug * us_ref[...]  # (B, rank) * (B, 1)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        ug, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=contraction_precision(v_ref.dtype),
    )  # (B, block_i) on the MXU
    if has_vscale:
        s = s * vs_ref[...]  # per-item scale, a (1, block_i) lane row
    s = jnp.where(mask_ref[...] != 0, NEG_INF, s)
    gidx = ii * block_i + jax.lax.broadcasted_iota(
        jnp.int32, (batch, block_i), 1
    )
    n_inserted = _merge_block(
        s, gidx, s_ref, vals_ref, idxs_ref, k=k, batch=batch
    )
    if with_stats:
        # the whole (2,) output lives in SMEM for the sweep: it is its own
        # accumulator, written back once when the grid ends
        stats_out[0] += n_inserted
        stats_out[1] += (n_inserted > 0).astype(jnp.int32)

    @pl.when(ii == n_i - 1)
    def _finalize():
        vals_out[...] = vals_ref[...]
        idx_out[...] = idxs_ref[...]


def fused_gather_score_topk(
    U: jax.Array,
    V: jax.Array,
    u_idx: jax.Array,
    k: int,
    item_mask: Optional[jax.Array] = None,
    *,
    u_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    block_items: Optional[int] = None,
    with_stats: bool = False,
):
    """Fused top-k scores: ``(values (B, k), indices (B, k))``.

    ``U``/``V`` may be f32, bf16, or int8 (int8 requires the matching
    per-row ``u_scale``/``v_scale`` from :mod:`ops.quantize`); the kernel
    upcasts after the HBM stream.  ``item_mask`` is True for EXCLUDED
    items.  ``interpret`` defaults to True off-TPU so tests run the kernel
    anywhere; masked/padded slots can never win (NEG_INF before merge).
    Callers wanting zero-copy dispatch should pre-pad the item dimension
    to :func:`pad_block_items`; ragged inputs are padded (and the tail
    masked) here.  ``with_stats`` appends a third output, int32 ``(2,)``:
    the merge passes that inserted, summed over the sweep, and the blocks
    that merged anything (what the merge cost: module docstring).
    """
    interpret = pallas_mode.resolve("score_topk", interpret)
    n_items, rank = V.shape
    batch = u_idx.shape[0]
    if not 0 < k <= n_items:
        raise ValueError(f"k={k} out of range for {n_items} items")
    n_pad = pad_block_items(n_items)
    block_i = min(block_items or BLOCK_I, n_pad)
    if n_pad % block_i:
        raise ValueError(f"block_items={block_i} must divide {n_pad}")
    excl = (
        item_mask if item_mask is not None
        else jnp.zeros((n_items,), jnp.bool_)
    )
    pad_i = n_pad - n_items
    if pad_i:
        V = jnp.pad(V, ((0, pad_i), (0, 0)))
        excl = jnp.pad(excl, (0, pad_i), constant_values=True)
        if v_scale is not None:
            v_scale = jnp.pad(v_scale, ((0, pad_i), (0, 0)))
    # per-item operands ride as (1, n_pad) lane rows so each block already
    # has the score tile's layout: Mosaic lowers neither a 1-D int8 block
    # nor the (block_i, 1) -> (1, block_i) relayout of a scale column
    mask_row = excl.astype(jnp.int32).reshape(1, n_pad)

    has_us = u_scale is not None
    has_vs = v_scale is not None
    kernel = functools.partial(
        _score_topk_kernel,
        k=k, block_i=block_i, batch=batch,
        has_uscale=has_us, has_vscale=has_vs, with_stats=with_stats,
    )

    def _pinned(ii):
        return (0, 0)

    # the (B,) embedding rows are gathered by XLA ahead of the kernel — B
    # narrow rows, once per dispatch — and stay VMEM-resident for the whole
    # item sweep; the full user matrix never leaves HBM.  (An in-kernel
    # per-row DMA cannot slice a rank-wide row out of a 128-lane tile.)
    u_idx = u_idx.astype(jnp.int32)
    in_specs = [pl.BlockSpec((batch, rank), _pinned)]
    operands = [U[u_idx]]
    if has_us:
        in_specs.append(pl.BlockSpec((batch, 1), _pinned))
        operands.append(u_scale.astype(jnp.float32)[u_idx])
    in_specs.append(pl.BlockSpec((block_i, rank), lambda ii: (ii, 0)))
    operands.append(V)
    if has_vs:
        in_specs.append(pl.BlockSpec((1, block_i), lambda ii: (0, ii)))
        operands.append(v_scale.astype(jnp.float32).reshape(1, n_pad))
    in_specs.append(pl.BlockSpec((1, block_i), lambda ii: (0, ii)))
    operands.append(mask_row)

    out_specs = [pl.BlockSpec((batch, k), _pinned),
                 pl.BlockSpec((batch, k), _pinned)]
    out_shape = [
        jax.ShapeDtypeStruct((batch, k), jnp.float32),
        jax.ShapeDtypeStruct((batch, k), jnp.int32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((2,), jnp.int32))
    return tuple(pl.pallas_call(
        kernel,
        grid=(n_pad // block_i,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((batch, block_i), jnp.float32),  # live score tile
            pltpu.VMEM((batch, k), jnp.float32),  # running top-k values
            pltpu.VMEM((batch, k), jnp.int32),  # running global indices
        ],
        interpret=interpret,
    )(*operands))
