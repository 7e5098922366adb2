"""Fused Pallas gather→dot→top-k scoring kernel for the serving fast path.

The XLA reference path (``ops/topk.py``) runs gather, dot, and top-k as
separate ops with the (B, n_items) score matrix round-tripping through HBM
between stages; ``docs/perf_roofline.md`` measures that round trip (plus
the ~sector amplification on the row gather) as the reason serving MFU is
effectively nil.  This kernel fuses all three stages on-chip:

* the (B,) user rows are gathered once per dispatch (by XLA, ahead of the
  kernel — the full user matrix never leaves HBM) and sit VMEM-resident
  for the whole item sweep;
* the item-factor matrix streams through VMEM in blocks of item rows
  (1-D grid, like the K sweep in ``ops/flash_attention.py``) and is dotted
  against the resident gathered rows on the MXU;
* a masked running top-k accumulator — (B, k) values + global indices —
  lives in VMEM scratch across the whole sweep, so the score matrix is
  never materialized anywhere.

The tile of one grid step — rows x item block — is sized from the shapes
the program is compiled for (:func:`tile_geometry`), not fixed:

* the block holds ``BLOCK_BYTES`` (2 MB) of V: a grid step costs ~0.7 µs
  whatever it moves, so at 128 x f32 blocks of 512 rows were 11,133 steps
  and 8 ms of a 9.2 ms dispatch where HBM needs 3.6; blocks of 4,096 are
  1,392 steps and 4.3 ms (v5e, ``tools/chip_probes/results/
  score_sweep.pr30.*.json``).  At 2,048 x bf16 (the sequence head) 512
  rows already are 2 MB.  More rows or a narrow dtype's f32 upcast halve
  the block until the step's live tiles fit ``VMEM_TILE_BUDGET``, well
  under the scoped VMEM a kernel gets by default;
* rows come in whole sublane tiles (``ROW_TILE`` 8): a (1, block) tile is
  a shape Mosaic handles badly — at a 4,096 block it ran 9.4 ms and took
  7.3 s to compile where an 8-row tile runs 4.7 and compiles in 0.3 — so a
  call with fewer rows repeats its last one and returns the first ``B``;
* the item axis is still PADDED to ``BLOCK_I`` 512 only
  (:func:`pad_block_items`: tables, IVF clusters, shards), so the last
  block may hang over the table's end; its lanes there are excluded by
  their global index, never by what the overhang read.

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
  nothing costs exactly one.  At 5.7 M x 128, k = 100, on the chip: 824 /
  1,922 / 2,373 / 2,737 / 4,533 passes a dispatch at rungs 1 / 8 / 16 /
  32 / 64 (blocks of 4,096; 2,048 at rung 64), where blocks of 512 made
  1,008 … 9,808 and a fixed ``k`` trips a block, which this loop replaced,
  1,113,300 whatever the rung.  The worst input (scores ascending in item
  order) places min(k, block) entries in every block: the fixed loop's
  cost, never more.

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
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode
from predictionio_tpu.ops.quantize import contraction_precision

NEG_INF = -1e30  # plain float: jnp constants would be captured as operands
_IDX_SENTINEL = 2**31 - 1

# The item axis is PADDED to this (``pad_block_items``: the table, every
# IVF cluster, every shard) and it is the narrowest block the sweep
# streams; the block a compiled shape actually takes is ``tile_geometry``'s.
BLOCK_I = 512
# Rows of the score tile come in whole f32 sublane tiles: a (1, block) tile
# is a shape Mosaic handles badly (module docstring).
ROW_TILE = 8
# V bytes streamed per grid step.  A step costs ~0.7 µs whatever it moves
# (11,133 steps of 256 KB: 8 ms of a 9.2 ms dispatch), so a block is sized
# by its bytes, not its rows.
BLOCK_BYTES = 2 << 20
# What ``tile_geometry`` lets one grid step keep live in VMEM, by its own
# count; v5e's scoped default is 16 MiB and the kernel asks for no more.
VMEM_TILE_BUDGET = 10 << 20


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


def _live_tile_bytes(rows: int, block_i: int, lanes: int, itemsize: int) -> int:
    """What one grid step holds in VMEM at this tile, counted from above
    (``lanes``: the rank as VMEM and HBM tile it, whole 128-lane tiles):
    the double-buffered V block, its f32 upcast where it streams narrower,
    the score tile with its global ids and the merge's selects over them,
    and the double-buffered (1, block) mask and scale lane rows (a lane
    row occupies a whole 8-sublane tile)."""
    v = 2 * block_i * lanes * itemsize
    upcast = block_i * lanes * 4 if itemsize < 4 else 0
    tiles = 6 * rows * block_i * 4
    lane_rows = 2 * 2 * ROW_TILE * block_i * 4
    return v + upcast + tiles + lane_rows


def tile_geometry(batch: int, rank: int, dtype, n_pad: int) -> tuple[int, int]:
    """``(tile_rows, block_items)`` of the sweep for one compiled shape —
    a function of static shapes only, and the one place that decides it
    (``BucketedScorer.stats()`` reports what this returns).

    Rows round up to whole sublane tiles.  The item block is
    ``BLOCK_BYTES`` of V in ``BLOCK_I`` multiples — 4,096 rows at 128 x
    f32, 512 at 2,048 x bf16 — at most the table, and halved while the
    step's live tiles would pass ``VMEM_TILE_BUDGET`` (many rows, or a
    narrow dtype whose upcast is the large tile).  A table of one
    ``BLOCK_I`` or less (an IVF cluster) is one block of its own size.
    """
    rows = -(-batch // ROW_TILE) * ROW_TILE
    if n_pad <= BLOCK_I:
        return rows, n_pad
    itemsize = jnp.dtype(dtype).itemsize
    lanes = -(-rank // 128) * 128  # a row occupies whole lane tiles
    m = max(BLOCK_BYTES // (lanes * itemsize * BLOCK_I), 1)
    while m > 1 and (
        _live_tile_bytes(rows, m * BLOCK_I, lanes, itemsize)
        > VMEM_TILE_BUDGET
    ):
        m //= 2
    return rows, min(m * BLOCK_I, n_pad)


def tile_report(batches, rank: int, dtype, n_pad: int) -> dict:
    """:func:`tile_geometry` per compiled batch size, as ``stats()`` of the
    scorers prints it: ``{"1": {"tile_rows": 8, "block_items": 4096}, …}``."""
    report = {}
    for b in batches:
        rows, block_i = tile_geometry(b, rank, dtype, n_pad)
        report[str(b)] = {"tile_rows": rows, "block_items": block_i}
    return report


def item_mask_row(mask) -> np.ndarray:
    """A bool per-item exclusion mask as the int32 ``(1, n)`` lane row the
    kernel reads: placement builds it once, so no dispatch converts it."""
    return np.asarray(mask).astype(np.int32).reshape(1, -1)


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
    *refs, k: int, block_i: int, batch: int, n_pad: int,
    has_uscale: bool, has_vscale: bool, with_stats: bool,
):
    """One grid step: dot the resident user rows against this item block,
    merge into the running top-k, emit on the last block.  ``batch`` is
    the tile's rows (whole sublane tiles), ``n_pad`` the table's."""
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
    gidx = ii * block_i + jax.lax.broadcasted_iota(
        jnp.int32, (batch, block_i), 1
    )
    excluded = mask_ref[...] != 0
    if n_pad % block_i:
        # the last block hangs over the table's end: what it read there of
        # V, the mask row or a scale row is anything at all, so those
        # lanes are decided by their index alone
        excluded = excluded | (gidx >= n_pad)
    s = jnp.where(excluded, NEG_INF, s)
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
    items: a bool ``(n_items,)`` vector, or the int32 ``(1, n_items)`` lane
    row the kernel reads (:func:`item_mask_row`), which a placement builds
    once so that no dispatch converts it.  ``interpret`` defaults to True
    off-TPU so tests run the kernel anywhere; masked/padded slots can
    never win (NEG_INF before merge).  Callers wanting zero-copy dispatch
    should pre-pad the item dimension to :func:`pad_block_items`; ragged
    inputs are padded (and the tail masked) here.  The tile is
    :func:`tile_geometry`'s: fewer rows than a whole sublane tile are
    filled by repeating the last one (a repeated row finds the same
    candidates in the same pass as its original, so it adds no merge pass
    and the counters stay the real rows') and only the first ``B`` rows
    return.  ``block_items`` (tests, the chip probe) overrides the block:
    any width, the last block may hang over the table's end.
    ``with_stats`` appends a third output, int32 ``(2,)``: the merge
    passes that inserted, summed over the sweep, and the blocks that
    merged anything (what the merge cost: module docstring).
    """
    interpret = pallas_mode.resolve("score_topk", interpret)
    n_items, rank = V.shape
    batch = u_idx.shape[0]
    if not 0 < k <= n_items:
        raise ValueError(f"k={k} out of range for {n_items} items")
    n_pad = pad_block_items(n_items)
    rows, block_i = tile_geometry(batch, rank, V.dtype, n_pad)
    if block_items:
        block_i = min(block_items, n_pad)
    # per-item operands ride as (1, n_pad) lane rows so each block already
    # has the score tile's layout: Mosaic lowers neither a 1-D int8 block
    # nor the (block_i, 1) -> (1, block_i) relayout of a scale column
    if item_mask is None:
        mask_row = jnp.zeros((1, n_items), jnp.int32)
    elif item_mask.ndim == 2:
        mask_row = item_mask
    else:
        mask_row = item_mask.astype(jnp.int32).reshape(1, n_items)
    if mask_row.shape != (1, n_items) or mask_row.dtype != jnp.int32:
        raise ValueError(
            f"item_mask must be bool ({n_items},) or int32 (1, {n_items}), "
            f"got {mask_row.dtype} {mask_row.shape}")
    pad_i = n_pad - n_items
    if pad_i:
        V = jnp.pad(V, ((0, pad_i), (0, 0)))
        mask_row = jnp.pad(mask_row, ((0, 0), (0, pad_i)), constant_values=1)
        if v_scale is not None:
            v_scale = jnp.pad(v_scale, ((0, pad_i), (0, 0)))

    has_us = u_scale is not None
    has_vs = v_scale is not None
    kernel = functools.partial(
        _score_topk_kernel,
        k=k, block_i=block_i, batch=rows, n_pad=n_pad,
        has_uscale=has_us, has_vscale=has_vs, with_stats=with_stats,
    )

    def _pinned(ii):
        return (0, 0)

    # the (B,) embedding rows are gathered by XLA ahead of the kernel — B
    # narrow rows, once per dispatch — and stay VMEM-resident for the whole
    # item sweep; the full user matrix never leaves HBM.  (An in-kernel
    # per-row DMA cannot slice a rank-wide row out of a 128-lane tile.)
    u_idx = u_idx.astype(jnp.int32)
    if rows > batch:
        u_idx = jnp.concatenate(
            [u_idx, jnp.broadcast_to(u_idx[-1:], (rows - batch,))])
    in_specs = [pl.BlockSpec((rows, rank), _pinned)]
    operands = [U[u_idx]]
    if has_us:
        in_specs.append(pl.BlockSpec((rows, 1), _pinned))
        operands.append(u_scale.astype(jnp.float32)[u_idx])
    in_specs.append(pl.BlockSpec((block_i, rank), lambda ii: (ii, 0)))
    operands.append(V)
    if has_vs:
        in_specs.append(pl.BlockSpec((1, block_i), lambda ii: (0, ii)))
        operands.append(v_scale.astype(jnp.float32).reshape(1, n_pad))
    in_specs.append(pl.BlockSpec((1, block_i), lambda ii: (0, ii)))
    operands.append(mask_row)

    out_specs = [pl.BlockSpec((rows, k), _pinned),
                 pl.BlockSpec((rows, k), _pinned)]
    out_shape = [
        jax.ShapeDtypeStruct((rows, k), jnp.float32),
        jax.ShapeDtypeStruct((rows, k), jnp.int32),
    ]
    if with_stats:
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((2,), jnp.int32))
    vals, idx, *stats = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n_pad, block_i),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((rows, block_i), jnp.float32),  # live score tile
            pltpu.VMEM((rows, k), jnp.float32),  # running top-k values
            pltpu.VMEM((rows, k), jnp.int32),  # running global indices
        ],
        interpret=interpret,
    )(*operands)
    return (vals[:batch], idx[:batch], *stats)
