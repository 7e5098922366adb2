"""The gated state-space recurrence (Mamba-2 / SSD) over PACKED histories: a
chunked Pallas scan that carries a matrix state per head along the token
axis, with the input and output maps shared by GROUPS of heads.

Per head, with ``x_t`` in R^P, a step ``dt_t > 0`` and a decay ``a_t =
exp(dt_t A)`` (``A < 0``), and per GROUP of heads ``B_t, C_t`` in R^N, the
state ``h`` in R^(P x N) starts at a history's first event from ``h_0``
(zeros unless the caller hands one over) and moves as

    h_t = a_t h_(t-1) + dt_t x_t (x) B_t,        y_t = h_t C_t + D x_t

The scan computes it a CHUNK of ``L`` tokens at a time.  With ``G_i`` the
running sum of ``dt A`` inside the chunk, unrolling gives

    Y = (D_ij * (C B^T)) (dt X)  +  diag(c) C S_in  +  D X
    S_out = c_L S_in + (diag(kw) B)^T (dt X)

where ``D_ij = exp(G_i - G_j)`` for ``j <= i``, ``c_i`` is the decay from the
state's instant to token ``i`` and ``kw_j`` the decay from token ``j`` to the
chunk's end.  ``C B^T`` (L x L) is made ONCE per group and chunk; the decay
mask, the three products that touch the state and the state itself are per
head.  One grid step holds a chunk of every head of a group side by side on
the lanes — ``x`` and ``y`` stay in the layout the projections around the
scan have, (T, heads * P), so nothing is transposed — and the state of those
heads as ONE (N, heads * P) f32 scratch: ``C S_in`` and the state's update
are one product each for the whole group.  The write is a plain rank-one
update, so unlike ``ops/gated_delta.py`` (the delta rule: a triangular
inverse a chunk, q and k per head) there is no pre-pass and one kernel,
``pio.ssd_scan``; the PACKING is that module's, shared
(``gated_delta._side_inputs``): histories lie end to end on the token axis
(``seg_start``), a pair of tokens of different histories is masked out of
``D`` and of ``S_out`` and never a zero decay put inside a history, the
carried state reaches only the history open at the chunk's start, chunks
past ``n_real`` are not run, ``h0`` gives rows an initial state and
``output_final_state`` returns each row's last one (one head a grid step
then: every row's state of the step's heads sits in VMEM; serving whole
histories uses neither).

Precision: x, B, C, the masked ``C B^T`` tile and the state as an operand of
a product are bf16 (the compute dtype follows ``x``'s: the tests also run
f32), ``dt``, the decays, every accumulation and the carried state f32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import gated_delta as _gd
from predictionio_tpu.ops import pallas_mode

SCAN_SCOPE = "pio.ssd_scan"
CONV_SCOPE = "pio.ssd_conv"
CHUNK = 128
# heads a grid step holds (the largest divisor of a group's heads up to it)
HEADS_PER_STEP = 16
# the columns of ``gated_delta._side_inputs``; its write strength is ``dt``
_G, _DT, _CDEC, _KW, _SEGREL, _NCOLS = (
    _gd._G, _gd._BETA, _gd._CDEC, _gd._KW, _gd._SEGREL, _gd._NCOLS)
_dot = _gd._dot
# the convolution before the scan and the chunk count are that module's too
causal_conv, conv_tail, scan_chunks = (
    _gd.causal_conv, _gd.conv_tail, _gd.scan_chunks)
_LAST = (((1,), (1,)), ((), ()))
_FIRST = (((0,), (0,)), ((), ()))


def _scan_kernel(live, rs_lo, rs_hi, re_lo, re_hi, row_start, row_last,
                 last_rel,  # SMEM
                 x_ref, b_ref, c_ref, cols_ref, grow_ref, d_ref, *rest,
                 chunk: int, heads: int, p: int, has_init: bool,
                 want_final: bool):
    """One chunk of ``heads`` heads of one group: ``C B^T`` once, then per
    head the masked tile's product with ``dt x``, and for all of them at
    once what the carried state gives (``C S``) and takes (``B^T (kw dt
    x)``).  A step past the last real token's chunk writes zeros and
    fetches nothing (its blocks are the last real chunk's)."""
    rest = list(rest)
    h0_ref = rest.pop(0) if has_init else None
    y_ref = rest.pop(0)
    hT_ref = rest.pop(0) if want_final else None
    (s_ref,) = rest
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        if want_final:
            hT_ref[...] = jnp.zeros_like(hT_ref)

    @pl.when(ci >= live[0])
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(ci < live[0])
    def _():
        cdt = x_ref.dtype
        # where the chunk's last token's history starts
        seg_last = last_rel[ci]
        ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
        cols = cols_ref[...]

        def col(k, h):  # a per-token column of head h, (chunk, 1)
            at = k * heads + h
            return cols[:, at:at + 1]

        def decay_row(cdec, at):
            # a token's decay as a (1, p) row (Mosaic spreads no (1, 1)
            # value over both axes): spread over lanes, pick the sublane
            wide = jnp.broadcast_to(cdec, (chunk, p))
            return jnp.sum(jnp.where(pos == at, wide, 0.0), axis=0,
                           keepdims=True)

        def side_by_side(parts):
            return parts[0] if heads == 1 else jnp.concatenate(parts, axis=1)

        segrel = col(_SEGREL, 0).astype(jnp.int32)
        seen = (jj >= segrel) & (jj <= ii)  # j in i's history, j <= i
        # the state each token's history comes from: the carried one for
        # the history open at the chunk's start, a row's own h0 where it
        # starts here
        cont = (segrel < 0).astype(jnp.float32)  # (chunk, 1)
        bm, cm = b_ref[...], c_ref[...]  # (chunk, N)
        cb = _dot(cm, bm, _LAST)  # (chunk, chunk), once for the group
        s0 = s_ref[...]  # (N, heads * p) f32
        cs = _dot(cm, s0.astype(cdt)) * cont
        s_in_last = jnp.where(seg_last < 0, s0, 0.0)
        if has_init:
            def add_row(r, carry):
                cs, s_in_last = carry
                rel = row_start[r] - ci * chunk
                mine = (segrel == rel).astype(jnp.float32)
                sr = h0_ref[r]
                return (cs + mine * _dot(cm, sr.astype(cdt)),
                        jnp.where(seg_last == rel, sr, s_in_last))

            cs, s_in_last = jax.lax.fori_loop(
                rs_lo[ci], rs_hi[ci], add_row, (cs, s_in_last))
        xdt, xw, keep = [], [], []
        for h in range(heads):
            lanes = slice(h * p, (h + 1) * p)
            xh = x_ref[:, lanes].astype(jnp.float32)
            decay = jnp.where(
                seen, jnp.exp(jnp.minimum(
                    col(_G, h) - grow_ref[h:h + 1, :], 0.0)), 0.0)
            xd = xh * col(_DT, h)
            y = (_dot((decay * cb).astype(cdt), xd.astype(cdt))
                 + col(_CDEC, h) * cs[:, lanes] + d_ref[:, lanes] * xh)
            y_ref[:, lanes] = y.astype(y_ref.dtype)
            xdt.append(xd)
            xw.append((xd * col(_KW, h)).astype(cdt))
            keep.append(decay_row(col(_CDEC, h), chunk - 1))
        s_ref[...] = (side_by_side(keep) * s_in_last
                      + _dot(bm, side_by_side(xw), _FIRST))
        if want_final:
            def put_row(r, carry):
                e = row_last[r] - ci * chunk
                rel = row_start[r] - ci * chunk
                s_in = jnp.where(rel < 0, s0,
                                 h0_ref[r] if has_init else 0.0)
                inside = (pos <= e) & (pos >= rel)
                taken, kept = [], []
                for h in range(heads):
                    g_col = col(_G, h)
                    g_e = jnp.sum(jnp.where(pos == e, g_col, 0.0), axis=0,
                                  keepdims=True)
                    w = jnp.where(
                        inside, jnp.exp(jnp.minimum(g_e - g_col, 0.0)), 0.0)
                    taken.append((xdt[h] * w).astype(cdt))
                    kept.append(decay_row(col(_CDEC, h), e))
                hT_ref[r] = (side_by_side(kept) * s_in
                             + _dot(bm, side_by_side(taken), _FIRST))
                return carry

            jax.lax.fori_loop(re_lo[ci], re_hi[ci], put_row, 0)


def _heads_a_step(per_group: int, want: int) -> int:
    return max(d for d in range(1, want + 1) if per_group % d == 0)


def _by_step(a, steps: int, hs: int):
    """(H, ...) -> (steps, hs, ...): the heads of each grid step."""
    return a.reshape(steps, hs, *a.shape[1:])


def ssd_scan(
    x: jax.Array, b: jax.Array, c: jax.Array, dt: jax.Array, a: jax.Array,
    d: jax.Array, seg_start: jax.Array, *, n_groups: int,
    chunk: Optional[int] = None, n_real: Optional[jax.Array] = None,
    h0: Optional[jax.Array] = None, row_start: Optional[jax.Array] = None,
    row_last: Optional[jax.Array] = None, output_final_state: bool = False,
    interpret: Optional[bool] = None,
):
    """The gated state-space recurrence per head over a packed token axis.

    ``x`` (T, H * P), head ``h`` in columns ``[h P, (h + 1) P)``; ``b`` /
    ``c`` (T, G * N), group ``g`` in columns ``[g N, (g + 1) N)`` and read by
    heads ``[g H / G, (g + 1) H / G)``; ``dt`` (T, H) f32, positive (after
    its softplus); ``a`` (H,) f32, negative; ``d`` (H,) f32, the skip;
    ``seg_start`` (T,) int32.  ``T`` must be a multiple of ``chunk`` (128, or
    ``T`` itself when shorter).  Returns ``y`` (T, H * P) in ``x``'s dtype.

    ``n_real``, ``h0`` / ``row_start`` / ``row_last`` and
    ``output_final_state`` as ``gated_delta.gdn_scan``'s; the states are
    (R, H, P, N) f32.
    """
    t, heads = dt.shape
    p, n = x.shape[1] // heads, b.shape[1] // n_groups
    chunk = chunk or min(CHUNK, t)
    has_init, want_final = h0 is not None, bool(output_final_state)
    if t % chunk:
        raise ValueError(f"{t} tokens are not a multiple of the chunk {chunk}")
    if (heads % n_groups or x.shape != (t, heads * p)
            or b.shape != (t, n_groups * n) or c.shape != b.shape):
        raise ValueError(
            f"x {x.shape} / b {b.shape} / c {c.shape}: {heads} heads in "
            f"{n_groups} groups on one token axis")
    if (has_init or want_final) and (row_start is None or row_last is None):
        raise ValueError("h0 / output_final_state need row_start and row_last")
    interpret = pallas_mode.resolve("ssd_scan", interpret)
    n_chunks = t // chunk
    if n_real is None:
        live = jnp.full((1,), n_chunks, jnp.int32)
    else:
        live = jnp.clip((jnp.asarray(n_real, jnp.int32) + chunk - 1) // chunk,
                        1, n_chunks).reshape(1)
    per_group = heads // n_groups
    # with the carry every row's state of the step's heads sits in VMEM: one
    # head a step there
    hs = (1 if has_init or want_final
          else _heads_a_step(per_group, HEADS_PER_STEP))
    steps = heads // hs
    dt = dt.astype(jnp.float32)
    cols, g_rows, last_rel = _gd._side_inputs(
        (dt * a.astype(jnp.float32)).T, dt.T, seg_start, chunk)
    # per grid step: a head's columns side by side, column k of head h at
    # lane k * hs + h; the running sums once more as rows, a head a sublane
    cols = jnp.transpose(_by_step(cols, steps, hs), (0, 2, 3, 1)).reshape(
        steps, t, _NCOLS * hs)
    g_rows = jnp.swapaxes(
        _by_step(g_rows[:, :, 0, :chunk], steps, hs), 1, 2)
    d_rows = jnp.repeat(d.astype(jnp.float32), p).reshape(steps, 1, hs * p)
    if has_init or want_final:
        row_start = row_start.astype(jnp.int32)
        row_last = row_last.astype(jnp.int32)
        ranges = _gd._row_ranges(row_start, row_last, n_chunks, chunk)
        rows = row_start.shape[0]
    else:
        row_start = row_last = jnp.zeros((1,), jnp.int32)
        ranges = (jnp.zeros((n_chunks,), jnp.int32),) * 4
        rows = 0

    # (grid indices, then the eight prefetched scalars)
    def per_chunk(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (_gd._live_chunk(ci, live), s)

    def group_chunk(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (_gd._live_chunk(ci, live), s * hs // per_group)

    def step_chunk(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (s, _gd._live_chunk(ci, live), 0)

    def step_chunk_rows(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (s, _gd._live_chunk(ci, live), 0, 0)

    def per_step(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (s, 0, 0)

    def per_step_rows(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (0, s, 0, 0)

    def per_chunk_out(s, ci, live, s0, s1, e0, e1, rs, rl, lr):
        return (ci, s)

    in_specs = [
        pl.BlockSpec((chunk, hs * p), per_chunk),
        pl.BlockSpec((chunk, n), group_chunk),
        pl.BlockSpec((chunk, n), group_chunk),
        pl.BlockSpec((None, chunk, _NCOLS * hs), step_chunk),
        pl.BlockSpec((None, None, hs, chunk), step_chunk_rows),
        pl.BlockSpec((None, 1, hs * p), per_step),
    ]
    args = [x, b, c, cols, g_rows, d_rows]
    if has_init:
        in_specs.append(pl.BlockSpec((rows, None, n, hs * p), per_step_rows))
        args.append(_states_by_step(h0.astype(jnp.float32), steps, hs))
    out_shape = [jax.ShapeDtypeStruct((t, heads * p), x.dtype)]
    out_specs = [pl.BlockSpec((chunk, hs * p), per_chunk_out)]
    if want_final:
        out_shape.append(
            jax.ShapeDtypeStruct((rows, steps, n, hs * p), jnp.float32))
        out_specs.append(pl.BlockSpec((rows, None, n, hs * p), per_step_rows))
    with jax.named_scope(SCAN_SCOPE):
        outs = pl.pallas_call(
            functools.partial(_scan_kernel, chunk=chunk, heads=hs, p=p,
                              has_init=has_init, want_final=want_final),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=8,
                grid=(steps, n_chunks),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((n, hs * p), jnp.float32)],
            ),
            out_shape=out_shape,
            compiler_params=_gd._PARAMS,
            interpret=interpret,
        )(live, *ranges, row_start, row_last, last_rel, *args)
    if not want_final:
        return outs[0]
    # (R, steps, N, hs * P) -> (R, H, P, N)
    final = outs[1].reshape(rows, steps, n, hs, p)
    return outs[0], jnp.transpose(final, (0, 1, 3, 4, 2)).reshape(
        rows, heads, p, n)


def _states_by_step(h0, steps: int, hs: int):
    """(R, H, P, N) -> (R, steps, N, hs * P), the kernel's layout."""
    r, _, p, n = h0.shape
    return jnp.transpose(h0.reshape(r, steps, hs, p, n),
                         (0, 1, 4, 2, 3)).reshape(r, steps, n, hs * p)
