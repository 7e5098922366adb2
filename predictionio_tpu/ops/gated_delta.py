"""The gated delta rule over PACKED histories: a chunked Pallas scan that
carries a matrix state along the token axis, and the short causal
convolution that precedes it.

Per head, with ``q_t, k_t`` in R^dk, ``v_t`` in R^dv, a decay ``a_t =
exp(g_t)`` in (0, 1] and a write strength ``b_t`` in (0, 2), the state ``S``
in R^(dk x dv) starts at a history's first event from ``S_0`` (zeros unless
the caller hands one over) and moves as

    S_t = a_t S_(t-1) + b_t k_t (v_t - a_t S_(t-1)^T k_t)^T,   o_t = S_t^T q_t

(the ``GatedDeltaNet`` recurrence).  The kernel computes it a CHUNK of ``C``
tokens at a time.  With ``G_i`` the running sum of ``g`` inside the chunk,
``D_ij = exp(G_i - G_j)`` for ``j <= i`` and ``u_i = b_i (v_i - a_i
S_(i-1)^T k_i)`` (so that ``S_i = a_i S_(i-1) + k_i u_i^T``), unrolling gives

    (I + A) U = diag(b) (V - diag(c) K S_in),   A = tril(diag(b) (D * K K^T), -1)
    O = diag(c) Q S_in + tril(D * Q K^T) U
    S_out = c_C S_in + (diag(D_C.) K)^T U

where ``c_i`` is the decay from the state's instant to token ``i``.  The
unit lower-triangular ``I + A`` is inverted by doubling — blocks of 1, 2, 4
... ``C`` rows: ``inv([[L11, 0], [L21, L22]]) = [[T11, 0], [-T22 L21 T11,
T22]]``, two ``C x C`` products a level in f32 — which is triangular
inversion proper (no power of ``A`` is ever formed).

**Packing.**  Several histories lie end to end on the token axis
(``seg_start[t]`` = index of the first token of token ``t``'s history; a
padded token is a history of its own) and a history may start and end
anywhere in a chunk.  A reset is not ``a = 0`` at a first token — that
would make ``D`` a 0/0 inside the new history — but the form equal to it:
every pair ``(i, j)`` of different histories is masked out of ``A``, of the
``Q K^T`` term and of ``S_out``, and the carried state reaches only the
tokens of the history that was open at the chunk's start.  ``G`` is the
plain running sum: between two tokens of ONE history it never crosses a
reset.  Every chunk costs the same whatever it holds, so the padded tail
costs what real tokens cost.

**Carry.**  ``h0`` gives rows an initial state and ``output_final_state``
returns each row's last one (f32), so ``scan(A || B)`` equals ``scan(B)``
from what ``scan(A)`` returned; rows are then named by ``row_start`` /
``row_last``.  Both are loops over the rows that start or end in a chunk,
compiled in only when asked for: serving whole histories uses neither.

Precision: q, k, v and the state as an operand of a product are bf16 (the
compute dtype follows ``q``'s: the tests also run f32), every accumulation,
the inversion, ``U`` and the carried state f32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode

SCAN_SCOPE = "pio.gdn_scan"
CONV_SCOPE = "pio.gdn_conv"
CHUNK = 64
# heads a grid step holds (the largest divisor of the head count up to it)
HEADS_PER_STEP = 6
# columns of the per-token f32 side input
_G, _BETA, _CDEC, _KW, _SEGREL, _NCOLS = 0, 1, 2, 3, 4, 8
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot32(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(rs_lo, rs_hi, re_lo, re_hi, row_start, row_last, last_rel,  # SMEM
            q_ref, k_ref, v_ref, cols_ref, grow_ref, *rest,
            chunk: int, group: int, has_init: bool, want_final: bool):
    rest = list(rest)
    h0_ref = rest.pop(0) if has_init else None
    o_ref = rest.pop(0)
    hT_ref = rest.pop(0) if want_final else None
    (s_ref,) = rest
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)
        if want_final:
            hT_ref[...] = jnp.zeros_like(hT_ref)

    # a chunk of ``group`` heads a grid step: each head's chain of small
    # dependent products is independent of the others', so the scheduler
    # has something to fill a product's latency with
    for hh in range(group):
        _one_head(hh, ci, rs_lo, rs_hi, re_lo, re_hi, row_start, row_last,
                  last_rel, q_ref, k_ref, v_ref, cols_ref, grow_ref, h0_ref,
                  o_ref, hT_ref, s_ref, chunk=chunk)


def _one_head(hh, ci, rs_lo, rs_hi, re_lo, re_hi, row_start, row_last,
              last_rel, q_ref, k_ref, v_ref, cols_ref, grow_ref, h0_ref,
              o_ref, hT_ref, s_ref, *, chunk: int):
    has_init, want_final = h0_ref is not None, hT_ref is not None
    q, k, v = q_ref[hh], k_ref[hh], v_ref[hh]
    cdt = q.dtype
    cols = cols_ref[hh]
    g_col, beta = cols[:, _G:_G + 1], cols[:, _BETA:_BETA + 1]
    cdec, kw = cols[:, _CDEC:_CDEC + 1], cols[:, _KW:_KW + 1]
    segrel = cols[:, _SEGREL:_SEGREL + 1].astype(jnp.int32)
    g_row = grow_ref[hh]  # (1, C)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    same = jj >= segrel  # j in i's history, for j <= i
    seen = same & (jj <= ii)
    decay = jnp.where(seen, jnp.exp(jnp.minimum(g_col - g_row, 0.0)), 0.0)
    last = (((1,), (1,)), ((), ()))
    a = jnp.where(jj < ii, beta * decay * _dot(k, k, last), 0.0)
    # (I + A)^-1 by doubling the inverted diagonal blocks
    t = (ii == jj).astype(jnp.float32)
    b = 1
    while b < chunk:
        off = ((ii // (2 * b) == jj // (2 * b)) & (ii % (2 * b) >= b)
               & (jj % (2 * b) < b))
        t = t - _dot32(_dot32(t, jnp.where(off, a, 0.0)), t)
        b *= 2

    # the state each token's history comes from: the carried one for the
    # history open at the chunk's start, a row's own h0 where it starts here
    s0 = s_ref[hh]
    cont = (segrel < 0).astype(jnp.float32)  # (C, 1)
    s0c = s0.astype(cdt)
    ks = _dot(k, s0c) * cont
    qs = _dot(q, s0c) * cont
    seg_last = last_rel[ci]  # where the chunk's last token's history starts
    s_in_last = jnp.where(seg_last < 0, s0, 0.0)
    if has_init:
        def add_row(r, carry):
            ks, qs, s_in_last = carry
            rel = row_start[r] - ci * chunk
            mine = (segrel == rel).astype(jnp.float32)
            sr = h0_ref[r, hh]
            src = sr.astype(cdt)
            return (ks + mine * _dot(k, src), qs + mine * _dot(q, src),
                    jnp.where(seg_last == rel, sr, s_in_last))

        ks, qs, s_in_last = jax.lax.fori_loop(
            rs_lo[ci], rs_hi[ci], add_row, (ks, qs, s_in_last))
    u = _dot32(t, beta * (v.astype(jnp.float32) - cdec * ks))  # (C, dv) f32
    ub = u.astype(cdt)
    qk = (decay * _dot(q, k, last)).astype(cdt)
    o_ref[hh] = (cdec * qs + _dot(qk, ub)).astype(o_ref.dtype)
    first = (((0,), (0,)), ((), ()))
    kf = k.astype(jnp.float32)
    # a token's decay as a (1, dv) row (Mosaic spreads no (1, 1) value over
    # both axes): spread over lanes, then pick the token's sublane
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
    cdec_wide = jnp.broadcast_to(cdec, (chunk, v.shape[1]))

    def decay_row(at):
        return jnp.sum(jnp.where(pos == at, cdec_wide, 0.0), axis=0,
                       keepdims=True)

    s_ref[hh] = (decay_row(chunk - 1) * s_in_last
                 + _dot((kw * kf).astype(cdt), ub, first))
    if want_final:
        def put_row(r, carry):
            e = row_last[r] - ci * chunk
            rel = row_start[r] - ci * chunk
            at_e = (pos == e).astype(jnp.float32)
            g_e = jnp.sum(at_e * g_col, axis=0, keepdims=True)
            w = jnp.where((pos <= e) & (pos >= rel),
                          jnp.exp(jnp.minimum(g_e - g_col, 0.0)), 0.0)
            s_in = jnp.where(rel < 0, s0,
                             h0_ref[r, hh] if has_init else 0.0)
            hT_ref[r, hh] = (decay_row(e) * s_in
                             + _dot((w * kf).astype(cdt), ub, first))
            return carry

        jax.lax.fori_loop(re_lo[ci], re_hi[ci], put_row, 0)


def _side_inputs(g, beta, seg_start, chunk):
    """Per token and head, what the kernel needs of the decays, made in one
    XLA fusion: see the columns' names."""
    h, t = g.shape
    n = t // chunk
    g = g.astype(jnp.float32)
    big_g = jnp.cumsum(g.reshape(h, n, chunk), axis=-1).reshape(h, t)
    at = jnp.arange(t, dtype=jnp.int32)
    chunk_start = (at // chunk) * chunk
    seg_start = seg_start.astype(jnp.int32)
    cont = seg_start < chunk_start
    # the running sum just BEFORE a history that starts in this chunk
    g_before = jnp.where(cont[None], 0.0, (big_g - g)[:, seg_start])
    cdec = jnp.exp(big_g - g_before)
    end = chunk_start + chunk - 1
    in_last = seg_start == seg_start[end]
    kw = jnp.where(in_last[None], jnp.exp(big_g[:, end] - big_g), 0.0)
    segrel = jnp.broadcast_to(
        (seg_start - chunk_start).astype(jnp.float32)[None], (h, t))
    zero = jnp.zeros_like(big_g)
    cols = jnp.stack([big_g, beta.astype(jnp.float32), cdec, kw, segrel]
                     + [zero] * (_NCOLS - 5), axis=-1)
    return cols, big_g.reshape(h, n, 1, chunk)


def _row_ranges(row_start, row_last, n, chunk):
    edges = jnp.arange(n + 1, dtype=jnp.int32) * chunk
    s = jnp.searchsorted(row_start, edges).astype(jnp.int32)
    e = jnp.searchsorted(row_last, edges).astype(jnp.int32)
    return s[:-1], s[1:], e[:-1], e[1:]


def gdn_scan(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    seg_start: jax.Array, *, chunk: Optional[int] = None,
    h0: Optional[jax.Array] = None, row_start: Optional[jax.Array] = None,
    row_last: Optional[jax.Array] = None, output_final_state: bool = False,
    interpret: Optional[bool] = None,
):
    """The gated delta rule per head over a packed token axis.

    ``q``/``k`` (H, T, dk) — already normalised and scaled —, ``v`` (H, T,
    dv), ``g`` (log decay, <= 0) and ``beta`` (H, T) f32, ``seg_start`` (T,)
    int32.  ``T`` must be a multiple of ``chunk`` (64, or ``T`` itself when
    shorter).  A grid step holds ``HEADS_PER_STEP`` heads' chunks (or the
    largest divisor of ``H`` under it).  Returns ``o`` (H, T, dv) in ``v``'s
    dtype.

    With ``h0`` (R, H, dk, dv) f32 the row that starts at ``row_start[r]``
    starts from ``h0[r]``; with ``output_final_state`` the state after token
    ``row_last[r]`` is returned as well, (R, H, dk, dv) f32.  ``row_start``
    and ``row_last`` (R,) int32 must increase; an entry of ``T`` or more
    names no row.
    """
    heads, t, dk = q.shape
    dv = v.shape[2]
    chunk = chunk or min(CHUNK, t)
    has_init, want_final = h0 is not None, bool(output_final_state)
    # with the carry every row's state of the step's heads sits in VMEM: one
    # head a step there
    want = 1 if has_init or want_final else HEADS_PER_STEP
    group = max(d for d in range(1, want + 1) if heads % d == 0)
    if t % chunk:
        raise ValueError(f"{t} tokens are not a multiple of the chunk {chunk}")
    if (has_init or want_final) and (row_start is None or row_last is None):
        raise ValueError("h0 / output_final_state need row_start and row_last")
    interpret = pallas_mode.resolve("gdn_scan", interpret)
    n = t // chunk
    cols, g_rows = _side_inputs(g, beta, seg_start, chunk)
    if has_init or want_final:
        row_start = row_start.astype(jnp.int32)
        row_last = row_last.astype(jnp.int32)
        ranges = _row_ranges(row_start, row_last, n, chunk)
        rows = row_start.shape[0]
    else:
        row_start = row_last = jnp.zeros((1,), jnp.int32)
        ranges = (jnp.zeros((n,), jnp.int32),) * 4
        rows = 0
    ends = jnp.arange(n, dtype=jnp.int32) * chunk + chunk - 1
    last_rel = seg_start.astype(jnp.int32)[ends] - (ends - chunk + 1)

    # (grid indices, then the seven prefetched scalars)
    def per_chunk(h, c, s0, s1, e0, e1, rs, rl, lr):
        return (h, c, 0)

    def per_chunk_row(h, c, s0, s1, e0, e1, rs, rl, lr):
        return (h, c, 0, 0)

    def per_head_rows(h, c, s0, s1, e0, e1, rs, rl, lr):
        return (0, h, 0, 0)

    in_specs = [
        pl.BlockSpec((group, chunk, dk), per_chunk),
        pl.BlockSpec((group, chunk, dk), per_chunk),
        pl.BlockSpec((group, chunk, dv), per_chunk),
        pl.BlockSpec((group, chunk, _NCOLS), per_chunk),
        pl.BlockSpec((group, None, 1, chunk), per_chunk_row),
    ]
    args = [q, k, v, cols, g_rows]
    if has_init:
        in_specs.append(pl.BlockSpec((rows, group, dk, dv), per_head_rows))
        args.append(h0.astype(jnp.float32))
    out_shape = [jax.ShapeDtypeStruct((heads, t, dv), v.dtype)]
    out_specs = [pl.BlockSpec((group, chunk, dv), per_chunk)]
    if want_final:
        out_shape.append(
            jax.ShapeDtypeStruct((rows, heads, dk, dv), jnp.float32))
        out_specs.append(pl.BlockSpec((rows, group, dk, dv), per_head_rows))
    with jax.named_scope(SCAN_SCOPE):
        outs = pl.pallas_call(
            functools.partial(_kernel, chunk=chunk, group=group,
                              has_init=has_init, want_final=want_final),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=7,
                grid=(heads // group, n),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((group, dk, dv), jnp.float32)],
            ),
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024,
            ),
            interpret=interpret,
        )(*ranges, row_start, row_last, last_rel, *args)
    return tuple(outs) if want_final else outs[0]


def scan_chunks(t: int, chunk: Optional[int] = None) -> int:
    """Chunks one head's scan of a ``t``-token axis takes."""
    return t // (chunk or min(CHUNK, t))


def causal_conv(x: jax.Array, w: jax.Array, positions: jax.Array, *,
                tail: Optional[jax.Array] = None,
                row_of: Optional[jax.Array] = None):
    """Depthwise causal convolution of width ``W`` over each packed history:
    ``y_t = sum_j w[j] x_(t-W+1+j)``, zeros before a history's first event.

    ``x`` (T, channels), ``w`` (W, channels), ``positions`` (T,) int32 (index
    within the history).  With ``tail`` (R, W-1, channels) — the last
    ``W - 1`` inputs of row ``row_of[t]``'s earlier part, oldest first — the
    history continues from them instead of zeros (``row_of`` (T,) int32, -1
    for a token of no row).  Returns (T, channels) f32.  Plain XLA: one
    elementwise fusion, which a TPU trace does not name.
    """
    width = w.shape[0]
    xf = x.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    with jax.named_scope(CONV_SCOPE):
        y = xf * wf[width - 1]
        for back in range(1, width):
            shifted = jnp.pad(xf, ((back, 0), (0, 0)))[:xf.shape[0]]
            inside = (positions >= back)[:, None]
            if tail is None:
                before = 0.0
            else:
                # token at position p reads tail[W-1 - (back - p)]
                slot = jnp.clip(width - 1 - back + positions, 0, width - 2)
                before = jnp.where(
                    (row_of >= 0)[:, None],
                    tail.astype(jnp.float32)[jnp.maximum(row_of, 0), slot],
                    0.0)
            y = y + jnp.where(inside, shifted, before) * wf[width - 1 - back]
        return y


def conv_tail(x: jax.Array, row_start: jax.Array, row_last: jax.Array,
              width: int, tail: Optional[jax.Array] = None) -> jax.Array:
    """The last ``width - 1`` pre-convolution inputs of each row, oldest
    first, (R, width-1, channels): what :func:`causal_conv` takes as
    ``tail`` for the row's next part.  Inputs before the row's start come
    from its own earlier ``tail`` (zeros if none)."""
    xf = x.astype(jnp.float32)
    outs = []
    for back in range(width - 1, 0, -1):  # oldest first
        at = row_last - (back - 1)
        have = at >= row_start
        got = xf[jnp.clip(at, 0, xf.shape[0] - 1)]
        if tail is None:
            old = jnp.zeros_like(got)
        else:
            # (row_start - at) inputs short: reach that far back into tail
            slot = jnp.clip(width - 1 - (row_start - at), 0, width - 2)
            old = jnp.where(
                (row_start - at <= width - 1)[:, None],
                tail.astype(jnp.float32)[jnp.arange(tail.shape[0]), slot],
                0.0)
        outs.append(jnp.where(have[:, None], got, old))
    return jnp.stack(outs, axis=1)
