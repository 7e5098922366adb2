"""The gated delta rule over PACKED histories: a chunked Pallas scan that
carries a matrix state along the token axis, and the short causal
convolution that precedes it.

Per head, with ``q_t, k_t`` in R^dk, ``v_t`` in R^dv, a decay ``a_t =
exp(g_t)`` in (0, 1] and a write strength ``b_t`` in (0, 2), the state ``S``
in R^(dk x dv) starts at a history's first event from ``S_0`` (zeros unless
the caller hands one over) and moves as

    S_t = a_t S_(t-1) + b_t k_t (v_t - a_t S_(t-1)^T k_t)^T,   o_t = S_t^T q_t

(the ``GatedDeltaNet`` recurrence).  The scan computes it a CHUNK of ``C``
tokens at a time.  With ``G_i`` the running sum of ``g`` inside the chunk,
``D_ij = exp(G_i - G_j)`` for ``j <= i`` and ``u_i = b_i (v_i - a_i
S_(i-1)^T k_i)`` (so that ``S_i = a_i S_(i-1) + k_i u_i^T``), unrolling gives

    (I + A) U = diag(b) (V - diag(c) K S_in),   A = tril(diag(b) (D * K K^T), -1)
    O = diag(c) Q S_in + tril(D * Q K^T) U
    S_out = c_C S_in + (diag(D_C.) K)^T U

where ``c_i`` is the decay from the state's instant to token ``i``.

**Two kernels.**  ``T = (I + A)^-1`` and ``P = tril(D * Q K^T)`` depend on
the chunk's own ``q, k, g, b`` and resets alone, so a PRE-PASS
(``pio.gdn_scan_prep``) makes them for every (head, chunk), which are
independent of each other: two heads side by side on the lanes, the stages
issued for all of a grid step's pairs before the next.  The unit
lower-triangular ``I + A`` is inverted by doubling — blocks of 1, 2, 4 ...
``C`` rows: ``inv([[L11, 0], [L21, L22]]) = [[T11, 0], [-T22 L21 T11,
T22]]``, two products a level in f32 at ``HIGHEST`` — which is triangular
inversion proper (no power of ``A`` is ever formed); the first level, blocks
of one row, is ``I - off(A)`` and needs no product.  The STEP
(``pio.gdn_scan``), the one loop that has to run in order, keeps what needs
the carried state: ``[K; Q] S_in``, ``U = T rhs`` (f32 at ``HIGHEST``), ``O``
and ``S_out`` — a chain of four products a chunk.

**Packing.**  Several histories lie end to end on the token axis
(``seg_start[t]`` = index of the first token of token ``t``'s history; a
padded token is a history of its own) and a history may start and end
anywhere in a chunk.  A reset is not ``a = 0`` at a first token — that
would make ``D`` a 0/0 inside the new history — but the form equal to it:
every pair ``(i, j)`` of different histories is masked out of ``A``, of the
``Q K^T`` term and of ``S_out``, and the carried state reaches only the
tokens of the history that was open at the chunk's start.  ``G`` is the
plain running sum: between two tokens of ONE history it never crosses a
reset.

**The padded tail.**  A chunk that is run costs the same whatever it holds;
a chunk past the last real token (``n_real``) is not run: both kernels'
grids are gated by one prefetched count of live chunks, a step past it
fetches nothing (its block indices are the last live chunk's) and writes
zeros to ``o``.  A padded token is a one-token history that no attention
row, no convolution tap and no ``last_idx`` reads, so real rows' answers do
not change by a bit.

**Carry.**  ``h0`` gives rows an initial state and ``output_final_state``
returns each row's last one (f32), so ``scan(A || B)`` equals ``scan(B)``
from what ``scan(A)`` returned; rows are then named by ``row_start`` /
``row_last``.  Both are loops over the rows that start or end in a chunk,
compiled into the step only when asked for (one head a grid step then):
serving whole histories uses neither.

Precision: q, k, v, ``P`` and the state as an operand of a product are bf16
(the compute dtype follows ``q``'s: the tests also run f32), every
accumulation, the inversion, ``T``, ``U`` and the carried state f32.
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
PREP_SCOPE = "pio.gdn_scan_prep"
CONV_SCOPE = "pio.gdn_conv"
CHUNK = 64
# heads a grid step of either kernel holds (the largest divisor of the head
# count up to it): their chains are independent, and the stages of all of
# them are issued side by side
HEADS_PER_STEP = 6
# columns of the per-token f32 side input
_G, _BETA, _CDEC, _KW, _SEGREL, _NCOLS = 0, 1, 2, 3, 4, 8
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _dot32(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _prep_kernel(live, q_ref, k_ref, cols_ref, grow_ref, t_ref, p_ref, *,
                 chunk: int, group: int):
    """What a chunk's step needs that does NOT depend on the carried state:
    ``T = (I + A)^-1`` (f32) and ``P = tril(D * Q K^T)`` (compute dtype), for
    ``group`` heads' chunks a grid step.  Two heads lie side by side on the
    lanes, ``[x_1 | x_2]`` (C, 2C), and their products are made as one
    against a block-diagonal right-hand side; the stages are issued for all
    of the step's pairs before the next stage, so that independent chains
    stand next to each other."""

    @pl.when(pl.program_id(1) < live[0])
    def _():
        c = chunk
        ii = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
        right = lane >= c  # the pair's second head
        jj = jnp.where(right, lane - c, lane)
        eye = (ii == jj).astype(jnp.float32)
        levels = []  # blocks of b rows: the block below the diagonal
        b = 1
        while b < c:
            levels.append(((ii ^ jj) < 2 * b) & ((ii & b) != 0)
                          & ((jj & b) == 0))
            b *= 2

        def both(one, two):
            return jnp.where(right, two, one)

        def diag2(x):  # [x_1 | x_2] -> [[x_1, 0], [0, x_2]]
            return jnp.concatenate(
                [jnp.where(right, 0.0, x), jnp.where(right, x, 0.0)], axis=0)

        last = (((1,), (1,)), ((), ()))
        pairs = [(h, min(h + 1, group - 1)) for h in range(0, group, 2)]
        a_s, t_s = [], []
        for h1, h2 in pairs:
            cols1, cols2 = cols_ref[h1], cols_ref[h2]
            segrel = cols1[:, _SEGREL:_SEGREL + 1].astype(jnp.int32)
            g_col = both(cols1[:, _G:_G + 1], cols2[:, _G:_G + 1])
            beta = both(cols1[:, _BETA:_BETA + 1], cols2[:, _BETA:_BETA + 1])
            g_row = both(grow_ref[h1], grow_ref[h2])  # (1, 2C)
            seen = (jj >= segrel) & (jj <= ii)  # j in i's history, j <= i
            decay = jnp.where(
                seen, jnp.exp(jnp.minimum(g_col - g_row, 0.0)), 0.0)
            k1, k2 = k_ref[h1], k_ref[h2]
            k12 = jnp.concatenate([k1, k2], axis=0)  # (2C, dk)
            kk = both(_dot(k1, k12, last), _dot(k2, k12, last))
            qk = both(_dot(q_ref[h1], k12, last), _dot(q_ref[h2], k12, last))
            p = decay * qk
            p_ref[h1] = p[:, :c].astype(p_ref.dtype)
            if h2 != h1:
                p_ref[h2] = p[:, c:].astype(p_ref.dtype)
            a = jnp.where(jj < ii, beta * decay * kk, 0.0)
            a_s.append(a)
            # blocks of one row are their own inverse, and the first level
            # is ``I - off(a)`` exactly
            t_s.append(eye - jnp.where(levels[0], a, 0.0))
        for off in levels[1:]:
            x_s = [_dot32(t, diag2(jnp.where(off, a, 0.0)))
                   for a, t in zip(a_s, t_s)]
            t_s = [t - _dot32(x, diag2(t)) for x, t in zip(x_s, t_s)]
        for (h1, h2), t in zip(pairs, t_s):
            t_ref[h1] = t[:, :c]
            if h2 != h1:
                t_ref[h2] = t[:, c:]


def _step_kernel(live, rs_lo, rs_hi, re_lo, re_hi, row_start, row_last,
                 last_rel,  # SMEM
                 q_ref, k_ref, v_ref, cols_ref, t_ref, p_ref, *rest,
                 chunk: int, group: int, has_init: bool, want_final: bool):
    """What a chunk owes the carried state, for ``group`` heads: ``[K; Q]
    S``, ``U = T (b (V - c K S))``, ``O = c Q S + P U`` and ``S' = c_C S +
    (kw K)^T U`` — a chain of four products, issued stage by stage for all
    of the step's heads.  A step past the last real token's chunk writes
    zeros and fetches nothing (its blocks are the last real chunk's)."""
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

    @pl.when(ci >= live[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(ci < live[0])
    def _():
        cdt = q_ref.dtype
        dv = v_ref.shape[2]
        # where the chunk's last token's history starts
        seg_last = last_rel[ci]
        first = (((0,), (0,)), ((), ()))
        # a token's decay as a (1, dv) row (Mosaic spreads no (1, 1) value
        # over both axes): spread over lanes, then pick the token's sublane
        pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

        def decay_row(cdec, at):
            wide = jnp.broadcast_to(cdec, (chunk, dv))
            return jnp.sum(jnp.where(pos == at, wide, 0.0), axis=0,
                           keepdims=True)

        heads = range(group)
        cols = [cols_ref[hh] for hh in heads]
        beta = [c[:, _BETA:_BETA + 1] for c in cols]
        cdec = [c[:, _CDEC:_CDEC + 1] for c in cols]
        kw = [c[:, _KW:_KW + 1] for c in cols]
        segrel = cols[0][:, _SEGREL:_SEGREL + 1].astype(jnp.int32)
        # the state each token's history comes from: the carried one for
        # the history open at the chunk's start, a row's own h0 where it
        # starts here
        cont = (segrel < 0).astype(jnp.float32)  # (C, 1)
        s0 = [s_ref[hh] for hh in heads]
        ks, qs = [], []
        for hh in heads:
            kq = jnp.concatenate([k_ref[hh], q_ref[hh]], axis=0)  # (2C, dk)
            kqs = _dot(kq, s0[hh].astype(cdt)) * jnp.concatenate([cont, cont])
            ks.append(kqs[:chunk])
            qs.append(kqs[chunk:])
        s_in_last = [jnp.where(seg_last < 0, s, 0.0) for s in s0]
        if has_init:
            for hh in heads:
                k, q = k_ref[hh], q_ref[hh]

                def add_row(r, carry, hh=hh, k=k, q=q):
                    ks, qs, s_in_last = carry
                    rel = row_start[r] - ci * chunk
                    mine = (segrel == rel).astype(jnp.float32)
                    sr = h0_ref[r, hh]
                    src = sr.astype(cdt)
                    return (ks + mine * _dot(k, src), qs + mine * _dot(q, src),
                            jnp.where(seg_last == rel, sr, s_in_last))

                ks[hh], qs[hh], s_in_last[hh] = jax.lax.fori_loop(
                    rs_lo[ci], rs_hi[ci], add_row,
                    (ks[hh], qs[hh], s_in_last[hh]))
        u = [_dot32(t_ref[hh], beta[hh] * (v_ref[hh].astype(jnp.float32)
                                           - cdec[hh] * ks[hh]))
             for hh in heads]  # (C, dv) f32
        ub = [x.astype(cdt) for x in u]
        kf = [k_ref[hh].astype(jnp.float32) for hh in heads]
        for hh in heads:
            o_ref[hh] = (cdec[hh] * qs[hh]
                         + _dot(p_ref[hh], ub[hh])).astype(o_ref.dtype)
        for hh in heads:
            s_ref[hh] = (decay_row(cdec[hh], chunk - 1) * s_in_last[hh]
                         + _dot((kw[hh] * kf[hh]).astype(cdt), ub[hh], first))
        if want_final:
            for hh in heads:
                g_col = cols[hh][:, _G:_G + 1]

                def put_row(r, carry, hh=hh, g_col=g_col):
                    e = row_last[r] - ci * chunk
                    rel = row_start[r] - ci * chunk
                    at_e = (pos == e).astype(jnp.float32)
                    g_e = jnp.sum(at_e * g_col, axis=0, keepdims=True)
                    w = jnp.where((pos <= e) & (pos >= rel),
                                  jnp.exp(jnp.minimum(g_e - g_col, 0.0)), 0.0)
                    s_in = jnp.where(rel < 0, s0[hh],
                                     h0_ref[r, hh] if has_init else 0.0)
                    hT_ref[r, hh] = (decay_row(cdec[hh], e) * s_in
                                     + _dot((w * kf[hh]).astype(cdt), ub[hh],
                                            first))
                    return carry

                jax.lax.fori_loop(re_lo[ci], re_hi[ci], put_row, 0)


def _side_inputs(g, beta, seg_start, chunk):
    """Per token and head, what the kernels need of the decays, in plain XLA
    without a gather (on a TPU a gather of h x t scalars costs more than
    the scan it feeds): see the columns' names; and the running sum once
    more as a row, twice side by side (the pre-pass holds two heads on the
    lanes)."""
    h, t = g.shape
    n = t // chunk
    g = g.astype(jnp.float32).reshape(h, n, chunk)
    big_g = jnp.cumsum(g, axis=-1)
    # where in its chunk a token's history starts (negative: before it)
    segrel = (seg_start.astype(jnp.int32).reshape(n, chunk)
              - jnp.arange(n, dtype=jnp.int32)[:, None] * chunk)
    # the running sum just BEFORE a history that starts in this chunk, picked
    # by a one-hot sum over the chunk's places (none for a history that
    # started earlier: 0)
    starts_at = segrel[:, :, None] == jnp.arange(chunk, dtype=jnp.int32)
    g_before = jnp.sum(
        jnp.where(starts_at[None], (big_g - g)[:, :, None, :], 0.0), axis=-1)
    cdec = jnp.exp(big_g - g_before)
    in_last = segrel == segrel[:, -1:]  # the chunk's last token's history
    kw = jnp.where(in_last[None], jnp.exp(big_g[:, :, -1:] - big_g), 0.0)
    # stacked as rows and turned once: stacked on the last axis each column
    # is an (h, t, 1) array of its own, which a TPU pads to 128 lanes
    cols = jnp.stack(
        [big_g, beta.astype(jnp.float32).reshape(h, n, chunk), cdec, kw,
         jnp.broadcast_to(segrel.astype(jnp.float32)[None], (h, n, chunk))]
        + [jnp.zeros_like(big_g)] * (_NCOLS - 5), axis=2)
    rows = big_g.reshape(h, n, 1, chunk)
    return (jnp.swapaxes(cols, 2, 3).reshape(h, t, _NCOLS),
            jnp.concatenate([rows, rows], axis=-1), segrel[:, -1])


def _row_ranges(row_start, row_last, n, chunk):
    edges = jnp.arange(n + 1, dtype=jnp.int32) * chunk
    s = jnp.searchsorted(row_start, edges).astype(jnp.int32)
    e = jnp.searchsorted(row_last, edges).astype(jnp.int32)
    return s[:-1], s[1:], e[:-1], e[1:]


def _heads_a_step(heads: int, want: int) -> int:
    return max(d for d in range(1, want + 1) if heads % d == 0)


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _live_chunk(c, live):
    """A chunk past the last real one names that one's blocks, so nothing
    moves for it."""
    return jnp.minimum(c, live[0] - 1)


def _prepass(live, q, k, cols, g_rows, *, chunk, interpret):
    """``T`` (H, T, C) f32 and ``P`` (H, T, C) in ``q``'s dtype of every
    chunk that is run; the others' places are not written."""
    heads, t, dk = q.shape
    group = _heads_a_step(heads, HEADS_PER_STEP)

    def live_chunk(h, c, live):  # grid indices, then the prefetched scalar
        return (h, _live_chunk(c, live), 0)

    with jax.named_scope(PREP_SCOPE):
        return pl.pallas_call(
            functools.partial(_prep_kernel, chunk=chunk, group=group),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(heads // group, t // chunk),
                in_specs=[
                    pl.BlockSpec((group, chunk, dk), live_chunk),
                    pl.BlockSpec((group, chunk, dk), live_chunk),
                    pl.BlockSpec((group, chunk, _NCOLS), live_chunk),
                    pl.BlockSpec(
                        (group, None, 1, 2 * chunk),
                        lambda h, c, live: (h, _live_chunk(c, live), 0, 0)),
                ],
                out_specs=[
                    pl.BlockSpec((group, chunk, chunk), live_chunk)] * 2,
            ),
            out_shape=[jax.ShapeDtypeStruct((heads, t, chunk), jnp.float32),
                       jax.ShapeDtypeStruct((heads, t, chunk), q.dtype)],
            compiler_params=_PARAMS,
            interpret=interpret,
        )(live, q, k, cols, g_rows)


def _steps(live, q, k, v, cols, inv, qk, last_rel, *, chunk, interpret,
           h0=None, row_start=None, row_last=None, want_final=False):
    heads, t, dk = q.shape
    dv = v.shape[2]
    n = t // chunk
    has_init = h0 is not None
    if has_init or want_final:
        row_start = row_start.astype(jnp.int32)
        row_last = row_last.astype(jnp.int32)
        ranges = _row_ranges(row_start, row_last, n, chunk)
        rows = row_start.shape[0]
    else:
        row_start = row_last = jnp.zeros((1,), jnp.int32)
        ranges = (jnp.zeros((n,), jnp.int32),) * 4
        rows = 0
    # with the carry every row's state of the step's heads sits in VMEM: one
    # head a step there
    group = (1 if has_init or want_final
             else _heads_a_step(heads, HEADS_PER_STEP))

    # (grid indices, then the eight prefetched scalars)
    def per_chunk(h, c, live, s0, s1, e0, e1, rs, rl, lr):
        return (h, _live_chunk(c, live), 0)

    def per_chunk_out(h, c, live, s0, s1, e0, e1, rs, rl, lr):
        return (h, c, 0)

    def per_head_rows(h, c, live, s0, s1, e0, e1, rs, rl, lr):
        return (0, h, 0, 0)

    in_specs = [
        pl.BlockSpec((group, chunk, dk), per_chunk),
        pl.BlockSpec((group, chunk, dk), per_chunk),
        pl.BlockSpec((group, chunk, dv), per_chunk),
        pl.BlockSpec((group, chunk, _NCOLS), per_chunk),
        pl.BlockSpec((group, chunk, chunk), per_chunk),
        pl.BlockSpec((group, chunk, chunk), per_chunk),
    ]
    args = [q, k, v, cols, inv, qk]
    if has_init:
        in_specs.append(pl.BlockSpec((rows, group, dk, dv), per_head_rows))
        args.append(h0.astype(jnp.float32))
    out_shape = [jax.ShapeDtypeStruct((heads, t, dv), v.dtype)]
    out_specs = [pl.BlockSpec((group, chunk, dv), per_chunk_out)]
    if want_final:
        out_shape.append(
            jax.ShapeDtypeStruct((rows, heads, dk, dv), jnp.float32))
        out_specs.append(pl.BlockSpec((rows, group, dk, dv), per_head_rows))
    with jax.named_scope(SCAN_SCOPE):
        outs = pl.pallas_call(
            functools.partial(_step_kernel, chunk=chunk, group=group,
                              has_init=has_init, want_final=want_final),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=8,
                grid=(heads // group, n),
                in_specs=in_specs,
                out_specs=out_specs,
                scratch_shapes=[pltpu.VMEM((group, dk, dv), jnp.float32)],
            ),
            out_shape=out_shape,
            compiler_params=_PARAMS,
            interpret=interpret,
        )(live, *ranges, row_start, row_last, last_rel, *args)
    return tuple(outs) if want_final else outs[0]


def gdn_scan(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    seg_start: jax.Array, *, chunk: Optional[int] = None,
    n_real: Optional[jax.Array] = None,
    h0: Optional[jax.Array] = None, row_start: Optional[jax.Array] = None,
    row_last: Optional[jax.Array] = None, output_final_state: bool = False,
    interpret: Optional[bool] = None,
):
    """The gated delta rule per head over a packed token axis.

    ``q``/``k`` (H, T, dk) — already normalised and scaled —, ``v`` (H, T,
    dv), ``g`` (log decay, <= 0) and ``beta`` (H, T) f32, ``seg_start`` (T,)
    int32.  ``T`` must be a multiple of ``chunk`` (64, or ``T`` itself when
    shorter).  Returns ``o`` (H, T, dv) in ``v``'s dtype.

    ``n_real`` (a scalar, traced or not): the axis holds real tokens in its
    first ``n_real`` places and padding — one-token histories that nothing
    reads — after them.  Only the chunks up to the last real token are run;
    ``o`` of the others is zeros.  None: every chunk is run.

    With ``h0`` (R, H, dk, dv) f32 the row that starts at ``row_start[r]``
    starts from ``h0[r]``; with ``output_final_state`` the state after token
    ``row_last[r]`` is returned as well, (R, H, dk, dv) f32.  ``row_start``
    and ``row_last`` (R,) int32 must increase; an entry of ``T`` or more
    names no row.
    """
    t = q.shape[1]
    chunk = chunk or min(CHUNK, t)
    has_init, want_final = h0 is not None, bool(output_final_state)
    if t % chunk:
        raise ValueError(f"{t} tokens are not a multiple of the chunk {chunk}")
    if (has_init or want_final) and (row_start is None or row_last is None):
        raise ValueError("h0 / output_final_state need row_start and row_last")
    interpret = pallas_mode.resolve("gdn_scan", interpret)
    n = t // chunk
    if n_real is None:
        live = jnp.full((1,), n, jnp.int32)
    else:
        live = jnp.clip((jnp.asarray(n_real, jnp.int32) + chunk - 1) // chunk,
                        1, n).reshape(1)
    cols, g_rows, last_rel = _side_inputs(g, beta, seg_start, chunk)
    inv, qk = _prepass(live, q, k, cols, g_rows, chunk=chunk,
                       interpret=interpret)
    return _steps(live, q, k, v, cols, inv, qk, last_rel, chunk=chunk,
                  interpret=interpret, h0=h0, row_start=row_start,
                  row_last=row_last, want_final=want_final)


def scan_chunks(t: int, chunk: Optional[int] = None,
                n_real: Optional[int] = None) -> int:
    """Chunks one head's scan of a ``t``-token axis runs: all of them, or
    with ``n_real`` those up to the last real token."""
    chunk = chunk or min(CHUNK, t)
    if n_real is None:
        return t // chunk
    return min(max(-(-n_real // chunk), 1), t // chunk)


def causal_conv(x: jax.Array, w: jax.Array, positions: jax.Array, *,
                tail: Optional[jax.Array] = None,
                row_of: Optional[jax.Array] = None,
                bias: Optional[jax.Array] = None, scope: str = CONV_SCOPE):
    """Depthwise causal convolution of width ``W`` over each packed history:
    ``y_t = sum_j w[j] x_(t-W+1+j)`` (``+ bias`` (channels,) where given),
    zeros before a history's first event.

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
    with jax.named_scope(scope):
        y = xf * wf[width - 1]
        if bias is not None:
            y = y + bias.astype(jnp.float32)
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
