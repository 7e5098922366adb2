"""Pallas flash attention: the on-chip kernel for long-context blocks.

The long-context serving path (ring attention, ``parallel/ring.py``) computes
dense (T_local × T_local) score blocks per device; past a few thousand
positions that intermediate dominates VMEM/HBM traffic.  This module provides
the classic flash-attention formulation as a Pallas TPU kernel: the grid is
(q_blocks, k_blocks) with the K dimension iterated innermost, so each K/V
**block** streams through VMEM while the (o, m, l) online-softmax
accumulators persist in VMEM scratch across the K sweep — full K/V never
resides on-chip, so context length is bounded by HBM, not VMEM.

``flash_attention`` is numerically exact (float32 accumulators) and falls
back to interpret mode off-TPU, so the CPU test mesh exercises the identical
kernel code.  Callers dispatch explicitly (see the gate in
``models/sequential.py``: dense attention off-TPU or for short blocks,
``flash_attention`` for long blocks on TPU — training included).

Differentiable: a ``jax.custom_vjp`` supplies the standard
recomputation-form backward (FlashAttention-2 style).  The forward kernel
additionally emits the per-row logsumexp; the backward recomputes each
(q_block, k_block) score tile from Q/K + logsumexp instead of storing the
(T × T) probability matrix, as two Pallas kernels: dQ sweeps K blocks
innermost (dq accumulates in VMEM), dK/dV sweeps Q blocks innermost.
Training memory is O(T·D), not O(T²).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode

NEG_INF = -1e30

# (sublane, lane)-friendly defaults; one Q×K score block fits VMEM easily
BLOCK_Q = 128
BLOCK_K = 128


def use_flash_default(t: int) -> bool:
    """The one gate policy for 'should this sequence take the Pallas path':
    long 128-aligned blocks on TPU; short blocks and CPU stay dense
    (interpret-mode flash loses on CPU).  Shared by the sequential model
    and Ulysses so the threshold cannot drift between call sites."""
    return t >= 256 and t % BLOCK_Q == 0 and jax.default_backend() == "tpu"


def _causal_mask(qi, ki, block_q, block_k):
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return q_pos >= k_pos


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
    causal: bool, scale: float, block_q: int, block_k: int
):
    qi = pl.program_id(0)
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[...].astype(jnp.float32) * scale  # (block_q, d)
    k = k_ref[...].astype(jnp.float32)  # (block_k, d) — this K block only
    v = v_ref[...].astype(jnp.float32)
    s = q @ k.T  # MXU
    if causal:
        s = jnp.where(_causal_mask(qi, ki, block_q, block_k), s, NEG_INF)
    m_prev, l_prev = m_ref[...], l_ref[...]
    m_blk = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    m_ref[...] = m_new
    l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v

    @pl.when(ki == n_k - 1)
    def _finalize():
        l_final = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_final[:, None]).astype(o_ref.dtype)
        # per-row logsumexp, saved for the recomputation backward
        lse_ref[...] = (m_ref[...] + jnp.log(l_final))[:, None]


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def _flash_2d_res(q, k, v, causal, scale, block_q, block_k, interpret):
    """Forward returning (o, lse); lse feeds the recomputation backward."""
    t_q, d = q.shape
    t_kv = k.shape[0]
    grid = (t_q // block_q, t_kv // block_k)  # K innermost: accumulators carry
    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
            pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
            pl.BlockSpec((block_q, 1), lambda qi, ki: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_q, d), q.dtype),
            jax.ShapeDtypeStruct((t_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, acc_ref, *,
    causal: bool, scale: float, block_q: int, block_k: int
):
    qi = pl.program_id(0)
    ki = pl.program_id(1)
    n_k = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    s = (q * scale) @ k.T
    if causal:
        s = jnp.where(_causal_mask(qi, ki, block_q, block_k), s, NEG_INF)
    p = jnp.exp(s - lse_ref[...])  # (block_q, block_k); masked rows → 0
    dp = do @ v.T
    ds = p * (dp - delta_ref[...])
    acc_ref[...] += ds @ k

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[...] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, causal: bool, scale: float, block_q: int, block_k: int
):
    ki = pl.program_id(0)
    qi = pl.program_id(1)
    n_q = pl.num_programs(1)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    s = (q * scale) @ k.T
    if causal:
        s = jnp.where(_causal_mask(qi, ki, block_q, block_k), s, NEG_INF)
    p = jnp.exp(s - lse_ref[...])
    dv_acc[...] += p.T @ do
    dp = do @ v.T
    ds = p * (dp - delta_ref[...])
    dk_acc[...] += (ds.T @ q) * scale

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def _flash_2d_bwd(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                  interpret):
    t_q, d = q.shape
    t_kv = k.shape[0]
    # D_i = Σ_d dO·O — the softmax-Jacobian row term (plain XLA, one pass)
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )
    common = dict(causal=causal, scale=scale, block_q=block_q, block_k=block_k)
    q_specs = [
        pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
        pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
        pl.BlockSpec((block_k, d), lambda qi, ki: (ki, 0)),
        pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
        pl.BlockSpec((block_q, 1), lambda qi, ki: (qi, 0)),
        pl.BlockSpec((block_q, 1), lambda qi, ki: (qi, 0)),
    ]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(t_q // block_q, t_kv // block_k),  # K innermost
        in_specs=q_specs,
        out_specs=pl.BlockSpec((block_q, d), lambda qi, ki: (qi, 0)),
        out_shape=jax.ShapeDtypeStruct((t_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    kv_specs = [
        pl.BlockSpec((block_q, d), lambda ki, qi: (qi, 0)),
        pl.BlockSpec((block_k, d), lambda ki, qi: (ki, 0)),
        pl.BlockSpec((block_k, d), lambda ki, qi: (ki, 0)),
        pl.BlockSpec((block_q, d), lambda ki, qi: (qi, 0)),
        pl.BlockSpec((block_q, 1), lambda ki, qi: (qi, 0)),
        pl.BlockSpec((block_q, 1), lambda ki, qi: (qi, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(t_kv // block_k, t_q // block_q),  # Q innermost
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((block_k, d), lambda ki, qi: (ki, 0)),
            pl.BlockSpec((block_k, d), lambda ki, qi: (ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t_kv, d), k.dtype),
            jax.ShapeDtypeStruct((t_kv, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_2d(q, k, v, causal, scale, block_q, block_k, interpret):
    o, _ = _flash_2d_res(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _flash_2d_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_2d_res(q, k, v, causal, scale, block_q, block_k, interpret)
    return o, (q, k, v, o, lse)


def _flash_2d_vjp(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    return _flash_2d_bwd(
        q, k, v, o, lse, do, causal, scale, block_q, block_k, interpret
    )


_flash_2d.defvjp(_flash_2d_fwd, _flash_2d_vjp)


def flash_block_fwd(
    q, k, v, causal: bool, scale: float, block_q: int, block_k: int,
    interpret: bool,
):
    """One block-pair forward returning (o, lse); q/k/v: (..., T, D).

    ``o`` is the softmax-normalized attention of q over THIS k/v block and
    ``lse`` (..., T) its log-sum-exp — the pair composes across blocks via
    ``logaddexp`` merging, which is how ring attention stitches a global
    result out of per-block Pallas calls (parallel/ring.py).
    """
    fn = functools.partial(
        _flash_2d_res,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    for _ in range(q.ndim - 2):
        fn = jax.vmap(fn)
    o, lse = fn(q, k, v)
    return o, lse[..., 0]


def flash_block_bwd(
    q, k, v, o, lse, do, causal: bool, scale: float, block_q: int,
    block_k: int, interpret: bool,
):
    """One block-pair backward: (dq, dk, dv) contributions.

    ``o`` and ``lse`` are the GLOBAL (all-blocks) forward results for these
    queries — with a global lse, ``exp(s - lse)`` inside the kernels is the
    globally-normalized probability of this block, so the returned pieces
    are exactly this block's share of the full gradients (ring backward).
    ``lse``: (..., T).
    """
    fn = functools.partial(
        _flash_2d_bwd,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    for _ in range(q.ndim - 2):
        fn = jax.vmap(fn)
    return fn(q, k, v, o, lse[..., None], do)


def flash_attention(
    q,
    k,
    v,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: Optional[bool] = None,
):
    """Exact attention via the Pallas kernel. q/k/v: (..., T, D).

    T must divide by the block sizes (pad beforehand for ragged lengths).
    ``interpret`` defaults to True off-TPU so tests run the kernel anywhere.
    """
    interpret = pallas_mode.resolve("flash_attention", interpret)
    t_q, d = q.shape[-2], q.shape[-1]
    t_kv = k.shape[-2]
    block_q = min(block_q, t_q)
    block_k = min(block_k, t_kv)
    if t_q % block_q or t_kv % block_k:
        raise ValueError(
            f"sequence lengths ({t_q}, {t_kv}) must divide block sizes "
            f"({block_q}, {block_k})"
        )
    scale = scale if scale is not None else 1.0 / (d**0.5)
    fn = functools.partial(
        _flash_2d,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    for _ in range(q.ndim - 2):
        fn = jax.vmap(fn)
    return fn(q, k, v)


# -- packed histories (serving, forward only) ---------------------------------

PACKED_SCOPE = "pio.packed_attention"
PACKED_BLOCK = 256
_LANES = 128


def _packed_kernel(lo_ref, q_ref, start_ref, k_ref, v_ref, o_ref, acc_ref,
                   m_ref, l_ref, *, scale: float, block: int):
    qi = pl.program_id(1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[...]
    q_pos = qi * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 0)
    q_start = start_ref[...][:, :1]  # (block, 1)

    def step(kb, carry):
        at = pl.multiple_of(kb * block, block)
        k = k_ref[pl.ds(at, block), :]
        v = v_ref[pl.ds(at, block), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        k_pos = at + jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
        mask = (k_pos <= q_pos) & (k_pos >= q_start)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # a row with no key in this block keeps m at NEG_INF: exp(0) must
        # not count its masked entries
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(lo_ref[qi], qi + 1, step, 0)
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def packed_causal_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, seg_start: jax.Array, *,
    scale: Optional[float] = None, block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Plain multi-head ``softmax(q.k * scale).v`` over several histories
    PACKED into one token axis, each causal within itself (forward only).

    ``q``/``k``/``v`` (H, T, d); ``seg_start[t]`` (T,) int32 is the index of
    the first token of token ``t``'s history (histories contiguous, in
    order; a padded token is a history of its own), so token ``t`` attends
    to ``seg_start[t] <= s <= t``.  ``T`` must be a multiple of the block
    (256, or ``T`` itself when shorter).

    The layout of THIS call and of ``ops/latent_attention.py`` — not of
    packed attention in general: :func:`packed_grouped_attention` below
    streams its key blocks — is grid ``(heads, q_blocks)``, a head's whole
    K and V in VMEM while its query blocks sweep (``T x d`` twice a head,
    4 MB at 8,192 tokens of 128: VMEM bounds ``T`` here), the loop over key
    blocks INSIDE the kernel from the block that holds the start of the
    query block's first history to the diagonal — blocks above the diagonal
    or wholly in other histories cost neither a grid step nor a DMA.
    """
    heads, t, d = q.shape
    block = block or min(PACKED_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not a multiple of the block {block}")
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    interpret = pallas_mode.resolve("packed_attention", interpret)
    # first key block each query block needs (starts never decrease)
    lo = (seg_start[::block] // block).astype(jnp.int32)
    start_lanes = jnp.broadcast_to(
        seg_start.astype(jnp.int32)[:, None], (t, _LANES))

    def per_q(h, qi, lo):
        return (h, qi, 0)

    def per_head(h, qi, lo):
        return (h, 0, 0)

    with jax.named_scope(PACKED_SCOPE):
        return pl.pallas_call(
            functools.partial(_packed_kernel, scale=scale, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(heads, t // block),
                in_specs=[
                    pl.BlockSpec((None, block, d), per_q),
                    pl.BlockSpec((block, _LANES), lambda h, qi, lo: (qi, 0)),
                    pl.BlockSpec((None, t, d), per_head),
                    pl.BlockSpec((None, t, d), per_head),
                ],
                out_specs=pl.BlockSpec((None, block, d), per_q),
                scratch_shapes=[
                    pltpu.VMEM((block, d), jnp.float32),
                    pltpu.VMEM((block, 1), jnp.float32),
                    pltpu.VMEM((block, 1), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((heads, t, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary"),
                vmem_limit_bytes=64 * 1024 * 1024,
            ),
            interpret=interpret,
        )(lo, q, start_lanes, k, v)


# -- packed histories, grouped heads, an optional window (serving) ------------

WINDOW_SCOPE = "pio.window_attention"
GLOBAL_SCOPE = "pio.global_attention"


def sweep_blocks(seg_start: jax.Array, block: int,
                 window: Optional[int] = None):
    """Per query block of a packed axis, the first key block its sweep
    visits (it ends at the diagonal): ``lo``, the block that holds the
    lowest key any of its queries may see — ``max(seg_start[t], t - window
    + 1)`` of its FIRST query, as neither term ever decreases along the
    axis — and ``to_start``, the block of that query's history's first
    token: where a sweep that knew no window would begin.  Both (T / block,)
    int32; equal without a window."""
    first = seg_start[::block].astype(jnp.int32)
    to_start = first // block
    if window is None:
        return to_start, to_start
    q0 = jnp.arange(first.shape[0], dtype=jnp.int32) * block
    return jnp.maximum(first, q0 - (window - 1)) // block, to_start


def sweep_steps(t: int, block: int, window: Optional[int] = None) -> int:
    """Key blocks one query block can need at most: the grid's inner axis."""
    n_q = t // block
    if window is None:
        return n_q
    return min(n_q, -(-(window - 1) // block) + 1)


def _grouped_kernel(lo_ref, q_ref, low_ref, k_ref, v_ref, o_ref, acc_ref,
                    m_ref, l_ref, *, scale: float, block: int, group: int):
    qi, j = pl.program_id(1), pl.program_id(2)
    kb = lo_ref[qi] + j  # the key block of this step

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(kb <= qi)  # past the diagonal: the same block again, no work
    def _step():
        k, v = k_ref[...], v_ref[...]
        q_pos = qi * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 0)
        k_pos = kb * block + jax.lax.broadcasted_iota(
            jnp.int32, (block, block), 1)
        # one mask for the group's heads: they share keys and positions
        mask = (k_pos <= q_pos) & (k_pos >= low_ref[...][:, :1])
        for g in range(group):
            s = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # a row with no key in this block keeps m at NEG_INF: exp(0)
            # must not count its masked entries
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            m_ref[g] = m_new
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def packed_grouped_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, seg_start: jax.Array, *,
    window: Optional[int] = None, scale: Optional[float] = None,
    block: Optional[int] = None, interpret: Optional[bool] = None,
) -> jax.Array:
    """Grouped-query ``softmax(q.k * scale).v`` over histories PACKED into
    one token axis, causal within a history and, with ``window``, blind
    beyond the ``window`` most recent keys (forward only).

    ``q`` (Hq, T, d); ``k``/``v`` (Hkv, T, d), ``Hq`` a multiple of ``Hkv``:
    query head ``h`` reads key/value head ``h // (Hq / Hkv)``.
    ``seg_start`` as :func:`packed_causal_attention`'s; token ``t`` attends
    to ``max(seg_start[t], t - window + 1) <= s <= t``.  ``T`` must be a
    multiple of the block (256, or ``T`` itself when shorter).

    Grid ``(kv heads, query blocks, key steps)``: a step brings ONE key and
    one value block into VMEM (never a head's whole K and V: ``T`` is
    bounded by HBM) and all ``Hq / Hkv`` query heads of the group use it, so
    K and V cross HBM once per kv head and query block, not once per query
    head.  A query block's steps start at :func:`sweep_blocks`'s ``lo`` —
    the block that holds the lowest key the mask lets it see — and end at
    the diagonal: with a window of 4,096 and blocks of 256 that is at most
    17 key blocks however long the history, and :func:`sweep_steps` is the
    grid's inner extent.  A step past the diagonal names the diagonal's
    block again (no DMA) and does nothing.  The op is named
    ``pio.window_attention`` with a window and ``pio.global_attention``
    without.
    """
    hq, t, d = q.shape
    hkv = k.shape[0]
    if hq % hkv or k.shape != (hkv, t, d) or v.shape != k.shape:
        raise ValueError(
            f"q {q.shape} / k {k.shape} / v {v.shape}: the query heads must "
            "be a multiple of the key/value heads, on one token axis")
    group = hq // hkv
    block = block or min(PACKED_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not a multiple of the block {block}")
    if window is not None and window < 1:
        raise ValueError(f"window {window}: at least the token itself")
    scale = float(scale if scale is not None else 1.0 / (d ** 0.5))
    interpret = pallas_mode.resolve(
        "window_attention" if window is not None else "global_attention",
        interpret)
    lo, _ = sweep_blocks(seg_start, block, window)
    low = seg_start.astype(jnp.int32)
    if window is not None:
        low = jnp.maximum(low, jnp.arange(t, dtype=jnp.int32) - (window - 1))
    low_lanes = jnp.broadcast_to(low[:, None], (t, _LANES))

    def group_blocks(h, qi, j, lo):
        return (h, 0, qi, 0)

    def key_block(h, qi, j, lo):
        return (h, jnp.minimum(lo[qi] + j, qi), 0)

    with jax.named_scope(WINDOW_SCOPE if window is not None
                         else GLOBAL_SCOPE):
        o = pl.pallas_call(
            functools.partial(_grouped_kernel, scale=scale, block=block,
                              group=group),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(hkv, t // block, sweep_steps(t, block, window)),
                in_specs=[
                    pl.BlockSpec((None, group, block, d), group_blocks),
                    pl.BlockSpec((block, _LANES),
                                 lambda h, qi, j, lo: (qi, 0)),
                    pl.BlockSpec((None, block, d), key_block),
                    pl.BlockSpec((None, block, d), key_block),
                ],
                out_specs=pl.BlockSpec((None, group, block, d), group_blocks),
                scratch_shapes=[
                    pltpu.VMEM((group, block, d), jnp.float32),
                    pltpu.VMEM((group, block, 1), jnp.float32),
                    pltpu.VMEM((group, block, 1), jnp.float32),
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((hkv, group, t, d), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary"),
            ),
            interpret=interpret,
        )(lo, q.reshape(hkv, group, t, d), low_lanes, k, v)
    return o.reshape(hq, t, d)
