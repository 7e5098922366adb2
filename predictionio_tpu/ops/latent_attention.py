"""Pallas attention for latent-attention blocks over PACKED histories.

What ``ops/flash_attention.py`` cannot take and a latent-attention layer
needs: a q·k product over two parts of different widths — a per-head part
(``q_nope · k_nope``) and a rotary part whose key is ONE vector per
position shared by every head (``q_rope · k_rope``) — a value width that
differs from the q/k width, and several users' histories packed into one
token axis, each causal within itself.  Forward only (serving).

Layout: grid ``(heads, q_blocks)``.  A head's whole K and V (a few MB at
8,192 tokens) sit in VMEM while its query blocks sweep; the loop over key
blocks runs INSIDE the kernel, from the block that holds the start of the
query block's first history to the diagonal, so blocks above the diagonal
or wholly in other histories cost nothing — neither a grid step nor a DMA.
The ``(heads, T, T)`` score tensor never exists: one ``(block, block)``
f32 tile at a time, online softmax in f32.

``seg_start[t]`` is the index of the first token of the history token ``t``
belongs to (histories are contiguous, in order); a padded token is a
history of its own.  Token ``t`` attends to ``seg_start[t] <= s <= t``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode

ATTN_SCOPE = "pio.mla_attention"
NEG_INF = -1e30
BLOCK = 256
_LANES = 128


def _kernel(lo_ref, qn_ref, qr_ref, start_ref, kn_ref, kr_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, block: int):
    qi = pl.program_id(1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    qn = qn_ref[...]
    qr = qr_ref[...]
    q_pos = qi * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, block), 0)
    q_start = start_ref[...][:, :1]  # (block, 1)
    dims = (((1,), (1,)), ((), ()))

    def step(kb, carry):
        at = pl.multiple_of(kb * block, block)
        kn = kn_ref[pl.ds(at, block), :]
        kr = kr_ref[pl.ds(at, block), :]
        v = v_ref[pl.ds(at, block), :]
        s = jax.lax.dot_general(
            qn, kn, dims, preferred_element_type=jnp.float32)
        s += jax.lax.dot_general(
            qr, kr, dims, preferred_element_type=jnp.float32)
        s *= scale
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


def _call(q_nope, q_rope, k_nope, k_rope, v, seg_start, scale, block,
          interpret):
    heads, t, d_nope = q_nope.shape
    d_rope = q_rope.shape[2]
    d_v = v.shape[2]
    n_q = t // block
    # first key block each query block needs: its first token's history
    # start (starts never decrease along the axis)
    lo = (seg_start[::block] // block).astype(jnp.int32)
    start_lanes = jnp.broadcast_to(
        seg_start.astype(jnp.int32)[:, None], (t, _LANES))

    def per_q(h, qi, lo):
        return (h, qi, 0)

    def per_head(h, qi, lo):
        return (h, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(heads, n_q),
            in_specs=[
                pl.BlockSpec((None, block, d_nope), per_q),
                pl.BlockSpec((None, block, d_rope), per_q),
                pl.BlockSpec((block, _LANES), lambda h, qi, lo: (qi, 0)),
                pl.BlockSpec((None, t, d_nope), per_head),
                pl.BlockSpec((t, d_rope), lambda h, qi, lo: (0, 0)),
                pl.BlockSpec((None, t, d_v), per_head),
            ],
            out_specs=pl.BlockSpec((None, block, d_v), per_q),
            scratch_shapes=[
                pltpu.VMEM((block, d_v), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
                pltpu.VMEM((block, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((heads, t, d_v), q_nope.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        interpret=interpret,
    )(lo, q_nope, q_rope, start_lanes, k_nope, k_rope, v)


def mla_attention(
    q_nope: jax.Array, q_rope: jax.Array, k_nope: jax.Array,
    k_rope: jax.Array, v: jax.Array, seg_start: jax.Array, *,
    scale: float, block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``softmax((q_nope·k_nope + q_rope·k_rope) * scale) · v`` per head,
    causal within each packed history.

    ``q_nope``/``k_nope`` (H, T, d_nope), ``q_rope`` (H, T, d_rope),
    ``k_rope`` (T, d_rope) shared by every head, ``v`` (H, T, d_v),
    ``seg_start`` (T,) int32.  ``T`` must be a multiple of the block (256,
    or ``T`` itself when shorter).  Returns (H, T, d_v) in q's dtype.
    """
    t = q_nope.shape[1]
    block = block or min(BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens are not a multiple of the block {block}")
    interpret = pallas_mode.resolve("mla_attention", interpret)
    with jax.named_scope(ATTN_SCOPE):
        return _call(q_nope, q_rope, k_nope, k_rope, v, seg_start,
                     float(scale), block, interpret)
