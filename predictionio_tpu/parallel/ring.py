"""Ring attention: sequence/context parallelism over the device mesh.

The reference has no sequence dimension at all (SURVEY.md §5 "long-context:
absent"), but this framework treats long-context as first-class so sequential
models (e.g. transformer recommenders over long user event histories) scale
past single-chip memory from day one.

Design (standard ring attention, cf. Liu et al. 2023 / the scaling-book
recipe): the sequence axis is sharded over a mesh axis; each device holds one
Q/K/V block. K/V blocks circulate around the ring with ``jax.lax.ppermute``
(ICI neighbor exchanges, overlapping compute) while each device accumulates
its queries' attention over every block using the **online-softmax** update
(running max ``m``, denominator ``l``, numerator ``o``) — numerically exact,
no T×T materialization, O(T_local) memory per device.

``ring_attention`` is the user-facing wrapper (shard_map over the mesh);
``_ring_attention_block`` is the per-device kernel, usable inside other
shard_mapped programs.  Causal masking uses global block offsets so the
result equals single-device causal attention exactly.

``ring_flash_attention`` is the same contract with the Pallas flash kernel
inside each ring step (no (T_local, T_local) score tile is ever
materialized) and a hand-written ring VJP: the forward saves the global
log-sum-exp, and the backward circulates k/v (with their dk/dv
accumulators) around the ring once more, each device adding its block's
exact gradient share — the configuration for genuinely long contexts.
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from predictionio_tpu.ops import pallas_mode
from predictionio_tpu.parallel.mesh import MeshContext, pcast_varying, shard_map

NEG_INF = -1e30


def _ring_attention_block(q, k, v, axis_name: str, n_blocks: int, causal: bool,
                          scale: Optional[float] = None):
    """Per-device ring attention. q,k,v: (..., T_local, D) local blocks."""
    t_local = q.shape[-2]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = my_idx * t_local + jnp.arange(t_local)  # global query positions

    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]

    def body(carry, step):
        o, m, l, k_blk, v_blk = carry
        # block we currently hold started at device (my_idx - step) % n_blocks
        src = (my_idx - step) % n_blocks
        k_pos = src * t_local + jnp.arange(t_local)
        s = jnp.einsum("...qd,...kd->...qk", q, k_blk) * scale
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # rescale previous accumulators to the new max
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum("...qk,...kd->...qd", p, v_blk)
        # pass K/V to the next device in the ring (ICI neighbor exchange)
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o_new, m_new, l_new, k_next, v_next), None

    o0 = jnp.zeros_like(q)
    # constant-initialized carries must be marked varying over the ring axis
    m0 = pcast_varying(
        jnp.full(q.shape[:-1], NEG_INF, q.dtype), axis_name
    )
    l0 = pcast_varying(jnp.zeros(q.shape[:-1], q.dtype), axis_name)
    (o, m, l, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k, v), jnp.arange(n_blocks)
    )
    # fully-masked rows (can't happen with causal self-attention) guard
    return o / jnp.maximum(l, 1e-30)[..., None]


def ring_attention(
    ctx: MeshContext,
    q,
    k,
    v,
    axis: str = "data",
    causal: bool = False,
    scale: Optional[float] = None,
):
    """Exact attention over a sequence sharded on mesh axis ``axis``.

    q/k/v: (..., T, D) with T divisible by the axis size; inputs may be host
    arrays (they are placed sharded along T).  Returns the (..., T, D)
    result sharded the same way.
    """
    n_blocks = ctx.axis_size(axis)
    t = q.shape[-2]
    if t % n_blocks:
        raise ValueError(f"sequence length {t} not divisible by {n_blocks} shards")
    ndim = q.ndim
    spec = P(*([None] * (ndim - 2) + [axis, None]))
    sharding = ctx.sharding(*spec)
    q, k, v = (jax.device_put(jnp.asarray(x), sharding) for x in (q, k, v))
    fn = _build_ring_fn(ctx.mesh, axis, n_blocks, causal, scale, ndim)
    return fn(q, k, v)


@lru_cache(maxsize=64)
def _build_ring_fn(mesh, axis: str, n_blocks: int, causal: bool,
                   scale: Optional[float], ndim: int):
    """Cache the jitted shard_map so repeat calls hit the XLA jit cache."""
    spec = P(*([None] * (ndim - 2) + [axis, None]))
    kernel = partial(
        _ring_attention_block,
        axis_name=axis,
        n_blocks=n_blocks,
        causal=causal,
        scale=scale,
    )
    return jax.jit(
        shard_map(kernel, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    )


# -- ring + Pallas flash blocks: the production long-context configuration --
#
# _ring_attention_block above materializes a (T_local, T_local) score tile
# per ring step; for long local blocks that tile is the VMEM/HBM hot spot.
# The flash composition below never materializes it: each ring step runs the
# Pallas flash kernel on the (q_local, k_blk) pair and merges the
# (o, logsumexp) pair across steps — mathematically the same online softmax,
# tiled on the MXU. The backward is the standard ring backward: with the
# GLOBAL lse saved from the forward, each block's Pallas backward yields
# exactly its share of dq/dk/dv; dk/dv accumulators travel around the ring
# with their k/v blocks and arrive home after n steps.


def _ring_causal_switch(src, my_idx, full_fn, diag_fn, skip_fn):
    """Dispatch a ring step by block relation: past=full, self=diag, future=skip."""
    branch = jnp.where(src == my_idx, 1, jnp.where(src < my_idx, 0, 2))
    return jax.lax.switch(branch, (full_fn, diag_fn, skip_fn), None)


def _ring_flash_fwd_impl(q, k, v, axis_name, n_blocks, causal, scale,
                         block_q, block_k, interpret):
    from predictionio_tpu.ops.flash_attention import flash_block_fwd

    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]

    def step_fn(carry, step):
        o, lse, k_blk, v_blk = carry
        src = (my_idx - step) % n_blocks

        def full(_):
            return flash_block_fwd(
                q, k_blk, v_blk, False, scale, block_q, block_k, interpret
            )

        def diag(_):
            return flash_block_fwd(
                q, k_blk, v_blk, True, scale, block_q, block_k, interpret
            )

        def skip(_):
            return (
                jnp.zeros_like(q),
                jnp.full(q.shape[:-1], NEG_INF, jnp.float32),
            )

        if causal:
            o_b, lse_b = _ring_causal_switch(src, my_idx, full, diag, skip)
        else:
            o_b, lse_b = full(None)
        lse_new = jnp.logaddexp(lse, lse_b)
        w_old = jnp.exp(lse - lse_new)
        w_new = jnp.exp(lse_b - lse_new)
        # accumulate in f32 whatever the input dtype (stable scan carry)
        o = o * w_old[..., None] + o_b.astype(jnp.float32) * w_new[..., None]
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        return (o, lse_new, k_next, v_next), None

    o0 = jnp.zeros(q.shape, jnp.float32)
    # no pcast here (unlike _ring_attention_block): this kernel runs under
    # check_vma=False, where constants need no varying annotation
    lse0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    (o, lse, _, _), _ = jax.lax.scan(
        step_fn, (o0, lse0, k, v), jnp.arange(n_blocks)
    )
    return o.astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_flash(q, k, v, axis_name, n_blocks, causal, scale, block_q,
                block_k, interpret):
    o, _ = _ring_flash_fwd_impl(
        q, k, v, axis_name, n_blocks, causal, scale, block_q, block_k,
        interpret,
    )
    return o


def _ring_flash_fwd(q, k, v, axis_name, n_blocks, causal, scale, block_q,
                    block_k, interpret):
    o, lse = _ring_flash_fwd_impl(
        q, k, v, axis_name, n_blocks, causal, scale, block_q, block_k,
        interpret,
    )
    return o, (q, k, v, o, lse)


def _ring_flash_bwd(axis_name, n_blocks, causal, scale, block_q, block_k,
                    interpret, res, do):
    from predictionio_tpu.ops.flash_attention import flash_block_bwd

    q, k, v, o, lse = res
    my_idx = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_blocks) for j in range(n_blocks)]

    def step_fn(carry, step):
        dq, k_blk, v_blk, dk_blk, dv_blk = carry
        src = (my_idx - step) % n_blocks

        def full(_):
            return flash_block_bwd(
                q, k_blk, v_blk, o, lse, do, False, scale, block_q, block_k,
                interpret,
            )

        def diag(_):
            return flash_block_bwd(
                q, k_blk, v_blk, o, lse, do, True, scale, block_q, block_k,
                interpret,
            )

        def skip(_):
            return (
                jnp.zeros_like(q),
                jnp.zeros_like(k_blk),
                jnp.zeros_like(v_blk),
            )

        if causal:
            dq_c, dk_c, dv_c = _ring_causal_switch(
                src, my_idx, full, diag, skip
            )
        else:
            dq_c, dk_c, dv_c = full(None)
        # f32 accumulation whatever the input dtype (same stable-carry rule
        # as the forward's o): bf16 += per-block shares would round at every
        # ring step
        dq = dq + dq_c.astype(jnp.float32)
        dk_blk = dk_blk + dk_c.astype(jnp.float32)
        dv_blk = dv_blk + dv_c.astype(jnp.float32)
        # dk/dv ride the ring WITH their k/v block: after n steps each
        # block's accumulated gradient is back at its owner
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        dk_next = jax.lax.ppermute(dk_blk, axis_name, perm)
        dv_next = jax.lax.ppermute(dv_blk, axis_name, perm)
        return (dq, k_next, v_next, dk_next, dv_next), None

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    (dq, _, _, dk, dv), _ = jax.lax.scan(
        step_fn, (dq0, k, v, dk0, dv0), jnp.arange(n_blocks)
    )
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


@lru_cache(maxsize=64)
def _build_ring_flash_fn(mesh, axis: str, n_blocks: int, causal: bool,
                         scale: float, ndim: int, block_q: int, block_k: int,
                         interpret: bool):
    spec = P(*([None] * (ndim - 2) + [axis, None]))
    kernel = partial(
        _ring_flash,
        axis_name=axis,
        n_blocks=n_blocks,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        interpret=interpret,
    )
    return jax.jit(
        shard_map(
            kernel,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # pallas_call out_shapes carry no vma annotation; the kernel's
            # collectives are hand-placed, so skip the vma checker here
            check_vma=False,
        )
    )


def ring_flash_attention(
    ctx: MeshContext,
    q,
    k,
    v,
    axis: str = "data",
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Exact attention, sequence-sharded over ``axis``, Pallas inside.

    Same contract as :func:`ring_attention` (forward AND backward, via the
    hand-written ring VJP) but each ring step runs the flash kernel instead
    of materializing a (T_local, T_local) score tile — the configuration
    for genuinely long contexts on TPU.
    """
    from predictionio_tpu.ops.flash_attention import BLOCK_K, BLOCK_Q

    n_blocks = ctx.axis_size(axis)
    t = q.shape[-2]
    if t % n_blocks:
        raise ValueError(f"sequence length {t} not divisible by {n_blocks} shards")
    t_local = t // n_blocks
    bq = min(block_q or BLOCK_Q, t_local)
    bk = min(block_k or BLOCK_K, t_local)
    if t_local % bq or t_local % bk:
        raise ValueError(
            f"flash block sizes ({bq}, {bk}) must divide local block length {t_local}"
        )
    interpret = pallas_mode.resolve("ring_flash_attention", interpret)
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    ndim = q.ndim
    spec = P(*([None] * (ndim - 2) + [axis, None]))
    sharding = ctx.sharding(*spec)
    q, k, v = (jax.device_put(jnp.asarray(x), sharding) for x in (q, k, v))
    fn = _build_ring_flash_fn(
        ctx.mesh, axis, n_blocks, causal, scale, ndim, bq, bk, interpret
    )
    return fn(q, k, v)


def full_attention(q, k, v, causal: bool = False, scale: Optional[float] = None):
    """Single-device reference implementation (tests / small inputs)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d**0.5)
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)
