"""Ulysses-style all-to-all sequence parallelism.

The second of the two standard long-context strategies (the first, ring
attention, lives in ``parallel/ring.py``; the reference has neither —
SURVEY.md §5 "long-context: absent").  Where the ring circulates K/V blocks
around the mesh with ``ppermute`` (n-1 neighbor exchanges, any head count),
Ulysses (cf. DeepSpeed-Ulysses, Jacobs et al. 2023) redistributes ONCE with
``all_to_all``: the sequence-sharded activations are exchanged for
head-sharded ones, every device then runs ordinary full-sequence attention
for its subset of heads, and a second ``all_to_all`` restores sequence
sharding.

Trade-off, for choosing between them:

* **Ulysses**: 2 all-to-alls per attention (4 counting the backward), each
  moving ``T·D/n`` per device — constant in ring steps, so latency is two
  collective hops regardless of mesh size; but it requires
  ``n_heads % axis_size == 0`` and holds the FULL sequence's K/V for its
  heads on every device (memory O(T·D/H_ratio), not O(T/n)).
* **Ring**: O(T/n) memory per device and no head-count constraint, at the
  cost of n-1 ppermute rounds (fully overlappable with block compute).

Per-head attention inside Ulysses is plain local attention, so the Pallas
flash kernel (with its custom VJP) drops in unchanged for long sequences;
the whole construction is differentiable end-to-end (``all_to_all``
transposes to ``all_to_all``), needing no hand-written VJP.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from predictionio_tpu.ops import pallas_mode
from predictionio_tpu.parallel.mesh import MeshContext, shard_map
from predictionio_tpu.parallel.ring import full_attention


@lru_cache(maxsize=32)
def _build_ulysses_fn(mesh, axis: str, causal: bool, scale: Optional[float],
                      ndim: int, use_flash: bool, interpret: bool):
    # dim indices: heads at ndim-3, sequence at ndim-2, features at ndim-1
    h_dim, t_dim = ndim - 3, ndim - 2
    spec = P(*([None] * t_dim + [axis, None]))

    def local(q, k, v):
        # (..., H, T/n, D) --all_to_all--> (..., H/n, T, D)
        def scatter_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=h_dim, concat_axis=t_dim, tiled=True
            )

        def gather_heads(x):
            return jax.lax.all_to_all(
                x, axis, split_axis=t_dim, concat_axis=h_dim, tiled=True
            )

        q, k, v = scatter_heads(q), scatter_heads(k), scatter_heads(v)
        if use_flash:
            from predictionio_tpu.ops.flash_attention import flash_attention

            o = flash_attention(
                q, k, v, causal=causal, scale=scale, interpret=interpret
            )
        else:
            o = full_attention(q, k, v, causal=causal, scale=scale)
        return gather_heads(o)

    return jax.jit(
        shard_map(
            local,
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            # Pallas calls don't annotate varying-across-mesh on their out
            # shapes; skip the vma check like ring.py's flash path
            check_vma=False,
        )
    )


def ulysses_attention(
    ctx: MeshContext,
    q,
    k,
    v,
    axis: str = "data",
    causal: bool = False,
    scale: Optional[float] = None,
    use_flash: Optional[bool] = None,
    interpret: Optional[bool] = None,
):
    """Exact attention with the sequence sharded on mesh axis ``axis``.

    q/k/v: (..., H, T, D) — explicit head dim required (Ulysses shards
    heads); T and H must both be divisible by the axis size.  Inputs may be
    host arrays; the result comes back sharded along T like the inputs.

    ``use_flash`` selects the Pallas kernel for the per-head local
    attention (default: on TPU only); ``interpret`` forces Pallas interpret
    mode (default: off-TPU only).
    """
    n = ctx.axis_size(axis)
    if q.ndim < 3:
        raise ValueError(
            f"ulysses_attention needs (..., H, T, D) inputs, got {q.shape}"
        )
    h, t = q.shape[-3], q.shape[-2]
    if t % n:
        raise ValueError(f"sequence length {t} not divisible by {n} shards")
    if h % n:
        raise ValueError(
            f"n_heads {h} not divisible by axis size {n}: Ulysses shards "
            "heads — use ring attention for head counts below the mesh size"
        )
    if use_flash is None:
        # the local per-head attention sees the FULL sequence after the
        # all_to_all; the shared gate lives next to the kernel
        from predictionio_tpu.ops.flash_attention import use_flash_default

        use_flash = use_flash_default(t)
    # only the flash path has a kernel to run; dense attention has no mode
    interpret = (
        pallas_mode.resolve("ulysses_attention", interpret)
        if use_flash else False
    )
    ndim = q.ndim
    spec = P(*([None] * (ndim - 2) + [axis, None]))
    sharding = ctx.sharding(*spec)
    q, k, v = (jax.device_put(jnp.asarray(x), sharding) for x in (q, k, v))
    fn = _build_ulysses_fn(
        ctx.mesh, axis, causal, scale, ndim, use_flash, interpret
    )
    return fn(q, k, v)
