"""Position-wise work over a packed token axis, run only where a real
token lies.

A sequence program is compiled for a rung of ``T`` tokens and a dispatch
fills the first ``n_real`` of them (``models/latent_moe.pack`` lays rows end
to end from token 0, so the padding is always a tail).  The kernels this
package wrote skip that tail themselves; what XLA compiles — projections,
feed-forwards, norms, gates, rotary embedding — runs over the whole rung
unless told otherwise.  :func:`over_real_tiles` tells it: a segment of
position-wise work runs tile by tile under a loop whose trip count is
``ceil(n_real / tile)``, and the tiles past the last real token are never
computed (their rows of the result are zeros: no model's output).

WHICH rungs run so is the caller's to say (a model knows its ladder): a rung
it runs whole never comes here, and its program is what it would be without
this module.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# Tokens a tile.  A pass over one tile streams the segment's weights from
# HBM once, so a tile must hold enough tokens for the products to hide that:
# a bf16 weight pass is compute-bound above 197 TFLOP/s / 819 GB/s = 240
# tokens (v5e).  512 is twice that ridge, and the cheapest rung per token in
# every family's per-rung table (PERF.md section 5); below 256 a tile would
# be weight-bound, above 1,024 the top rungs' (T, F) f32 intermediates cross
# HBM again.  A constant of the program, not of any configuration.
DENSE_TILE = 512


def dense_tiles(t_pad: int, n_real, tile: int = DENSE_TILE):
    """Tiles of a rung of ``t_pad`` tokens that hold one of its first
    ``n_real`` (an int, or a traced scalar): the loop's trip count in the
    program, and what a family's counter adds on the host."""
    if t_pad % tile:
        raise ValueError(f"tiles of {tile} do not divide a rung of {t_pad}")
    return (n_real + tile - 1) // tile


def over_real_tiles(fn, n_real, *xs, tile: int = DENSE_TILE, in_axes=0,
                    out_axes=0):
    """``fn(*xs)`` on the tiles of the token axis that hold a real token,
    zeros beyond.

    ``fn`` is position-wise: it maps arrays with a token axis to a pytree of
    arrays with a token axis, and a token's part of the result depends on
    that token's part of ``xs`` alone (what else it reads — weights — it
    closes over).  ``in_axes`` / ``out_axes`` say where the token axis lies,
    one int for all or one per argument / per output leaf (``vmap``'s
    convention, without ``None``).  ``n_real``: a traced scalar; the tokens
    from there on are padding.

    A ``fori_loop`` with a traced bound (a ``while`` once lowered) over
    ``ceil(n_real / tile)`` tiles, each sliced out of ``xs`` and written
    into a zeroed result, so ``fn``'s intermediates exist at a tile's size
    only.  ``tile`` must divide the token axis.

    NOT for the body of a ``lax.scan`` over stacked weights: the result is
    right there too, but the scan hands its body the layer's slices and the
    loop takes them as operands, that is as COPIES (0.66 GB a layer of the
    widths served, read on the chip as 0.73 GB of temporaries against 0.09).
    A slice at a traced index inside the loop is hoisted out of it to the
    same end; unrolling the depth and slicing at static indices avoids the
    copies but compiles and loads a program a layer (``PERF.md`` section 6,
    PR 42).  The packed families whose depth is a scan do not call this."""
    in_axes = _per_leaf(in_axes, xs)
    t = xs[0].shape[in_axes[0]]

    def take(i):
        return [lax.dynamic_slice_in_dim(x, i * tile, tile, ax)
                for x, ax in zip(xs, in_axes)]

    one, treedef = jax.tree.flatten(jax.eval_shape(fn, *[
        jax.ShapeDtypeStruct(x.shape[:ax] + (tile,) + x.shape[ax + 1:],
                             x.dtype) for x, ax in zip(xs, in_axes)]))
    axes = _per_leaf(out_axes, one)

    def body(i, outs):
        got = jax.tree.leaves(fn(*take(i)))
        return [lax.dynamic_update_slice_in_dim(o, g, i * tile, ax)
                for o, g, ax in zip(outs, got, axes)]

    # zeros, not `lax.empty` and a second loop that zeroes what the first
    # left: XLA copies a result whole between two loops, dearer than a fill
    init = [jnp.zeros(s.shape[:ax] + (t,) + s.shape[ax + 1:], s.dtype)
            for s, ax in zip(one, axes)]
    outs = lax.fori_loop(0, dense_tiles(t, n_real, tile), body, init)
    return treedef.unflatten(outs)


def real_tiles(n_real, tile: int = DENSE_TILE):
    """:func:`over_real_tiles` with a dispatch's ``n_real`` and the tile
    bound: what a model's blocks are handed as ``tiles``."""
    def tiles(fn, *xs, in_axes=0, out_axes=0):
        return over_real_tiles(fn, n_real, *xs, tile=tile, in_axes=in_axes,
                               out_axes=out_axes)

    return tiles


def _per_leaf(axes, leaves) -> tuple:
    if isinstance(axes, int):
        return (axes,) * len(leaves)
    axes = tuple(axes)
    if len(axes) != len(leaves):
        raise ValueError(f"{len(axes)} axes for {len(leaves)} arrays")
    return axes
