"""Sparse-expert feed-forward: routing and grouped expert products.

Two device stages, each under a ``jax.named_scope``.  A TPU trace names a
Pallas call after the innermost scope, so the three grouped products of a
layer appear as ``pio.moe_experts``; XLA fusions keep ``fusion.N`` there, so
routing (``pio.moe_route``) and the sort, gather and combine around the
products are in the HLO's metadata but cannot be found by name in a trace:

* ``pio.moe_route`` — :func:`route_sigmoid_topk`: ``sigma = sigmoid(x @ W_g)``
  in f32, the ``top_k`` largest of ``sigma + bias`` are picked (the bias
  SELECTS), the weights are the unbiased ``sigma`` of the picked experts
  (the bias never WEIGHS), normalised and scaled.  Beside it, under the
  same scope, :func:`route_topk_softmax`: the ``top_k`` largest LOGITS are
  picked (no bias) and the weights are a softmax over the picked alone.
* ``pio.moe_experts`` — :func:`expert_products`: the ``T * top_k``
  (token, expert) assignments are sorted by expert, each expert's rows go
  through its SwiGLU as one group of a grouped matmul, and the results are
  gathered back and combined with the routing weights.  Every assignment
  to an expert whose weights are HERE is computed: there is no capacity and
  no dropped token, whatever the skew.  Padded tokens (``valid == False``)
  are sorted past the last expert and belong to no group, so they touch no
  expert's weights.

The layer is told which experts it holds: ``w1 / w3 / w2`` are the
contiguous slice ``[first, first + n_held)`` of the ``n_experts`` the router
scores (one rank's share under expert parallelism; ``first = 0`` and
``n_held = n_experts``, the default, is a layer that holds them all).
Routing is always over all ``n_experts``.  An assignment to an expert held
elsewhere is sorted past the last group exactly as a padded token is: it
touches no weight and adds nothing here — what it would add is the other
ranks' part of the sum, and nothing in this module stands in for them or
for their exchange.  The rows gathered for the products are then bounded
by what can be local (:func:`local_row_bound`), not by ``T * top_k``; a
dispatch whose local assignments exceed the bound runs the products again
over the next rows, so none is ever dropped.

The grouped matmul is the Pallas TPU kernel JAX ships
(``jax.experimental.pallas.ops.tpu.megablox.gmm``): one work item per
(row tile, expert) pair that intersects, the expert's weight tile streamed
once per item, f32 accumulation.  Off-TPU the same kernel runs in interpret
mode (``ops/pallas_mode.py`` counts how it ran), like every other kernel of
this package.
"""

from __future__ import annotations

import importlib
from typing import Optional

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import pallas_mode

ROUTE_SCOPE = "pio.moe_route"
EXPERTS_SCOPE = "pio.moe_experts"

# rows per work item of the grouped matmul: one MXU pass deep
ROW_TILE = 128


def route_sigmoid_topk(
    x: jax.Array, w_gate: jax.Array, bias: jax.Array, *,
    top_k: int, scale: float, normalize: bool = True,
):
    """Route ``x`` (T, D) over ``E`` experts (``w_gate`` (D, E), ``bias``
    (E,)), all in f32 at HIGHEST.  Returns ``picked`` (T, top_k) int32,
    ``weights`` (T, top_k) f32 and the unbiased scores ``sigma`` (T, E)."""
    with jax.named_scope(ROUTE_SCOPE):
        logits = jnp.dot(
            x.astype(jnp.float32), w_gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        sigma = jax.nn.sigmoid(logits)
        _, picked = jax.lax.top_k(sigma + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(sigma, picked, axis=1)
        if normalize:
            w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
        return picked.astype(jnp.int32), w * scale, sigma


def route_topk_softmax(x: jax.Array, w_gate: jax.Array, *, top_k: int):
    """Route ``x`` (T, D) over ``E`` experts (``w_gate`` (D, E), no bias),
    all in f32 at HIGHEST: the ``top_k`` largest LOGITS are picked (ties to
    the lower index) and the weights are a softmax OVER THE PICKED logits
    alone, so they sum to one whatever the other experts score.  Returns
    ``picked`` (T, top_k) int32, ``weights`` (T, top_k) f32 and the logits
    (T, E)."""
    with jax.named_scope(ROUTE_SCOPE):
        logits = jnp.dot(
            x.astype(jnp.float32), w_gate.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top, picked = jax.lax.top_k(logits, top_k)
        return picked.astype(jnp.int32), jax.nn.softmax(top, axis=1), logits


# the most one expert's (K, N) weight tile may take of VMEM (it is held
# twice, for the next work item's prefetch)
WEIGHT_TILE_BYTES = 4 * 1024 * 1024


def _row_tile(m: int) -> int:
    return ROW_TILE if m % ROW_TILE == 0 else m


def _col_tile(k: int, n: int, itemsize: int) -> int:
    """The widest column tile whose ``(k, tile)`` weight tile fits
    ``WEIGHT_TILE_BYTES``: ``n`` itself where the whole matrix does (every
    shape before the 3,072 x 3,072 experts), else the largest multiple of
    128 lanes that divides ``n`` and fits."""
    if k * n * itemsize <= WEIGHT_TILE_BYTES:
        return n
    fits = [c for c in range(128, n, 128)
            if n % c == 0 and k * c * itemsize <= WEIGHT_TILE_BYTES]
    return max(fits, default=n)


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]`` for every group: ``lhs`` (M, K)
    sorted by group, ``rhs`` (G, K, N), ``group_sizes`` (G,) int32 summing to
    at most M.  f32 out; rows past the last group are NOT written."""
    # the kernel's own module (the package exports a custom_vjp wrapper
    # under the same name), and its undecorated function: under its own
    # `jit` the device op would be named `gmm`, not after the scope this
    # runs in (`pio.moe_experts`)
    gmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm
    gmm = getattr(gmm, "__wrapped__", gmm)
    interpret = pallas_mode.resolve("moe_grouped_matmul", interpret)
    m, k = lhs.shape
    n = rhs.shape[2]
    # one expert's whole (K, N) weight tile per work item (column tiles of
    # it where the whole does not fit VMEM): each touched expert's weights
    # cross HBM once per row tile that holds its rows
    return gmm(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
        tiling=(_row_tile(m), k, _col_tile(k, n, rhs.dtype.itemsize)),
        interpret=interpret,
    )


def local_row_bound(n_assignments: int, n_held: int, n_experts: int) -> int:
    """Rows one pass of the held experts' products gathers: TWICE the held
    experts' even share of the dispatch's ``T * top_k`` assignments, in
    whole row tiles, and never more than all of them.  A pass is exact
    whatever the bound; a dispatch with more local assignments takes
    another pass."""
    if n_held >= n_experts:
        return n_assignments
    share = -(-n_assignments * n_held // n_experts)
    return min(n_assignments, -(-2 * share // ROW_TILE) * ROW_TILE)


def expert_products(
    x: jax.Array, picked: jax.Array, weights: jax.Array,
    w1: jax.Array, w3: jax.Array, w2: jax.Array,
    valid: Optional[jax.Array] = None, *, first: int = 0,
    n_experts: Optional[int] = None, max_local_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """``sum_j weights[t, j] * SwiGLU_{picked[t, j]}(x[t])`` for every token,
    over the picks whose expert is held here.

    ``x`` (T, D); ``picked``/``weights`` (T, k), ``picked`` in the router's
    ``[0, n_experts)``; ``w1``/``w3`` (n_held, D, F) and ``w2`` (n_held, F,
    D) in the compute dtype of ``x``: the router's experts ``[first, first
    + n_held)``.  ``n_experts`` None: all are held (``first`` 0).  Returns
    ``y`` (T, D) f32 (zero rows for padded tokens and for tokens none of
    whose picks is held) and ``counts`` (n_held,) int32, the valid
    assignments each HELD expert received — the dispatch's load here, and
    which of the held experts' weights it touched.  ``max_local_rows``
    overrides :func:`local_row_bound` (tests).
    """
    n_held = w1.shape[0]
    if first or (n_experts is not None and n_experts != n_held):
        return _held_products(
            x, picked, weights, w1, w3, w2, valid, first=first,
            bound=max_local_rows or local_row_bound(
                picked.size, n_held, n_experts or first + n_held),
            interpret=interpret)
    t, _ = x.shape
    n_experts = n_held
    k = picked.shape[1]
    with jax.named_scope(EXPERTS_SCOPE):
        flat = picked.reshape(-1)
        if valid is not None:
            flat = jnp.where(jnp.repeat(valid, k), flat, n_experts)
        counts = jnp.zeros((n_experts + 1,), jnp.int32).at[flat].add(1)[
            :n_experts]
        order = jnp.argsort(flat, stable=True)  # assignments, by expert
        xs = x[order // k]  # (T*k, D)
        gate = grouped_matmul(xs, w1, counts, interpret=interpret)
        up = grouped_matmul(xs, w3, counts, interpret=interpret)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        ys = grouped_matmul(h, w2, counts, interpret=interpret)
        back = jnp.argsort(order)  # where each assignment went
        yk = ys[back].reshape(t, k, -1)
        y = jnp.einsum("tkd,tk->td", yk, weights.astype(jnp.float32))
        if valid is not None:
            # a padded token's rows lie past the last group: never written
            y = jnp.where(valid[:, None], y, 0.0)
        return y, counts


def _held_products(x, picked, weights, w1, w3, w2, valid, *, first: int,
                   bound: int, interpret):
    """:func:`expert_products` for a layer that holds the router's experts
    ``[first, first + n_held)`` only.  Assignments are sorted held-first by
    expert; ``bound`` rows at a time go through the grouped products (one
    pass unless the local assignments exceed it), each pass's group sizes
    the part of every expert's run that falls in its rows."""
    t, d = x.shape
    n_held, k = w1.shape[0], picked.shape[1]
    with jax.named_scope(EXPERTS_SCOPE):
        local = picked - first
        held = (local >= 0) & (local < n_held)
        if valid is not None:
            held &= valid[:, None]
        flat = jnp.where(held, local, n_held).reshape(-1)
        counts = jnp.zeros((n_held + 1,), jnp.int32).at[flat].add(1)[:n_held]
        n_local = jnp.sum(counts)
        order = jnp.argsort(flat, stable=True)  # held first, by expert
        back = jnp.argsort(order).reshape(t, k)  # where each assignment went
        order = jnp.pad(order, (0, -order.shape[0] % bound))
        ends = jnp.cumsum(counts)
        w = weights.astype(jnp.float32)

        def one_pass(c, y):
            at = c * bound
            xs = x[jax.lax.dynamic_slice(order, (at,), (bound,)) // k]
            sizes = (jnp.clip(ends, at, at + bound)
                     - jnp.clip(ends - counts, at, at + bound))
            # the scope again, INSIDE the loop's body: a Pallas call takes
            # the innermost name, which would otherwise be the loop's `body`
            with jax.named_scope(EXPERTS_SCOPE):
                gate = grouped_matmul(xs, w1, sizes, interpret=interpret)
                up = grouped_matmul(xs, w3, sizes, interpret=interpret)
                h = (jax.nn.silu(gate) * up).astype(x.dtype)
                ys = grouped_matmul(h, w2, sizes, interpret=interpret)
            for j in range(k):  # a (T, D) gather a pick, never (T * k, D)
                pos = back[:, j] - at
                here = held[:, j] & (pos >= 0) & (pos < bound)
                # rows past the last group are never written: select, do
                # not multiply
                y = y + jnp.where(
                    here[:, None],
                    ys[jnp.clip(pos, 0, bound - 1)] * w[:, j:j + 1], 0.0)
            return y

        y = jax.lax.fori_loop(0, -(-n_local // bound), one_pass,
                              jnp.zeros((t, d), jnp.float32))
        return y, counts
