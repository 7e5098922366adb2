"""Sparse-expert feed-forward: sigmoid routing and grouped expert products.

Two device stages, each under a ``jax.named_scope``.  A TPU trace names a
Pallas call after the innermost scope, so the three grouped products of a
layer appear as ``pio.moe_experts``; XLA fusions keep ``fusion.N`` there, so
routing (``pio.moe_route``) and the sort, gather and combine around the
products are in the HLO's metadata but cannot be found by name in a trace:

* ``pio.moe_route`` — :func:`route_sigmoid_topk`: ``sigma = sigmoid(x @ W_g)``
  in f32, the ``top_k`` largest of ``sigma + bias`` are picked (the bias
  SELECTS), the weights are the unbiased ``sigma`` of the picked experts
  (the bias never WEIGHS), normalised and scaled.
* ``pio.moe_experts`` — :func:`expert_products`: the ``T * top_k``
  (token, expert) assignments are sorted by expert, each expert's rows go
  through its SwiGLU as one group of a grouped matmul, and the results are
  gathered back and combined with the routing weights.  Every assignment
  is computed: there is no capacity and no dropped token, whatever the
  skew.  Padded tokens (``valid == False``) are sorted past the last expert
  and belong to no group, so they touch no expert's weights.

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


def _row_tile(m: int) -> int:
    return ROW_TILE if m % ROW_TILE == 0 else m


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
    # one expert's whole (K, N) weight tile per work item: each touched
    # expert's weights cross HBM once per row tile that holds its rows
    return gmm(
        lhs, rhs, group_sizes.astype(jnp.int32),
        preferred_element_type=jnp.float32,
        tiling=(_row_tile(m), k, n), interpret=interpret,
    )


def expert_products(
    x: jax.Array, picked: jax.Array, weights: jax.Array,
    w1: jax.Array, w3: jax.Array, w2: jax.Array,
    valid: Optional[jax.Array] = None, *, interpret: Optional[bool] = None,
):
    """``sum_j weights[t, j] * SwiGLU_{picked[t, j]}(x[t])`` for every token.

    ``x`` (T, D); ``picked``/``weights`` (T, k); ``w1``/``w3`` (E, D, F) and
    ``w2`` (E, F, D) in the compute dtype of ``x``.  Returns ``y`` (T, D) f32
    (zero rows for padded tokens) and ``counts`` (E,) int32, the valid
    assignments each expert received — the dispatch's load, and which
    experts' weights it touched.
    """
    t, _ = x.shape
    n_experts = w1.shape[0]
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
