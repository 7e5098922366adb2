"""State-space layers with an attention layer among them, EVERY layer
followed by softmax-routed small experts and a shared expert, under four
scalar multipliers and with the head tied to the embedding, as a next-item
recommender: the block design of the public ``granitemoehybrid`` models
(here read from the ``granite-4.0-h-small`` config), with the catalog as its
vocabulary and a user's history as its prompt.

``x0 = embedding_multiplier * E[token]``; ``E`` is ALSO the head
(``tie_word_embeddings``): one table, resident once (``head`` in the
parameter dict, the name the shared score head and the scorer's ``stats``
read), gathered from by the embedding and swept by ``ops/score_kernel``.
Per layer ``i`` of kind ``layer_types[i]``, on ``x`` (T, hidden),
pre-normed (RMSNorm with a learned scale, eps from the config):

    a = RMSNorm(x)
    x = x + residual_multiplier * mixer(a)
    f = RMSNorm(x)
    x = x + residual_multiplier * (sum_{e in S} g_e SwiGLU_e(f) + SwiGLU_shared(f))

* **mamba** mixer (Mamba-2 / SSD; ``mamba_n_heads`` heads of
  ``mamba_d_head``, ``mamba_n_groups`` groups, state ``mamba_d_state``):
  ``p = a W_in`` with outputs ``[z | x | B | C | dt]``, no bias; ``[x | B |
  C]`` through a causal depthwise convolution of width ``mamba_d_conv`` over
  the history (zeros before its first event, WITH a bias) and SiLU; ``dt =
  softplus(p_dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; per head ``h_t
  = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t`` from
  ``h = 0`` at the history's first event (``ops/ssd_scan.py``, chunks of
  ``mamba_chunk_size``, over the packed axis); ``y <- GroupRMSNorm(y *
  SiLU(z))``, each of the ``mamba_n_groups`` groups normed on its own (ONE
  group in the published config: the norm runs over all channels); ``y
  W_out``.
* **attention** mixer: ``[q | k | v] = a W_qkv``, ``num_attention_heads``
  query heads over ``num_key_value_heads`` key/value heads of ``hidden /
  heads``; NO rotary embedding (``position_embedding_type`` "nope"); causal
  ``softmax(attention_multiplier * q k^T) v`` within the history — the
  multiplier is the scale, NOT ``1 / sqrt(head size)``
  (``ops/flash_attention.packed_grouped_attention``); ``o W_o``.
* **feed-forward**, behind either mixer: the router's ``num_local_experts``
  logits ``f W_r`` (no bias); ``S`` the ``num_experts_per_tok`` largest and
  ``g = softmax`` OVER THE PICKED logits (``ops/moe.route_topk_softmax``);
  every expert a SwiGLU of width ``intermediate_size``; the shared expert a
  SwiGLU of width ``shared_intermediate_size``, every token.

After the last layer ``h = RMSNorm(x)`` and the scores are ``(h_last . E) /
logits_scaling``.  EVERY multiplier is applied where the equations put it,
at run time; the one that is folded is ``1 / logits_scaling``, into the
final norm's output BEFORE it is rounded to the head's dtype (``h_last``
below is ``RMSNorm(x_last) / logits_scaling``, and the shared score kernel
then multiplies it with ``E`` unchanged): the published value is a power of
two, for which the two orders agree to the bit.

**Held experts**, as ``models/window_moe.py``: a model may hold a contiguous
slice of every layer's experts, ``num_experts_held`` from
``first_expert_held`` (one rank's share under expert parallelism).  The
router keeps its ``num_local_experts`` outputs and its picks; what the
experts held elsewhere would add is LEFT OUT (as in the reference) and the
partial result goes on to the next layer.  Nothing here stands in for the
other ranks or their exchange.

**Depth**: a run of equal layers is compiled once and scanned over weights
stacked on a leading axis (``R<j>.<name>`` for run ``j``, as
``gdn_hybrid.trunk``): the published pattern's first period, mamba x 5,
attention, mamba x 4, is three scans of two bodies.  A scan's body slices
its layer out of every stacked tensor, which for the matrices of a plain
product is a read in place and for the operand of a kernel a COPY — 0.68 GB
of held experts a layer.  So the experts are not scanned over: a run's
``n`` layers of ``held`` experts are ONE table of ``n x held`` groups
(the stack, reshaped: no copy), layer ``i``'s picks are sent to groups ``[i
held, (i + 1) held)`` and every other group is empty — the grouped matmul
visits no empty group, so it streams this layer's weights from where they
lie (``_held_products``).

Precision: weights and matmul operands bf16, accumulation f32; the residual
stream, norms, softmax, the router (weights and logits, f32 at ``HIGHEST``),
the convolution, ``dt``, the decays and the scan's carried state f32.  The
compute dtype follows the weights': the tests also run the same program on
f32 weights.

:func:`forward_packed` is the serving program, with the surface of
``models/latent_moe.py`` (whose ``pack`` / ``flatten`` layout and
``score_head`` it shares); the plain f32 reference of the same equations is
``models/ssm_moe_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models import latent_moe as _lm
from predictionio_tpu.models.latent_moe import (
    _mm, _swiglu, rms_norm, score_head,
)
from predictionio_tpu.ops import flash_attention as _fa
from predictionio_tpu.ops import moe as _moe
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops import ssd_scan as _ssd

# what `PackedSequenceScorer.stats()["family"]` says of this module's models
FAMILY = "ssm_moe_sequence"
# the host side of a dispatch is the other packed families', shared
pack, flatten = _lm.pack, _lm.flatten
MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class SSMMoEConfig:
    """The shape of the model, under the keys of the published config."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    # ONE expert's width (the published config has no other key for it)
    intermediate_size: int
    shared_intermediate_size: int
    num_local_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    layer_types: tuple
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    mamba_expand: int = 2
    attention_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    rms_norm_eps: float = 1e-5
    # the slice of every layer's experts held here (None: all)
    num_experts_held: Optional[int] = None
    first_expert_held: int = 0
    # the most recent events of a history that are read
    max_len: int = 2048

    UNSUPPORTED = {
        "hidden_act": "silu", "attention_bias": False,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "normalization_function": "rmsnorm",
        "position_embedding_type": "nope", "rope_scaling": None,
        "tie_word_embeddings": True,
    }

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "SSMMoEConfig":
        """From a published ``config.json``'s keys.  A key that selects a
        mechanism this module does not implement is refused, not ignored."""
        for key, only in cls.UNSUPPORTED.items():
            if key in hf and hf[key] != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: this module implements {only!r} only")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(overrides)
        kw["layer_types"] = tuple(kw["layer_types"])
        cfg = cls(**kw)
        if (len(cfg.layer_types) != cfg.num_hidden_layers
                or set(cfg.layer_types) - {MAMBA, ATTENTION}
                or cfg.hidden_size % cfg.num_attention_heads
                or cfg.num_attention_heads % cfg.num_key_value_heads
                or cfg.mamba_d_ssm != cfg.mamba_expand * cfg.hidden_size
                or cfg.mamba_n_heads % cfg.mamba_n_groups
                or cfg.num_experts_per_tok > cfg.num_local_experts):
            raise ValueError(
                f"{cfg}: layer_types do not name {cfg.num_hidden_layers} "
                "layers of the two kinds, the heads do not divide the hidden "
                "size or the key/value heads the query heads, mamba heads x "
                "head size is not mamba_expand x hidden, the groups do not "
                "divide the mamba heads, or a token picks more experts than "
                "the router scores")
        if not (0 <= cfg.first_expert_held
                and cfg.first_expert_held + cfg.n_held
                <= cfg.num_local_experts):
            raise ValueError(
                f"experts [{cfg.first_expert_held}, "
                f"{cfg.first_expert_held + cfg.n_held}) are not among the "
                f"router's {cfg.num_local_experts}")
        return cfg

    @property
    def n_held(self) -> int:
        return (self.num_local_experts if self.num_experts_held is None
                else self.num_experts_held)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: ``[x | B | C]``."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def ssm_in_width(self) -> int:
        """``[z | x | B | C | dt]``."""
        return self.mamba_d_ssm + self.conv_width + self.mamba_n_heads

    @property
    def qkv_width(self) -> int:
        return self.head_dim * (self.num_attention_heads
                                + 2 * self.num_key_value_heads)

    @property
    def runs(self) -> tuple:
        """The layers as runs of equal kinds, ``((kind, layers), ...)``."""
        return tuple((kind, len(list(group)))
                     for kind, group in itertools.groupby(self.layer_types))

    @property
    def n_mamba_layers(self) -> int:
        return self.layer_types.count(MAMBA)

    def mixer_param_count(self, kind: str) -> int:
        d = self.hidden_size
        if kind == ATTENTION:
            return d * self.qkv_width + self.num_attention_heads \
                * self.head_dim * d
        return (d * self.ssm_in_width + self.mamba_d_ssm * d
                + (self.mamba_d_conv + 1) * self.conv_width
                + 3 * self.mamba_n_heads + self.mamba_d_ssm)

    def ffn_param_count(self) -> int:
        """The feed-forward HELD here: router, held experts, shared expert."""
        d = self.hidden_size
        return (d * self.num_local_experts
                + 3 * d * self.intermediate_size * self.n_held
                + 3 * d * self.shared_intermediate_size)

    def param_count(self) -> int:
        """Parameters HELD here (the experts held elsewhere are not); the
        tied table once."""
        d = self.hidden_size
        return ((self.vocab_size + 1) * d + sum(
            self.mixer_param_count(kind) + self.ffn_param_count() + 2 * d
            for kind in self.layer_types))


def padded_vocab(cfg: SSMMoEConfig) -> int:
    """Head rows as the score kernel sweeps them (whole item blocks)."""
    return _score_kernel.pad_block_items(cfg.vocab_size)


def param_shapes(cfg: SSMMoEConfig) -> dict:
    """``{name: (shape, dtype)}`` of every tensor; the flat dict IS the
    parameter pytree.  ``head`` is the tied table (embedding and head);
    ``R<j>.<name>`` holds every layer of run ``j``, stacked on the leading
    axis.  ``qkv`` is ``[W_q | W_k | W_v]`` side by side and an expert's
    published ``input_linear`` the pair ``e_w1`` (gate), ``e_w3`` (up): the
    same parameters and products."""
    d, e, held = cfg.hidden_size, cfg.num_local_experts, cfg.n_held
    f, fs = cfg.intermediate_size, cfg.shared_intermediate_size
    h, ds = cfg.mamba_n_heads, cfg.mamba_d_ssm
    bf, f32 = jnp.bfloat16, jnp.float32
    out = {"head": ((padded_vocab(cfg), d), bf), "final_norm": ((d,), f32)}
    for j, (kind, n) in enumerate(cfg.runs):
        p = f"R{j}."
        if kind == MAMBA:
            out.update({
                p + "ssm_in": ((n, d, cfg.ssm_in_width), bf),
                p + "conv": ((n, cfg.mamba_d_conv, cfg.conv_width), bf),
                p + "conv_bias": ((n, cfg.conv_width), f32),
                p + "A_log": ((n, h), f32), p + "D": ((n, h), f32),
                p + "dt_bias": ((n, h), f32),
                p + "gate_norm": ((n, ds), f32),
                p + "ssm_out": ((n, ds, d), bf),
            })
        else:
            out.update({
                p + "qkv": ((n, d, cfg.qkv_width), bf),
                p + "o": ((n, cfg.num_attention_heads * cfg.head_dim, d), bf),
            })
        out.update({
            p + "in_norm": ((n, d), f32), p + "ffn_norm": ((n, d), f32),
            p + "gate": ((n, d, e), f32),
            p + "e_w1": ((n, held, d, f), bf),
            p + "e_w3": ((n, held, d, f), bf),
            p + "e_w2": ((n, held, f, d), bf),
            p + "s_w1": ((n, d, fs), bf), p + "s_w3": ((n, d, fs), bf),
            p + "s_w2": ((n, fs, d), bf),
        })
    return out


# a matrix is N(0, (gain / sqrt(fan_in))^2): what it makes of a unit-rms
# input has rms `gain`, whatever the width.  Chosen so that, THROUGH
# embedding_multiplier 12 and residual_multiplier 0.22, the mixer, the routed
# experts and the shared expert each add a norm of the same order to the
# residual stream (`init_params`)
GAINS = {"ssm_in": 1.5, "ssm_out": 9.0, "qkv": 4.8, "o": 6.5, "gate": 1.0,
         "e_w1": 3.0, "e_w3": 3.0, "e_w2": 6.0,
         "s_w1": 2.0, "s_w3": 2.0, "s_w2": 3.4}


def init_params(cfg: SSMMoEConfig, seed: int) -> dict:
    """Seeded weights made ON the device, tensor by tensor: unit norm
    scales, unit-scale rows in the tied table (as ``latent_moe.init_params``:
    untrained mixers average, and distinct tokens must stay distinct) and
    zero rows in its padding; the public Mamba-2 layer's own initial values
    where it has them — convolution taps and bias ``U(-1/2, 1/2)``, ``A =
    exp(A_log) ~ U(1, 16)``, ``dt_bias`` the inverse softplus of ``dt``
    log-uniform in [1e-3, 1e-1], ``D`` 1 — and the matrices ``N(0, (gain /
    sqrt(fan_in))^2)`` with :data:`GAINS`.

    Why not ``N(0, 0.02)`` as the window family: the stream starts at
    ``embedding_multiplier`` = 12 unit rows and every sublayer's output
    enters it through ``residual_multiplier`` = 0.22, which trained weights
    of matching size undo; under 0.02 a sublayer adds 1e-3 of the
    embedding's norm and a program that dropped one would pass any
    comparison."""
    key = jax.random.fold_in(
        jax.random.key(int(seed) % (2 ** 32), impl="rbg"), int(seed) >> 32)

    @functools.partial(jax.jit, static_argnames=("shape", "dtype"))
    def normal(k, s, shape, dtype):  # f32 draws, cast inside the one program
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    @functools.partial(jax.jit, static_argnames=("shape",))
    def uniform(k, lo, hi, shape):
        return jax.random.uniform(k, shape, jnp.float32, lo, hi)

    params = {}
    for i, (name, (shape, dtype)) in enumerate(sorted(
            param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        short = name.rpartition(".")[2]
        if short.endswith("norm") or short == "D":
            params[name] = jnp.ones(shape, dtype)
        elif name == "head":
            real = normal(k, 1.0, (cfg.vocab_size, shape[1]), dtype)
            params[name] = jnp.pad(
                real, ((0, shape[0] - cfg.vocab_size), (0, 0)))
        elif short in ("conv", "conv_bias"):
            params[name] = uniform(k, -0.5, 0.5, shape).astype(dtype)
        elif short == "A_log":
            params[name] = jnp.log(uniform(k, 1.0, 16.0, shape))
        elif short == "dt_bias":
            dt = jnp.exp(uniform(k, np.log(1e-3), np.log(1e-1), shape))
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:  # a matrix (layers, [experts,] fan_in, fan_out)
            params[name] = normal(
                k, GAINS[short] / math.sqrt(shape[-2]), shape, dtype)
    return params


# -- the blocks ---------------------------------------------------------------


def mamba_mixer(cfg, W, a, positions, seg_start, n_real, interpret):
    """The state-space mixer on the normed ``a``, (T, hidden) f32."""
    t = a.shape[0]
    ds, g = cfg.mamba_d_ssm, cfg.mamba_n_groups
    gn = g * cfg.mamba_d_state
    cdt = W["ssm_out"].dtype  # the compute dtype is the weights' (bf16)
    proj = _mm(a, W["ssm_in"])
    xbc = jax.nn.silu(_ssd.causal_conv(
        proj[:, ds:ds + cfg.conv_width], W["conv"], positions,
        bias=W["conv_bias"], scope=_ssd.CONV_SCOPE)).astype(cdt)
    dt = jax.nn.softplus(proj[:, ds + cfg.conv_width:] + W["dt_bias"])
    y = _ssd.ssd_scan(
        xbc[:, :ds], xbc[:, ds:ds + gn], xbc[:, ds + gn:], dt,
        -jnp.exp(W["A_log"]), W["D"], seg_start, n_groups=g,
        chunk=min(cfg.mamba_chunk_size, t), n_real=n_real,
        interpret=interpret)
    y = y.astype(jnp.float32) * jax.nn.silu(proj[:, :ds])
    # the gated norm: each group's channels on their own
    y = rms_norm(y.reshape(t, g, ds // g), W["gate_norm"].reshape(g, -1),
                 cfg.rms_norm_eps).reshape(t, ds)
    return _mm(y, W["ssm_out"])


def attention_mixer(cfg, W, a, seg_start, interpret):
    """Grouped-query attention without positions on the normed ``a``,
    (T, hidden) f32."""
    t = a.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    cdt = W["o"].dtype
    qkv = _mm(a, W["qkv"])
    heads_first = lambda z, h: z.reshape(t, h, hd).transpose(1, 0, 2).astype(
        cdt)
    o = _fa.packed_grouped_attention(
        heads_first(qkv[:, :hq * hd], hq),
        heads_first(qkv[:, hq * hd:(hq + hkv) * hd], hkv),
        heads_first(qkv[:, (hq + hkv) * hd:], hkv), seg_start,
        scale=cfg.attention_multiplier, interpret=interpret)
    return _mm(o.transpose(1, 0, 2).reshape(t, hq * hd), W["o"])


def _held_products(cfg, fb, picked, weights, tables, i, n, valid, interpret):
    """Layer ``i`` of a run's held experts' part for its tokens, ``tables``
    the run's ``n x held`` experts as ONE table of groups: the layer's local
    picks go to its own groups and any other pick past the last, where
    ``ops/moe.expert_products`` sends what touches no weight.  One pass
    gathers what ONE layer's held experts can be sent
    (``local_row_bound`` of the layer, not of the table).  Returns the part
    (T, hidden) f32, the layer's counts (held,) and which picks are held
    here (T, top_k)."""
    held = cfg.n_held
    local = picked - cfg.first_expert_held
    mine = (local >= 0) & (local < held)
    y, counts = _moe.expert_products(
        fb, jnp.where(mine, local + i * held, n * held), weights, *tables,
        valid, n_experts=n * cfg.num_local_experts,
        max_local_rows=_moe.local_row_bound(
            picked.size, held, cfg.num_local_experts),
        interpret=interpret)
    return y, jax.lax.dynamic_slice(counts, (i * held,), (held,)), mine


def feed_forward(cfg, W, tables, i, n, x, valid, interpret):
    """The routed experts held here and the shared expert on the stream:
    their two parts (T, hidden) f32, the picks (T, top_k), the valid
    assignments per held expert and the valid tokens without a held pick."""
    f = rms_norm(x, W["ffn_norm"], cfg.rms_norm_eps)
    picked, weights, _ = _moe.route_topk_softmax(
        f, W["gate"], top_k=cfg.num_experts_per_tok)
    fb = f.astype(W["s_w1"].dtype)
    routed, counts, mine = _held_products(
        cfg, fb, picked, weights, tables, i, n, valid, interpret)
    shared = _swiglu(fb, W["s_w1"], W["s_w3"], W["s_w2"])
    unheld = jnp.sum(valid & ~mine.any(axis=1), dtype=jnp.int32)
    return routed, shared, picked, counts, unheld


def layer(cfg, kind, W, tables, i, n, x, positions, seg_start, valid,
          n_real=None, interpret=None):
    """One block on the stream ``x`` (T, hidden) f32: ``W`` ONE layer's
    tensors but its experts, which are groups ``[i held, (i + 1) held)`` of
    ``tables``.  Returns the stream, the three parts that were added to it
    (before ``residual_multiplier``: mixer, routed, shared) and what the
    router did."""
    rm = cfg.residual_multiplier
    a = rms_norm(x, W["in_norm"], cfg.rms_norm_eps)
    if kind == MAMBA:
        m = mamba_mixer(cfg, W, a, positions, seg_start, n_real, interpret)
    else:
        m = attention_mixer(cfg, W, a, seg_start, interpret)
    x = x + rm * m
    routed, shared, picked, counts, unheld = feed_forward(
        cfg, W, tables, i, n, x, valid, interpret)
    return (x + rm * (routed + shared), (m, routed, shared),
            (picked, counts, unheld))


EXPERT_TABLES = ("e_w1", "e_w3", "e_w2")


def run_weights(P: dict, j: int):
    """Run ``j``'s stacked tensors as the scan takes them, and its experts
    as one table of groups each (a reshape of the stack: no copy)."""
    pre = f"R{j}."
    W = {name[len(pre):]: v for name, v in P.items() if name.startswith(pre)}
    tables = tuple(W.pop(name) for name in EXPERT_TABLES)
    return W, tuple(v.reshape(-1, *v.shape[2:]) for v in tables)


def trunk(cfg: SSMMoEConfig, P: dict, tokens, positions, seg_start, valid,
          *, n_real=None, interpret: Optional[bool] = None):
    """The block stack over a packed token axis.  Returns the residual
    stream (T, hidden) f32 BEFORE the final norm, every layer's picks
    (L, T, top_k), the valid assignments per HELD expert (L, n_held) and, per
    layer, the valid tokens none of whose picks is held (L,).  ``n_real``:
    the axis is padding from there on, and the scan stops at that chunk."""
    x = cfg.embedding_multiplier * P["head"][tokens].astype(jnp.float32)
    routed = []
    for j, (kind, n) in enumerate(cfg.runs):
        W, tables = run_weights(P, j)

        def one_layer(x, xs, kind=kind, n=n, tables=tables):
            i, Wi = xs
            x, _, did = layer(cfg, kind, Wi, tables, i, n, x, positions,
                              seg_start, valid, n_real, interpret)
            return x, did

        x, did = jax.lax.scan(
            one_layer, x, (jnp.arange(n, dtype=jnp.int32), W))
        routed.append(did)
    picks, counts, unheld = (
        jnp.concatenate([did[k] for did in routed]) for k in range(3))
    return x, picks, counts, unheld


def forward_packed(cfg: SSMMoEConfig, P: dict, tokens, positions, seg_start,
                   valid, last_idx, k: int, *,
                   interpret: Optional[bool] = None,
                   score_backend: Optional[str] = None) -> dict:
    """One dispatch: the packed token axis through the trunk, each row's
    last position through the final norm, and its top-``k`` items taken on
    the device by the TIED table.  Arguments as
    ``latent_moe.forward_packed``.  Returns ``values`` and ``indices``
    (R, k), ``h_last`` (R, hidden) bf16 — ``RMSNorm(x_last) /
    logits_scaling``, what the head multiplied — ``x_last`` (R, hidden) f32,
    the residual stream it is the norm of (for audits: bf16 hides what the
    layers add to an embedding several times their size), ``picks``,
    ``expert_counts`` (over the HELD experts), ``tokens_unheld`` and, on the
    fused score backend, the merge counters."""
    # `pack` lays rows end to end from token 0 and a padded row repeats row
    # 0, so the last real token is the largest of `last_idx`
    x, picks, counts, unheld = trunk(
        cfg, P, tokens, positions, seg_start, valid,
        n_real=jnp.max(last_idx) + 1, interpret=interpret)
    x_last = x[last_idx]
    res = score_head(
        {"head": P["head"],
         "final_norm": P["final_norm"] / cfg.logits_scaling},
        cfg.vocab_size, cfg.rms_norm_eps, x_last, k,
        interpret=interpret, score_backend=score_backend)
    res.update(x_last=x_last, picks=picks, expert_counts=counts,
               tokens_unheld=unheld)
    return res


def forward_flat(cfg: SSMMoEConfig, P: dict, flat, t_pad: int, k: int,
                 **kw) -> dict:
    """:func:`forward_packed` on ``latent_moe.flatten``'s layout."""
    tokens, positions, seg_start, valid = (
        flat[i * t_pad:(i + 1) * t_pad] for i in range(4))
    return forward_packed(cfg, P, tokens, positions, seg_start, valid != 0,
                          flat[4 * t_pad:], k, **kw)


def row_tiles(counts: np.ndarray, tile: int) -> int:
    """The (row tile, expert) pairs that hold a real row when assignments
    lie sorted by expert from row 0, ``counts`` (..., experts) a layer: the
    work items the grouped products run for it."""
    ends = np.cumsum(counts, axis=-1)
    starts = ends - counts
    return int(np.where(
        counts > 0, (ends - 1) // tile - starts // tile + 1, 0).sum())


class DispatchCounters:
    """This family's own counters in the packed scorer (``serving/seqpath``
    holds the lock).  What the scan of the mamba layers was asked (real
    tokens and rows, one state a row a layer) and what it ran (the chunks
    up to the last real token), each times the ``scan_layers``; the
    scorer's own ``causal_pairs`` is ONE layer's, times
    ``attention_layers``.  Over every layer of every dispatch, under the
    names ``window_moe``'s have and OVER THE HELD EXPERTS: the experts that
    received a token, the assignments to them, the busiest one's load over
    their mean load, all the assignments the router made, the tokens that
    picked no held expert, the dispatches of a layer whose local
    assignments took more than one pass — and ``expert_row_tiles``, the
    (128-row tile, held expert) pairs that held a real row: the work items
    the grouped products ran, beside the ``expert_assignments`` in them."""

    # outputs of the program fetched with every dispatch's answer
    fetch = ("expert_counts", "tokens_unheld")

    def __init__(self, config: SSMMoEConfig):
        self.config = config
        self.scan_tokens = 0
        self.scan_rows = 0
        self.scan_chunks = 0
        self.experts_touched = 0
        self.expert_assignments = 0
        self.expert_row_tiles = 0
        self.load_max_over_mean_sum = 0.0
        self.sparse_layer_dispatches = 0
        self.routed_assignments = 0
        self.tokens_without_held_expert = 0
        self.local_row_overflows = 0

    def add(self, t_pad: int, n_rows: int, n_tokens: int, got: dict) -> None:
        cfg = self.config
        layers = cfg.n_mamba_layers
        self.scan_tokens += layers * n_tokens
        self.scan_rows += layers * n_rows
        self.scan_chunks += layers * _ssd.scan_chunks(
            t_pad, min(cfg.mamba_chunk_size, t_pad), n_real=n_tokens)
        counts = got["expert_counts"]  # (layers, held experts)
        live = counts.sum(axis=1) > 0
        ratios = counts[live].max(axis=1) / counts[live].mean(axis=1)
        self.experts_touched += int((counts > 0).sum())
        self.expert_assignments += int(counts.sum())
        self.load_max_over_mean_sum += float(ratios.sum())
        self.sparse_layer_dispatches += int(live.sum())
        k = cfg.num_experts_per_tok
        self.routed_assignments += cfg.num_hidden_layers * n_tokens * k
        self.tokens_without_held_expert += int(got["tokens_unheld"].sum())
        bound = _moe.local_row_bound(
            t_pad * k, cfg.n_held, cfg.num_local_experts)
        self.local_row_overflows += int((counts.sum(axis=1) > bound).sum())
        self.expert_row_tiles += row_tiles(counts, _moe._row_tile(bound))

    def stats(self) -> dict:
        cfg = self.config
        return {
            "scan_layers": cfg.n_mamba_layers,
            "attention_layers": cfg.num_hidden_layers - cfg.n_mamba_layers,
            "scan_chunk": cfg.mamba_chunk_size,
            "scan_tokens": self.scan_tokens,
            "scan_rows": self.scan_rows,
            "scan_chunks": self.scan_chunks,
            "sparse_layers": cfg.num_hidden_layers,
            "experts": cfg.num_local_experts,
            "experts_held": cfg.n_held,
            "first_expert_held": cfg.first_expert_held,
            "experts_touched": self.experts_touched,
            "expert_assignments": self.expert_assignments,
            "expert_row_tiles": self.expert_row_tiles,
            "load_max_over_mean_sum": round(self.load_max_over_mean_sum, 4),
            "sparse_layer_dispatches": self.sparse_layer_dispatches,
            "routed_assignments": self.routed_assignments,
            "tokens_without_held_expert": self.tokens_without_held_expert,
            "local_row_overflows": self.local_row_overflows,
        }


@dataclasses.dataclass
class SSMMoEModel:
    """What the sequence template serves: the config, the parameter pytree
    (device-resident, or NumPy after a pickle round trip), the item id map,
    and optionally where histories come from (``histories``; None = the
    event store)."""

    config: SSMMoEConfig
    params: dict
    item_map: object
    histories: object = None


Config, Model = SSMMoEConfig, SSMMoEModel
