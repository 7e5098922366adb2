"""A state-space mixer and grouped-query attention SIDE BY SIDE in every
layer, under muP multipliers, as a next-item recommender: the block design
of the public ``falcon_h1`` models (here read from the
``Falcon-H1-34B-Instruct`` config), with the catalog as its vocabulary and a
user's history as its prompt.

``x0 = embedding_multiplier * E_in[token]``.  Per layer, on ``x`` (T,
hidden), pre-normed (RMSNorm with a learned scale, eps from the config):

    a   = RMSNorm(x)                                   (input_layernorm)
    m_s = state-space mixer(a)       m_a = attention(a)      — the SAME a
    x   = x + m_s + m_a
    x   = x + mlp_multipliers[1] * ((SiLU(mlp_multipliers[0] * f W_gate)
                                     * (f W_up)) W_down),   f = RMSNorm(x)

* **state-space mixer** (Mamba-2 / SSD; ``mamba_n_heads`` heads of
  ``mamba_d_head``, ``mamba_n_groups`` groups, state ``mamba_d_state``):
  ``p = ((ssm_in_multiplier * a) W_in) * mup``, ``W_in``'s outputs ``[z | x |
  B | C | dt]`` and ``mup`` = ``ssm_multipliers[0..4]`` over those five
  segments; ``[x | B | C]`` through a causal depthwise convolution of width
  ``mamba_d_conv`` over the history (zeros before its first event, WITH a
  bias) and SiLU; ``dt = softplus(p_dt + dt_bias)``, ``A = -exp(A_log)``; per
  head ``h_t = exp(dt_t A) h_(t-1) + dt_t x_t (x) B_t``, ``y_t = h_t C_t + D
  x_t`` from ``h = 0`` at the history's first event, B and C those of the
  head's GROUP (``ops/ssd_scan.py``, chunked, over the packed axis); ``y <-
  GroupRMSNorm(y * SiLU(z))`` (each group's channels normed on their own);
  ``m_s = ssm_out_multiplier * (y W_out)``.
* **attention**: ``q = (attention_in_multiplier * a) W_q``, ``k =
  key_multiplier * ((attention_in_multiplier * a) W_k)``, ``v`` likewise
  without the key's multiplier; half-rotation RoPE on q and k (position =
  index in the user's history); causal softmax within the history, query
  head ``h`` reading key/value head ``h // (heads / kv heads)``
  (``ops/flash_attention.packed_grouped_attention``, no window); ``m_a =
  attention_out_multiplier * (o W_o)``.

Final RMSNorm; the head's scores are ``lm_head_multiplier * h . E_out``
(untied).  EVERY multiplier is applied where the equations put it, at run
time; the one that is folded is ``lm_head_multiplier``, into the final
norm's output BEFORE it is rounded to the head's dtype (``h_last`` below is
``lm_head_multiplier * RMSNorm(x_last)``, and the shared score kernel then
multiplies it with ``E_out`` unchanged): the published value is a power of
two, for which the two orders agree to the bit.

The layers are one pattern, compiled once and scanned over depth
(``lax.scan`` over weights stacked on a leading axis, ``S.<name>``, as
``gdn_hybrid.trunk``).

Precision: weights and matmul operands bf16, accumulation f32; the residual
stream, norms, RoPE, softmax, the convolution, ``dt``, the decays and the
scan's carried state f32.  The compute dtype follows the weights': the tests
also run the same program on f32 weights.

:func:`forward_packed` is the serving program, with the surface of
``models/latent_moe.py`` (whose ``pack`` / ``flatten`` layout and
``score_head`` it shares); the plain f32 reference of the same equations is
``models/ssm_parallel_reference.py``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.models import latent_moe as _lm
from predictionio_tpu.models.latent_moe import _mm, rms_norm, score_head
from predictionio_tpu.models.window_moe import rope_half
from predictionio_tpu.ops import flash_attention as _fa
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops import ssd_scan as _ssd

# what `PackedSequenceScorer.stats()["family"]` says of this module's models
FAMILY = "ssm_parallel_sequence"
# the host side of a dispatch is the other packed families', shared
pack, flatten = _lm.pack, _lm.flatten


@dataclasses.dataclass(frozen=True)
class SSMParallelConfig:
    """The shape of the model, under the keys of the published config."""

    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    intermediate_size: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_d_ssm: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    embedding_multiplier: float = 1.0
    key_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    # over the segments [z | x | B | C | dt] of the input projection
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    # inside the gate's SiLU, and on the down projection's output
    mlp_multipliers: tuple = (1.0, 1.0)
    # the most recent events of a history that are read
    max_len: int = 2048

    UNSUPPORTED = {
        "hidden_act": "silu", "attention_bias": False, "mlp_bias": False,
        "projectors_bias": False, "mamba_proj_bias": False,
        "mamba_conv_bias": True, "mamba_rms_norm": True,
        "mamba_norm_before_gate": False, "mamba_use_mlp": True,
        "attn_layer_indices": None, "rope_scaling": None,
        "tie_word_embeddings": False,
    }

    @classmethod
    def from_hf(cls, hf: dict, **overrides) -> "SSMParallelConfig":
        """From a published ``config.json``'s keys.  A key that selects a
        mechanism this module does not implement is refused, not ignored."""
        for key, only in cls.UNSUPPORTED.items():
            if key in hf and hf[key] != only:
                raise ValueError(
                    f"{key}={hf[key]!r}: this module implements {only!r} only")
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in hf.items() if k in names}
        kw.update(overrides)
        for key in ("ssm_multipliers", "mlp_multipliers"):
            if key in kw:
                kw[key] = tuple(float(m) for m in kw[key])
        if "rope_theta" in kw:  # published as an integer beyond int32
            kw["rope_theta"] = float(kw["rope_theta"])
        cfg = cls(**kw)
        if (cfg.mamba_d_ssm != cfg.mamba_n_heads * cfg.mamba_d_head
                or cfg.mamba_n_heads % cfg.mamba_n_groups
                or cfg.mamba_d_ssm % cfg.mamba_n_groups
                or cfg.num_attention_heads % cfg.num_key_value_heads
                or cfg.head_dim % 2
                or len(cfg.ssm_multipliers) != 5
                or len(cfg.mlp_multipliers) != 2):
            raise ValueError(
                f"{cfg}: mamba_d_ssm is not heads x head size, the groups do "
                "not divide the heads, the key/value heads do not divide the "
                "query heads, the head size is odd, or the multipliers are "
                "not five and two")
        return cfg

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

    def layer_param_count(self) -> int:
        d = self.hidden_size
        ssm = (d * self.ssm_in_width + self.mamba_d_ssm * d
               + (self.mamba_d_conv + 1) * self.conv_width
               + 3 * self.mamba_n_heads + self.mamba_d_ssm)
        attn = d * self.qkv_width + self.num_attention_heads * self.head_dim * d
        return ssm + attn + 3 * d * self.intermediate_size + 2 * d

    def param_count(self) -> int:
        return ((2 * self.vocab_size + 1) * self.hidden_size
                + self.num_hidden_layers * self.layer_param_count())


def padded_vocab(cfg: SSMParallelConfig) -> int:
    """Head rows as the score kernel sweeps them (whole item blocks)."""
    return _score_kernel.pad_block_items(cfg.vocab_size)


def param_shapes(cfg: SSMParallelConfig) -> dict:
    """``{name: (shape, dtype)}`` of every tensor; the flat dict IS the
    parameter pytree.  ``S.<name>`` holds every layer's tensor, stacked on
    the leading axis.  ``qkv`` is ``[W_q | W_k | W_v]`` side by side: the
    same parameters and products, one matmul."""
    d, f, n = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    h, ds = cfg.mamba_n_heads, cfg.mamba_d_ssm
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "embed": ((cfg.vocab_size, d), bf),
        "head": ((padded_vocab(cfg), d), bf),
        "final_norm": ((d,), f32),
        "S.in_norm": ((n, d), f32),
        "S.ssm_in": ((n, d, cfg.ssm_in_width), bf),
        "S.conv": ((n, cfg.mamba_d_conv, cfg.conv_width), bf),
        "S.conv_bias": ((n, cfg.conv_width), f32),
        "S.A_log": ((n, h), f32), "S.D": ((n, h), f32),
        "S.dt_bias": ((n, h), f32),
        "S.gate_norm": ((n, ds), f32),
        "S.ssm_out": ((n, ds, d), bf),
        "S.qkv": ((n, d, cfg.qkv_width), bf),
        "S.o": ((n, cfg.num_attention_heads * cfg.head_dim, d), bf),
        "S.ffn_norm": ((n, d), f32),
        "S.w1": ((n, d, f), bf), "S.w3": ((n, d, f), bf),
        "S.w2": ((n, f, d), bf),
    }


# a matrix is N(0, (gain / sqrt(fan_in))^2): what it makes of a unit-rms
# input has rms `gain`, whatever the width.  Chosen so that, THROUGH the
# published multipliers, each of the three branches adds a norm of the same
# order to the residual stream (`init_params`)
GAINS = {"ssm_in": 18.0, "ssm_out": 11.5, "qkv": 14.0, "o": 5.0,
         "w1": 7.2, "w3": 7.2, "w2": 14.7}


def init_params(cfg: SSMParallelConfig, seed: int, *, gains: dict = GAINS,
                head_std: float = 0.02, embed_std: float = 1.0) -> dict:
    """Seeded weights made ON the device, tensor by tensor: unit norm
    scales, unit-scale embedding rows (as ``latent_moe.init_params``:
    untrained mixers average, and distinct tokens must stay distinct),
    ``N(0, head_std)`` head rows and zero rows in the head's padding; the
    public Mamba-2 layer's own initial values where it has them —
    convolution taps and bias ``U(-1/2, 1/2)`` (a width-4 depthwise
    Conv1d's default), ``A = exp(A_log) ~ U(1, 16)``, ``dt_bias`` the inverse
    softplus of ``dt`` log-uniform in [1e-3, 1e-1], ``D`` 1 — and the
    matrices ``N(0, (gain / sqrt(fan_in))^2)`` with :data:`GAINS`.

    Why not ``N(0, 0.02)`` as the other families: this family's branches
    end in multipliers (0.088 on the state-space branch, 0.0375 on
    attention, 0.011 on the feed-forward, beside 5.66 on the embedding) that
    trained weights of matching size undo; under 0.02 every branch adds
    1e-3 of the embedding's norm and a program that dropped one would pass
    any comparison.  With these gains each branch adds about one unit of
    rms a layer to a stream that starts at 5.66, the attention's logits
    have an rms of ~2 (so the key's multiplier and the rotation show) and
    the recurrence is a visible part of the state-space branch beside its
    skip."""
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
            real = normal(k, head_std, (cfg.vocab_size, shape[1]), dtype)
            params[name] = jnp.pad(
                real, ((0, shape[0] - cfg.vocab_size), (0, 0)))
        elif name == "embed":
            params[name] = normal(k, embed_std, shape, dtype)
        elif short in ("conv", "conv_bias"):
            params[name] = uniform(k, -0.5, 0.5, shape).astype(dtype)
        elif short == "A_log":
            params[name] = jnp.log(uniform(k, 1.0, 16.0, shape))
        elif short == "dt_bias":
            dt = jnp.exp(uniform(k, np.log(1e-3), np.log(1e-1), shape))
            params[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:  # a matrix (layers, fan_in, fan_out)
            params[name] = normal(
                k, gains[short] / math.sqrt(shape[1]), shape, dtype)
    return params


# -- the blocks ---------------------------------------------------------------


def mup_vector(cfg: SSMParallelConfig):
    """``ssm_multipliers`` over the segments ``[z | x | B | C | dt]`` of the
    input projection's outputs, (ssm_in_width,) f32."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    widths = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.asarray(np.concatenate([
        np.full(w, m, np.float32)
        for w, m in zip(widths, cfg.ssm_multipliers)]))


def ssm_branch(cfg, W, a, positions, seg_start, n_real, interpret):
    """The state-space mixer's part of the block, (T, hidden) f32."""
    t = a.shape[0]
    ds, g = cfg.mamba_d_ssm, cfg.mamba_n_groups
    gn = g * cfg.mamba_d_state
    cdt = W["ssm_out"].dtype  # the compute dtype is the weights' (bf16)
    proj = _mm(cfg.ssm_in_multiplier * a, W["ssm_in"]) * mup_vector(cfg)
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
    return cfg.ssm_out_multiplier * _mm(y, W["ssm_out"])


def attention_branch(cfg, W, a, positions, seg_start, interpret):
    """The attention's part of the block, (T, hidden) f32."""
    t = a.shape[0]
    hq, hkv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                   cfg.head_dim)
    cdt = W["o"].dtype
    qkv = _mm(cfg.attention_in_multiplier * a, W["qkv"])
    q = qkv[:, :hq * hd].reshape(t, hq, hd)
    k = cfg.key_multiplier * qkv[:, hq * hd:(hq + hkv) * hd].reshape(
        t, hkv, hd)
    v = qkv[:, (hq + hkv) * hd:].reshape(t, hkv, hd)
    q = rope_half(q, positions, cfg.rope_theta)
    k = rope_half(k, positions, cfg.rope_theta)
    heads_first = lambda z: z.transpose(1, 0, 2).astype(cdt)
    o = _fa.packed_grouped_attention(
        heads_first(q), heads_first(k), heads_first(v), seg_start,
        scale=1.0 / math.sqrt(hd), interpret=interpret)
    o = o.transpose(1, 0, 2).reshape(t, hq * hd)
    return cfg.attention_out_multiplier * _mm(o, W["o"])


def mlp_branch(cfg, W, f):
    gate_m, down_m = cfg.mlp_multipliers
    h = jax.nn.silu(gate_m * _mm(f, W["w1"])) * _mm(f, W["w3"])
    return down_m * _mm(h, W["w2"])


def layer(cfg, W, x, positions, seg_start, n_real=None, interpret=None):
    """One block on the stream ``x`` (T, hidden) f32; ``W`` ONE layer's
    tensors.  Returns the stream and the two mixers' parts."""
    a = rms_norm(x, W["in_norm"], cfg.rms_norm_eps)
    m_s = ssm_branch(cfg, W, a, positions, seg_start, n_real, interpret)
    m_a = attention_branch(cfg, W, a, positions, seg_start, interpret)
    x = x + m_s + m_a
    f = rms_norm(x, W["ffn_norm"], cfg.rms_norm_eps)
    return x + mlp_branch(cfg, W, f), m_s, m_a


def trunk(cfg: SSMParallelConfig, P: dict, tokens, positions, seg_start, *,
          n_real=None, interpret: Optional[bool] = None):
    """The block stack over a packed token axis: the residual stream
    (T, hidden) f32 BEFORE the final norm.  ``n_real``: the axis is padding
    from there on, and the scan stops at that chunk (the padded tokens'
    rows of the result are then no model's output)."""
    stacked = {name[2:]: v for name, v in P.items() if name[:2] == "S."}

    def one_layer(x, W):
        x, _, _ = layer(cfg, W, x, positions, seg_start, n_real, interpret)
        return x, None

    x = cfg.embedding_multiplier * P["embed"][tokens].astype(jnp.float32)
    x, _ = jax.lax.scan(one_layer, x, stacked)
    return x


def forward_packed(cfg: SSMParallelConfig, P: dict, tokens, positions,
                   seg_start, last_idx, k: int, *,
                   interpret: Optional[bool] = None,
                   score_backend: Optional[str] = None) -> dict:
    """One dispatch: the packed token axis through the trunk, each row's
    last position through the final norm, and its top-``k`` items taken on
    the device.  Arguments as ``gdn_hybrid.forward_packed``.  Returns
    ``values`` and ``indices`` (R, k), ``h_last`` (R, hidden) bf16 —
    ``lm_head_multiplier * RMSNorm(x_last)``, what the head multiplied —
    ``x_last`` (R, hidden) f32, the residual stream it is the norm of (for
    audits: bf16 hides what the layers add to an embedding several times
    their size) and, on the fused score backend, the merge counters."""
    # `pack` lays rows end to end from token 0 and a padded row repeats row
    # 0, so the last real token is the largest of `last_idx`
    x = trunk(cfg, P, tokens, positions, seg_start,
              n_real=jnp.max(last_idx) + 1, interpret=interpret)
    x_last = x[last_idx]
    res = score_head(
        {"head": P["head"],
         "final_norm": cfg.lm_head_multiplier * P["final_norm"]},
        cfg.vocab_size, cfg.rms_norm_eps, x_last, k,
        interpret=interpret, score_backend=score_backend)
    res["x_last"] = x_last
    return res


def forward_flat(cfg: SSMParallelConfig, P: dict, flat, t_pad: int, k: int,
                 **kw) -> dict:
    """:func:`forward_packed` on ``latent_moe.flatten``'s layout."""
    tokens, positions, seg_start = (
        flat[i * t_pad:(i + 1) * t_pad] for i in range(3))
    return forward_packed(cfg, P, tokens, positions, seg_start,
                          flat[4 * t_pad:], k, **kw)


class DispatchCounters:
    """This family's own counters in the packed scorer: what the scan was
    asked (real tokens and rows, one state a row a layer) and what it ran
    (the chunks up to the last real token: alignment to chunks included, a
    rung's padded tail not), each times the layers — every layer scans.
    The scorer's own ``causal_pairs`` is ONE layer's; every layer attends
    (``attention_layers``)."""

    fetch = ()

    def __init__(self, config: SSMParallelConfig):
        self.config = config
        self.scan_tokens = 0
        self.scan_rows = 0
        self.scan_chunks = 0

    def add(self, t_pad: int, n_rows: int, n_tokens: int, got: dict) -> None:
        cfg = self.config
        layers = cfg.num_hidden_layers
        self.scan_tokens += layers * n_tokens
        self.scan_rows += layers * n_rows
        self.scan_chunks += layers * _ssd.scan_chunks(
            t_pad, min(cfg.mamba_chunk_size, t_pad), n_real=n_tokens)

    def stats(self) -> dict:
        cfg = self.config
        return {
            "scan_layers": cfg.num_hidden_layers,
            "attention_layers": cfg.num_hidden_layers,
            "scan_chunk": cfg.mamba_chunk_size,
            "scan_tokens": self.scan_tokens,
            "scan_rows": self.scan_rows,
            "scan_chunks": self.scan_chunks,
        }


@dataclasses.dataclass
class SSMParallelModel:
    """What the sequence template serves: the config, the parameter pytree
    (device-resident, or NumPy after a pickle round trip), the item id map,
    and optionally where histories come from (``histories``; None = the
    event store)."""

    config: SSMParallelConfig
    params: dict
    item_map: object
    histories: object = None


Config, Model = SSMParallelConfig, SSMParallelModel
