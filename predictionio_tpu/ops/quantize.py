"""Quantized factor storage: bf16 and int8 (per-row scale) variants.

The serving score path is memory-bound — per dispatch it streams the whole
item-factor matrix from HBM (see ``docs/perf_roofline.md``).  Narrowing the
factor dtype is therefore a direct bandwidth win: bf16 halves the bytes
moved, int8 halves them again.  ALS factors are small-magnitude and
per-row well-conditioned, so symmetric per-row int8 (one float32 scale per
embedding row, ``row ≈ q * scale``) keeps top-k rankings stable; the
publish-time accuracy gate in ``models/als.py`` measures exactly that
(top-k overlap vs fp32) before a quantized generation may ship.

Quantization happens ONCE, offline, at model publish; serving loads the
already-quantized arrays device-resident and the fused kernel dequantizes
in VMEM (``ops/score_kernel.py``), so HBM only ever sees the narrow bytes.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

# serving factor dtypes, narrowest last; "f32" means no quantization
FACTOR_DTYPES = ("f32", "bf16", "int8")

# bytes per factor element, used by the analytic cost models (obs/devprof)
FACTOR_BYTES = {"f32": 4.0, "bf16": 2.0, "int8": 1.0}


def contraction_precision(operand_dtype):
    """The matmul precision a contraction over ``operand_dtype`` states.

    A TPU's default rounds f32 operands to one bf16 pass — 3 decimal
    digits where the path promised f32 — and no CPU test can see it.  So
    an f32 contraction asks for ``HIGHEST``; bf16 operands, and f32
    operands that merely hold dequantized narrow values, are already what
    the MXU takes and stay at the default (None).
    """
    import jax
    import jax.numpy as jnp

    return jax.lax.Precision.HIGHEST if operand_dtype == jnp.float32 else None


def _bf16():
    # ml_dtypes ships with jax; numpy itself has no bfloat16
    import ml_dtypes

    return ml_dtypes.bfloat16


def quantize_factors(
    factors: np.ndarray, dtype: str
) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Quantize a (n, rank) float32 factor matrix to ``dtype``.

    Returns ``(quantized, scale)`` where ``scale`` is a (n, 1) float32
    per-row scale for int8 (``row ≈ q.astype(f32) * scale``) and None for
    f32/bf16 (bf16 is a plain downcast — same exponent range as f32).
    """
    f = np.asarray(factors, np.float32)
    if dtype == "f32":
        return f, None
    if dtype == "bf16":
        return f.astype(_bf16()), None
    if dtype == "int8":
        amax = np.max(np.abs(f), axis=1, keepdims=True)
        scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        q = np.clip(np.rint(f / scale), -127, 127).astype(np.int8)
        return q, scale
    raise ValueError(
        f"factor dtype must be one of {FACTOR_DTYPES}, got {dtype!r}"
    )


def quantize_factors_jax(factors, dtype: str):
    """In-graph (jnp) counterpart of :func:`quantize_factors`.

    The TRAINING compute path (``PIO_ALS_COMPUTE_DTYPE``) quantizes the
    opposite factor matrix once per half-step — the factors change every
    iteration, so the offline numpy path cannot serve it.  Same math:
    bf16 is a plain downcast, int8 is symmetric per-row (``row ≈
    q.astype(f32) * scale``).  Returns ``(quantized, scale-or-None)``.
    """
    import jax.numpy as jnp

    if dtype == "f32":
        return factors, None
    if dtype == "bf16":
        return factors.astype(jnp.bfloat16), None
    if dtype == "int8":
        amax = jnp.max(jnp.abs(factors), axis=1, keepdims=True)
        scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(factors / scale), -127, 127).astype(jnp.int8)
        return q, scale
    raise ValueError(
        f"factor dtype must be one of {FACTOR_DTYPES}, got {dtype!r}"
    )


def dequantize_factors(
    quantized: np.ndarray, scale: Optional[np.ndarray] = None
) -> np.ndarray:
    """Reconstruct float32 factors — the reference math the kernel fuses."""
    f = np.asarray(quantized).astype(np.float32)
    if scale is not None:
        f = f * np.asarray(scale, np.float32)
    return f


def factor_dtype_of(arr: np.ndarray) -> str:
    """Classify an array's serving factor dtype (for stats/metrics)."""
    if arr.dtype == np.int8:
        return "int8"
    if arr.dtype == _bf16():
        return "bf16"
    return "f32"
