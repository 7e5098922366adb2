"""Fused Pallas gather-contract kernel for the ALS *training* half-step.

The dense solver's per-bucket device program is ``Vg = V[idx]`` then two
batched contractions (``A = einsum('edk,edl->ekl', ·)``,
``b = einsum('edk,ed->ek', ·)``).  Left to XLA, the row gather reads one
~512 B sector per 40 B factor row — the ~12.8× read-amplification term
``docs/perf_roofline.md`` derives as the dense half-step's dominant byte
cost.  This kernel removes that term instead of hiding its latency:

* the OPPOSITE factor matrix streams into VMEM **once per grid** via a
  block whose index_map is pinned to ``(0, 0)`` — Pallas fetches it on the
  first grid step and keeps it resident, one sequential HBM read at full
  bandwidth.  It must FIT: VMEM holds it in lane-padded tiles (512 B per
  rank-10 f32 row, not 40), so dispatch counts the budget that way
  (:func:`fits_vmem`) and MovieLens-25M's sides do not qualify;
* the random row gather then runs AGAINST VMEM (a dynamic-row vector copy
  per rating slot — Mosaic has no ``gather`` lowering), where sub-sector
  access costs nothing;
* the rating stream (idx/rat/msk) tiles over the grid as usual — idx rides
  in SMEM so each row id is readable as a scalar — and the per-bucket
  ``(n_b, D_b, k)`` contraction stays a batched MXU matmul accumulating
  the ``(n_b, k, k)`` normal-equation tensor in f32
  (``preferred_element_type``).

Quantized COMPUTE dtype (``PIO_ALS_COMPUTE_DTYPE``): the gathered side may
arrive as bf16 or int8 (+ per-row f32 scales, ``ops/quantize.py``), so the
one sequential V read narrows to half/quarter the f32 bytes; int8
dequantizes in VMEM after the gather and all accumulation stays f32.  The
reference XLA path performs the identical math (dequantize → gather →
contract with the same operand order), so the equivalence suite can hold
the two backends to bit-identical solved factors.

Dispatch is a static rule (:func:`resolve_backend`): ``PIO_TRAIN_KERNEL``
(``fused`` | ``reference`` | ``auto``); ``auto`` takes the kernel only on
a real TPU (never the interpreter on CPU) and only for a gathered side
that fits the VMEM budget; an explicit ``fused`` that cannot fit raises;
``PIO_NATIVE=0`` kills it along with every other native kernel.  The
identical kernel runs anywhere via ``interpret=`` — that is how the CPU
equivalence tests exercise the real kernel body.
"""

from __future__ import annotations

import functools
import os
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from predictionio_tpu.ops import pallas_mode
from predictionio_tpu.ops.quantize import FACTOR_BYTES, contraction_precision

BACKENDS = ("fused", "reference", "auto")

# Entities contracted per grid step.  8 = one f32 sublane: the (BLOCK_E, k,
# k) accumulator tile and the (BLOCK_E·D_b, k) gathered-row scratch stay
# small next to the resident opposite-factor block at every bucket width.
BLOCK_E = 8

# Widest rating tile one grid step contracts.  The gathered-row scratch is
# BLOCK_E·block_d rows of one lane-padded tile row each (512 B at f32), so
# this caps it at 2 MiB however wide a degree bucket is.
BLOCK_D_MAX = 512

# Index rows gathered per grid step by the segment-solver gather kernel.
GATHER_BLOCK = 1024  # a 1-D int32 SMEM block must match XLA's 1024-element tile

# VMEM the pinned opposite-factor block may occupy before dispatch refuses
# the fused path: 12 of the 16 MiB Mosaic scopes to a kernel by default on
# v5e, leaving room for the rating tiles and the gather scratch.
VMEM_RESIDENT_BUDGET = 12 * 1024 * 1024

# Mosaic lays a VMEM array out in (sublane, 128-lane) tiles; the sublane
# count depends on the element width.
_LANES = 128
_SUBLANES = {"f32": 8, "bf16": 16, "int8": 32}


def use_fused_default() -> bool:
    """The one gate policy for 'should training take the Pallas path': TPU
    only — interpret-mode fused loses on CPU, so ``auto`` dispatch must
    never silently pick it there.  Mirrors ``score_kernel``."""
    return jax.default_backend() == "tpu"


def _tile_bytes(rows: int, cols: int, dtype: str) -> int:
    sub = _SUBLANES[dtype]
    return (
        -(-rows // sub) * sub * -(-cols // _LANES) * _LANES
        * int(FACTOR_BYTES[dtype])
    )


def resident_bytes(n_opp: int, rank: int, compute_dtype: str = "f32") -> int:
    """VMEM bytes the pinned opposite-factor block occupies, counted the
    way Mosaic allocates it — in padded tiles, not in elements: a rank-10
    f32 row is 40 B of data and 512 B of VMEM.  int8 adds the per-row f32
    scale column, itself one lane-padded tile column."""
    b = _tile_bytes(n_opp, rank, compute_dtype)
    if compute_dtype == "int8":
        b += _tile_bytes(n_opp, 1, "f32")
    return b


def fits_vmem(n_opp: int, rank: int, compute_dtype: str = "f32") -> bool:
    """Whether the opposite factor matrix fits the VMEM residency budget —
    the fused kernel's one hard precondition.  Pallas double-buffers every
    blocked input, the pinned one included, hence the factor 2."""
    return 2 * resident_bytes(n_opp, rank, compute_dtype) <= VMEM_RESIDENT_BUDGET


def refusal(
    n_opp: Optional[int], rank: Optional[int], compute_dtype: str
) -> Optional[str]:
    """Why the fused kernel cannot serve this gathered side here, or None.

    The two facts the static dispatch rule rests on, each a Mosaic limit
    met on the v5e chip rather than a preference: the pinned block must
    fit VMEM in padded tiles, and a compiled kernel can take a dynamic
    single row only from an unpacked (32-bit) array — bf16 and int8 rows
    pack several to a sublane ("cannot statically prove that index in
    dimension 0 is a multiple of 8").  The interpreter has no such limit.
    """
    if use_fused_default() and compute_dtype != "f32":
        return (
            f"compute dtype {compute_dtype} packs rows in a sublane and "
            "Mosaic cannot gather one of them by a dynamic index"
        )
    if n_opp is not None and not fits_vmem(n_opp, rank, compute_dtype):
        return (
            f"the gathered side ({n_opp} x {rank}, {compute_dtype}) needs "
            f"2 x {resident_bytes(n_opp, rank, compute_dtype)} B of VMEM in "
            f"padded tiles against a budget of {VMEM_RESIDENT_BUDGET} B"
        )
    return None


def resolve_backend(
    requested: Optional[str] = None,
    *,
    n_opp: Optional[int] = None,
    rank: Optional[int] = None,
    compute_dtype: str = "f32",
) -> str:
    """Resolve the training-kernel backend: ``"fused"`` or ``"reference"``.

    A static rule, never a try/except into the reference.  ``requested``
    overrides ``PIO_TRAIN_KERNEL``.  ``PIO_NATIVE=0`` forces the reference
    path — the same kill switch that disables every other native kernel
    in the repo.  ``auto`` takes the fused kernel only on a TPU and only
    where :func:`refusal` finds nothing against it; an explicit ``fused``
    that is refused raises with the reason.  Without the gathered side's
    shape (``n_opp``, ``rank``) the VMEM half of the rule is not applied.
    """
    req = (
        requested or os.environ.get("PIO_TRAIN_KERNEL") or "auto"
    ).strip().lower()
    if req not in BACKENDS:
        raise ValueError(
            f"PIO_TRAIN_KERNEL must be one of {BACKENDS}, got {req!r}"
        )
    if os.environ.get("PIO_NATIVE", "1") == "0":
        return "reference"
    if req == "reference":
        return req
    why_not = refusal(n_opp, rank, compute_dtype)
    if req == "fused":
        if why_not:
            raise ValueError(
                f"train kernel 'fused' was requested but cannot be used: "
                f"{why_not}; use 'auto' or 'reference'"
            )
        return req
    return "fused" if use_fused_default() and not why_not else "reference"


# -- live stats for the /metrics bridge ---------------------------------------
# models/als.py records the resolved dispatch here at step-build time; the
# obs bridge (obs/bridges.py) exports it as pio_train_kernel_* without the
# obs layer ever importing training internals at scrape time.

_stats_lock = threading.Lock()
_stats: dict = {}


def record_stats(**kw) -> None:
    """Merge step-build facts (backend, compute_dtype, resident bytes,
    analytic intensity) into the module-global stats the bridge scrapes."""
    with _stats_lock:
        _stats.update(kw)


def stats() -> dict:
    with _stats_lock:
        return dict(_stats)


def reset_stats() -> None:
    with _stats_lock:
        _stats.clear()


# -- the fused bucket kernel --------------------------------------------------


def _train_contract_kernel(
    idx_ref, rat_ref, msk_ref, *refs,
    block_e: int, block_d: int, k: int,
    implicit: bool, alpha: float, has_scale: bool,
):
    """One grid step: gather (block_e·block_d) rows from the resident V
    block, contract them against the rating tile, accumulate the
    normal-equation outputs (resident across the d sweep)."""
    it = iter(refs)
    v_ref = next(it)
    vs_ref = next(it) if has_scale else None
    a_out = next(it)
    b_out = next(it)
    cnt_out = next(it)
    vg_ref = next(it)
    vsg_ref = next(it) if has_scale else None

    di = pl.program_id(1)

    @pl.when(di == 0)
    def _init():
        a_out[...] = jnp.zeros_like(a_out)
        b_out[...] = jnp.zeros_like(b_out)
        cnt_out[...] = jnp.zeros_like(cnt_out)

    # row gather AGAINST the VMEM-resident V block: one dynamic-row copy
    # per rating slot (idx lives in SMEM so each row id reads as a scalar).
    # A vector copy, not a DMA: Mosaic cannot slice a rank-wide row out of
    # a 128-lane tile for a DMA.  Padding slots carry idx 0 — an
    # always-valid row whose contribution the zero mask erases below
    def gather(j, carry):
        e = j // block_d
        d = j - e * block_d
        row = idx_ref[e, d]
        vg_ref[pl.ds(j, 1), :] = v_ref[pl.ds(row, 1), :]
        if has_scale:
            vsg_ref[pl.ds(j, 1), :] = vs_ref[pl.ds(row, 1), :]
        return carry

    jax.lax.fori_loop(0, block_e * block_d, gather, 0)

    # dequantize in VMEM: HBM only ever streamed the narrow bytes.  int8
    # upcasts to f32 (per-row scale); f32/bf16 keep the storage dtype for
    # the multiplies — the same operand dtypes as the reference einsum —
    # and every contraction accumulates f32 via preferred_element_type.
    vg = vg_ref[...]
    if has_scale:
        vg = vg.astype(jnp.float32) * vsg_ref[...]
    vg = vg.reshape(block_e, block_d, k)
    cd = vg.dtype
    rat = rat_ref[...]
    msk = msk_ref[...]
    w = msk.astype(cd)
    f32 = jnp.float32
    # dimension_numbers spell out einsum('edk,edl->ekl'): contract d (dim
    # 1), batch e (dim 0) — the MXU shape, f32 accumulation.  The b
    # contraction einsum('edk,ed->ek') takes its right side as (e, d, 1):
    # Mosaic's matmul wants a matrix there, not a batched vector
    contract = (((1,), (1,)), ((0,), (0,)))
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=contract,
        preferred_element_type=f32, precision=contraction_precision(cd),
    )
    if implicit:
        # A_u += Σ α·r · v vᵀ ;  b_u += Σ (1+α·r) · v   (p=1, c=1+αr)
        cw = (alpha * rat).astype(cd) * w
        a_out[...] += dot(vg * cw[:, :, None], vg)
        b_out[...] += dot(
            vg, ((1.0 + alpha * rat).astype(cd) * w)[:, :, None]
        )[:, :, 0]
    else:
        W = vg * w[:, :, None]
        a_out[...] += dot(W, W)
        b_out[...] += dot(W, rat.astype(cd)[:, :, None])[:, :, 0]
        cnt_out[...] += jnp.sum(msk, axis=1, keepdims=True)


def fused_train_normal_eq(
    idx: jax.Array,
    rat: jax.Array,
    msk: jax.Array,
    V: jax.Array,
    v_scale: Optional[jax.Array] = None,
    *,
    implicit: bool = False,
    alpha: float = 1.0,
    interpret: Optional[bool] = None,
    block_e: Optional[int] = None,
    block_d: Optional[int] = None,
):
    """One bucket's normal equations, fused: ``(A (n_b,k,k), b (n_b,k),
    cnt (n_b,))`` — the gather + weighted outer-product contraction of
    ``models/als.py:_dense_half_step_local`` as a single ``pallas_call``.

    ``V`` may be f32, bf16, or int8 (int8 requires the matching per-row
    ``v_scale`` from :mod:`ops.quantize`); it streams into VMEM once and
    stays resident for the whole grid.  ``interpret`` defaults to True
    off-TPU so the equivalence tests run the identical kernel anywhere.
    ``block_d`` defaults to the bucket width up to :data:`BLOCK_D_MAX` —
    one d step for every narrower bucket, so f32 accumulation order matches
    the reference einsum exactly; wider buckets sweep d in ``BLOCK_D_MAX``
    steps, trading that bit-equality for a bounded gather scratch.
    """
    interpret = pallas_mode.resolve("train_contract", interpret)
    n_b, D = idx.shape
    n_opp, k = V.shape
    be = min(block_e or BLOCK_E, max(1, n_b))
    bd = min(block_d or BLOCK_D_MAX, D)
    e_pad = -(-n_b // be) * be
    d_pad = -(-D // bd) * bd
    if e_pad - n_b or d_pad - D:
        pad = ((0, e_pad - n_b), (0, d_pad - D))
        idx = jnp.pad(idx, pad)
        rat = jnp.pad(rat, pad)
        msk = jnp.pad(msk, pad)  # zero mask: padding contributes zero

    has_scale = v_scale is not None
    kernel = functools.partial(
        _train_contract_kernel,
        block_e=be, block_d=bd, k=k,
        implicit=implicit, alpha=float(alpha), has_scale=has_scale,
    )

    in_specs = [
        # idx rides in SMEM: the gather loop reads each row id as a scalar
        pl.BlockSpec((be, bd), lambda e, d: (e, d), memory_space=pltpu.SMEM),
        pl.BlockSpec((be, bd), lambda e, d: (e, d), memory_space=pltpu.VMEM),
        pl.BlockSpec((be, bd), lambda e, d: (e, d), memory_space=pltpu.VMEM),
        # the decisive block: index_map pinned to (0, 0) → Pallas streams V
        # into VMEM on the first step and keeps it resident for the grid
        pl.BlockSpec((n_opp, k), lambda e, d: (0, 0), memory_space=pltpu.VMEM),
    ]
    operands = [idx.astype(jnp.int32), rat, msk, V]
    if has_scale:
        in_specs.append(
            pl.BlockSpec(
                (n_opp, 1), lambda e, d: (0, 0), memory_space=pltpu.VMEM
            )
        )
        operands.append(v_scale.astype(jnp.float32))

    scratch = [pltpu.VMEM((be * bd, k), V.dtype)]  # gathered rows
    if has_scale:
        scratch.append(pltpu.VMEM((be * bd, 1), jnp.float32))

    A, b, cnt = pl.pallas_call(
        kernel,
        grid=(e_pad // be, d_pad // bd),
        in_specs=in_specs,
        # accumulators pinned over the d sweep: one writeback per e block
        out_specs=[
            pl.BlockSpec((be, k, k), lambda e, d: (e, 0, 0)),
            pl.BlockSpec((be, k), lambda e, d: (e, 0)),
            pl.BlockSpec((be, 1), lambda e, d: (e, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, k, k), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, k), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return A[:n_b], b[:n_b], cnt[:n_b, 0]


# -- the segment-solver gather kernel -----------------------------------------


def _gather_rows_kernel(
    idx_ref, *refs, block_n: int, k: int, has_scale: bool
):
    """One grid step: gather ``block_n`` rows from the resident V block
    and emit them dequantized to f32."""
    it = iter(refs)
    v_ref = next(it)
    vs_ref = next(it) if has_scale else None
    out_ref = next(it)
    vg_ref = next(it)
    vsg_ref = next(it) if has_scale else None

    def gather(j, carry):
        row = idx_ref[j]
        vg_ref[pl.ds(j, 1), :] = v_ref[pl.ds(row, 1), :]
        if has_scale:
            vsg_ref[pl.ds(j, 1), :] = vs_ref[pl.ds(row, 1), :]
        return carry

    jax.lax.fori_loop(0, block_n, gather, 0)
    out = vg_ref[...].astype(jnp.float32)
    if has_scale:
        out = out * vsg_ref[...]
    out_ref[...] = out


def fused_gather_rows(
    V: jax.Array,
    idx: jax.Array,
    v_scale: Optional[jax.Array] = None,
    *,
    interpret: Optional[bool] = None,
    block_n: Optional[int] = None,
) -> jax.Array:
    """``V[idx]`` dequantized to f32, gathered against VMEM-resident ``V``.

    The segment solver's chunk loop calls this in place of the XLA gather
    (``opp_full[ot]``) so its per-row reads also stop paying the sector
    amplification; everything downstream (``segment_sum`` accumulation)
    is unchanged.  Returns ``(len(idx), rank) float32``.
    """
    interpret = pallas_mode.resolve("train_gather_rows", interpret)
    (n,) = idx.shape
    n_opp, k = V.shape
    bn = min(block_n or GATHER_BLOCK, max(8, n))
    n_pad = -(-n // bn) * bn
    if n_pad - n:
        idx = jnp.pad(idx, (0, n_pad - n))

    has_scale = v_scale is not None
    kernel = functools.partial(
        _gather_rows_kernel, block_n=bn, k=k, has_scale=has_scale
    )
    in_specs = [
        pl.BlockSpec((bn,), lambda i: (i,), memory_space=pltpu.SMEM),
        pl.BlockSpec((n_opp, k), lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    operands = [idx.astype(jnp.int32), V]
    if has_scale:
        in_specs.append(
            pl.BlockSpec((n_opp, 1), lambda i: (0, 0), memory_space=pltpu.VMEM)
        )
        operands.append(v_scale.astype(jnp.float32))
    scratch = [pltpu.VMEM((bn, k), V.dtype)]
    if has_scale:
        scratch.append(pltpu.VMEM((bn, 1), jnp.float32))

    out = pl.pallas_call(
        kernel,
        grid=(n_pad // bn,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bn, k), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, k), jnp.float32),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*operands)
    return out[:n]
