"""Device-utilization accounting: cost models, peaks, live rates, capture.

Utilization as a RUNTIME fact, from a model of the work (what the chip
measured is the benchmark's: ``benchmark/``, ``PERF_LEDGER.jsonl``):

* **One cost model, one peak table.**  The analytic ALS iteration cost and
  the per-chip peak table live here, so the training loop and the serving
  fastpath divide by the same denominators.  ``PEAKS`` is keyed by
  ``device_kind``; a device that is not in it (a CPU, another TPU
  generation) reports null utilization, never another chip's.
* **Rolling-window dispatch accountant** (:class:`DeviceUtilization`).
  The serving fastpath annotates every AOT bucket with FLOPs/bytes from
  ``compiled.cost_analysis()`` (analytic fallback when the compiler
  declines) and records each dispatch's device wall here; the ALS train
  loop does the same per training step.  :meth:`DeviceUtilization.snapshot`
  reduces the window into achieved FLOP/s, HBM GB/s, MFU, HBM utilization,
  and device busy fraction — the live ``pio_device_*`` gauge families.
* **On-demand profile capture** (:func:`capture_profile`): a bounded
  ``jax.profiler`` window written under the basedir, driven by the query
  server's ``POST /debug/profile`` and the ``pio profile`` CLI.

Knobs: ``PIO_DEVPROF_WINDOW`` — rolling-window length in seconds for the
live gauges (default 60).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Optional

__all__ = [
    "PEAKS",
    "peak_for",
    "als_train_cost",
    "fused_train_cost",
    "fused_train_vread_bytes",
    "score_cost",
    "DeviceUtilization",
    "train_recorder",
    "train_snapshot",
    "capture_profile",
]

# Per-chip peaks for utilization accounting, keyed by the ``device_kind``
# JAX reports (``jax.devices()[0].device_kind``).  v5e: 197 TFLOP/s bf16
# MXU, 819 GB/s HBM (Google Cloud documentation, "TPU v5e").  mfu is defined
# against the bf16 peak — the number the hardware markets — so a 10×
# utilization regression is visible regardless of the dtype in use.  A
# device that is not listed reports null utilization: a CPU run never
# prints an mfu, and another TPU generation never borrows v5e's peaks.
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_gbps": 819e9},
}

DEFAULT_WINDOW_S = 60.0


def peak_for(device_kind: Optional[str]) -> Optional[dict]:
    """Per-chip peak {flops, hbm_gbps} for a jax ``device_kind``, or None."""
    if device_kind is None:
        return None
    return PEAKS.get(str(device_kind))


def als_train_cost(
    n_ratings: int, n_users: int, n_items: int, rank: int, dtype: str = "f32"
) -> tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) of ONE dense-solver ALS iteration.

    Cost model (both half-steps of one iteration, dense solver):
      FLOPs: per rating 2·(2k² + 4k) madds (outer product + rhs accumulate,
      both sides) + per entity 2·(k³/3) Cholesky factor+solve madds.
      HBM bytes: per rating, both sides: k·s gather read + 12 B of
      idx/rat/msk + k·s of A-tile write amortized; per entity k·4 factor
      write + opposite-factor read once per half-step.
    A model, not a measurement — good for regression visibility, not for
    publishing as achieved hardware counters.
    """
    k = rank
    s = 2 if dtype == "bf16" else 4  # bytes per factor element
    ents = n_users + n_items
    flops_per_iter = n_ratings * 2 * (2 * k * k + 4 * k) * 2 + ents * (
        2 * k**3 / 3
    )
    bytes_per_iter = (
        n_ratings * 2 * (k * s + 12)  # gather + idx/rat/msk streams
        + ents * k * (4 + s)  # factor write (f32) + opposite read
    )
    return float(flops_per_iter), float(bytes_per_iter)


# bytes per factor element by serving dtype (mirrors ops/quantize.py;
# duplicated here so the obs layer never imports the ops layer)
_FACTOR_BYTES = {"f32": 4.0, "bf16": 2.0, "int8": 1.0}


def fused_train_vread_bytes(
    n_users: int, n_items: int, rank: int, compute_dtype: str = "f32"
) -> float:
    """Bytes of the fused kernel's ONE sequential opposite-factor read per
    iteration (both half-steps): each side streams the other side's
    matrix into VMEM once at the compute dtype, plus the per-row f32
    scale column when int8.  This is the term the compute dtype narrows.
    """
    s = _FACTOR_BYTES.get(compute_dtype, 4.0)
    ents = float(n_users + n_items)
    nbytes = ents * rank * s
    if compute_dtype == "int8":
        nbytes += ents * 4.0
    return nbytes


def fused_train_cost(
    n_ratings: int, n_users: int, n_items: int, rank: int,
    compute_dtype: str = "f32",
) -> tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) of ONE FUSED-kernel ALS iteration.

    The Pallas training kernel (``ops/train_kernel.py``) streams the
    opposite factor matrix into VMEM once per half-step and gathers rows
    against VMEM, so the per-rating gather term — a ~512 B sector under
    XLA, ``k·s`` even in the charitable model — disappears from HBM
    entirely.  What remains:

    * per rating, both sides: 12 B of idx/rat/msk stream;
    * per half-step: the one sequential opposite-matrix read at the
      compute dtype (:func:`fused_train_vread_bytes`);
    * per entity: the k·4 f32 factor write.

    FLOPs match :func:`als_train_cost` — same contraction, same Cholesky;
    the fused win is bytes, i.e. arithmetic intensity.
    """
    k = rank
    flops, _ = als_train_cost(n_ratings, n_users, n_items, rank)
    ents = n_users + n_items
    nbytes = (
        n_ratings * 2 * 12.0  # idx/rat/msk streams, both sides
        + fused_train_vread_bytes(n_users, n_items, rank, compute_dtype)
        + ents * k * 4.0  # solved-factor write (always f32)
    )
    return float(flops), float(nbytes)


def score_cost(
    batch: int, n_items: int, rank: int, dtype: str = "f32"
) -> tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) of one bucketed score+top-k dispatch.

    Fallback for buckets where ``compiled.cost_analysis()`` declines:
    the (B, k) × (k, I) score matmul dominates FLOPs (plus ~8 ops/score
    for masking and the top-k compare network); bytes are the factor
    reads, the materialized score matrix round-trip, and the (B, k)
    result write.
    """
    b, i, k = float(batch), float(n_items), float(rank)
    s = _FACTOR_BYTES.get(dtype, 4.0)
    flops = b * i * (2.0 * k + 8.0)
    # quantized reference still materializes the dequantized f32 copy and
    # the f32 score matrix; only the factor stream itself narrows
    nbytes = i * k * s + b * k * s + 2.0 * b * i * 4.0 + b * k * 8.0
    return flops, nbytes


def fused_score_cost(
    batch: int, n_items: int, rank: int, top_k: int, dtype: str = "f32"
) -> tuple[float, float]:
    """Analytic (FLOPs, HBM bytes) of one FUSED score+top-k dispatch.

    The Pallas kernel (``ops/score_kernel.py``) keeps the score matrix in
    VMEM, so the reference model's dominant ``2·B·I·4`` HBM round-trip
    term disappears: bytes are just the one-pass factor stream (at the
    storage dtype — this is where bf16/int8 pay off), the B gathered user
    rows, the int8 per-row scales when present, the mask stream, and the
    (B, k) result write.  FLOPs match the reference (same matmul + ~8
    ops/score of masking/merge work), so the fused intensity gain is the
    byte reduction, directly.
    """
    b, i, r, k = float(batch), float(n_items), float(rank), float(top_k)
    s = _FACTOR_BYTES.get(dtype, 4.0)
    flops = b * i * (2.0 * r + 8.0)
    nbytes = i * r * s + b * r * s  # item stream + gathered user rows
    if dtype == "int8":
        nbytes += (i + b) * 4.0  # per-row f32 scales
    nbytes += i * 1.0  # int8 exclusion-mask stream
    nbytes += b * 4.0 + b * k * 8.0  # index upload + (vals, idx) readback
    return flops, nbytes


class DeviceUtilization:
    """Rolling-window accountant for cost-annotated device dispatches.

    The owner annotates each dispatch class (serving bucket, train step)
    with its FLOPs/bytes once via :meth:`set_cost`, then calls
    :meth:`record` with the measured device wall per dispatch.  Records
    older than the window age out; :meth:`snapshot` reduces what's left
    into achieved rates and utilization against the device's peak.  All
    methods are thread-safe; ``record`` is O(1) amortized.
    """

    def __init__(
        self,
        device_kind: Optional[str] = None,
        window_s: Optional[float] = None,
    ):
        if window_s is None:
            window_s = float(
                os.environ.get("PIO_DEVPROF_WINDOW", DEFAULT_WINDOW_S)
            )
        self.window_s = max(1.0, float(window_s))
        self.device_kind = device_kind
        self._costs: dict = {}  # dispatch key → (flops, bytes)
        self._cost_source: dict = {}  # dispatch key → "xla" | "analytic"
        # (t_recorded, device_seconds, flops, bytes) per dispatch
        self._records: deque = deque()
        self._lock = threading.Lock()
        self._t_created = time.monotonic()
        self.dispatches = 0  # lifetime, never pruned

    def set_cost(
        self, key, flops: Optional[float], nbytes: Optional[float],
        source: str = "xla",
    ) -> None:
        """Annotate dispatch class ``key`` with per-dispatch FLOPs/bytes."""
        with self._lock:
            self._costs[key] = (
                float(flops) if flops else 0.0,
                float(nbytes) if nbytes else 0.0,
            )
            self._cost_source[key] = source

    def costs(self) -> dict:
        with self._lock:
            return {
                k: {
                    "flops": f, "bytes": by,
                    "source": self._cost_source.get(k),
                }
                for k, (f, by) in self._costs.items()
            }

    def record(self, key, seconds: float) -> None:
        """Charge one dispatch of class ``key`` with measured device wall."""
        if seconds < 0:
            seconds = 0.0
        now = time.monotonic()
        with self._lock:
            flops, nbytes = self._costs.get(key, (0.0, 0.0))
            self._records.append((now, float(seconds), flops, nbytes))
            self.dispatches += 1
            self._prune(now)

    def _prune(self, now: float) -> None:
        cutoff = now - self.window_s
        while self._records and self._records[0][0] < cutoff:
            self._records.popleft()

    def snapshot(self) -> Optional[dict]:
        """Windowed rates + utilization; None before the first dispatch.

        ``busy_fraction`` (and the rates) divide by the OBSERVED span —
        window length once the accountant has lived that long, its age
        before that — so a freshly warmed server reports its true duty
        cycle instead of a number diluted by a mostly-empty window.
        """
        now = time.monotonic()
        with self._lock:
            self._prune(now)
            if not self.dispatches:
                return None
            elapsed = min(self.window_s, max(1e-9, now - self._t_created))
            busy = sum(r[1] for r in self._records)
            flops = sum(r[2] for r in self._records)
            nbytes = sum(r[3] for r in self._records)
            n = len(self._records)
        flops_per_s = flops / elapsed
        gbps = nbytes / elapsed
        peak = peak_for(self.device_kind)
        return {
            "device_kind": self.device_kind,
            "window_s": self.window_s,
            "elapsed_s": round(elapsed, 3),
            "dispatches_window": n,
            "dispatches_total": self.dispatches,
            "busy_s": round(busy, 6),
            "busy_fraction": round(min(1.0, busy / elapsed), 6),
            "flops_per_s": round(flops_per_s, 2),
            # 6 decimals: a rank-2 toy model on CPU still reads non-zero
            "hbm_gbps": round(gbps / 1e9, 6),
            "mfu": round(flops_per_s / peak["flops"], 9) if peak else None,
            "hbm_util": round(gbps / peak["hbm_gbps"], 9) if peak else None,
        }


# -- train-side recorder ------------------------------------------------------
# `pio train` has no HTTP server to scrape, so the train loop records into
# a process-global accountant; the CLI and tests read the snapshot, and the
# loop logs a utilization line per step so a long train is visible live.

_train_lock = threading.Lock()
_train_acc: Optional[DeviceUtilization] = None


def train_recorder(device_kind: Optional[str] = None) -> DeviceUtilization:
    """The process-global training accountant (created on first use)."""
    global _train_acc
    with _train_lock:
        if _train_acc is None or (
            device_kind is not None and _train_acc.device_kind != device_kind
        ):
            _train_acc = DeviceUtilization(device_kind=device_kind)
        return _train_acc


def train_snapshot() -> Optional[dict]:
    with _train_lock:
        acc = _train_acc
    return acc.snapshot() if acc is not None else None


# -- on-demand profile capture ------------------------------------------------


def capture_profile(ms: int, out_dir: Optional[str] = None) -> str:
    """Run ``jax.profiler`` for a bounded window; return the trace dir.

    Blocks the calling thread for ``ms`` milliseconds while the rest of
    the process keeps serving — exactly what the query server's
    ``POST /debug/profile`` wants. Traces land under
    ``<basedir>/profiles/<stamp>`` unless ``out_dir`` overrides.
    """
    import jax

    from predictionio_tpu.utils.fs import pio_base_dir

    ms = max(1, int(ms))
    if out_dir is None:
        stamp = time.strftime("%Y%m%d-%H%M%S", time.gmtime())
        out_dir = os.path.join(
            pio_base_dir(), "profiles", f"{stamp}-{os.getpid()}"
        )
    os.makedirs(out_dir, exist_ok=True)
    jax.profiler.start_trace(out_dir)
    try:
        time.sleep(ms / 1e3)
    finally:
        jax.profiler.stop_trace()
    return out_dir
