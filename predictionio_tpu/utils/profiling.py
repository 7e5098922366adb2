"""Tracing/profiling: jax.profiler traces + latency histograms.

The reference has no profiler beyond Spark's UI and the query server's
avg/last serving seconds (``CreateServer.scala:415-417,597-604``; SURVEY.md
§5).  TPU-first observability is stronger by design:

* :func:`trace` — context manager around ``jax.profiler`` writing a
  TensorBoard-loadable trace of device execution (set
  ``PIO_PROFILE_DIR`` or pass a path; no-op otherwise).
* :class:`LatencyHistogram` — lock-free-ish log-bucketed latency histogram
  with p50/p90/p99 readout, used by the query server per request.
"""

from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import Optional

import numpy as np


@contextlib.contextmanager
def trace(log_dir: Optional[str] = None):
    """Capture a device trace if a profile dir is configured; else no-op.

    The serving pipeline's stages reach such a trace by themselves:
    :func:`predictionio_tpu.obs.tracing.stage` enters a
    ``jax.profiler.TraceAnnotation`` per stage.
    """
    log_dir = log_dir or os.environ.get("PIO_PROFILE_DIR")
    if not log_dir:
        yield
        return
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class LatencyHistogram:
    """Log₂-bucketed histogram from 0.01 ms to ~100 s."""

    MIN_MS = 0.01
    N_BUCKETS = 48

    def __init__(self):
        self._counts = np.zeros(self.N_BUCKETS, np.int64)
        self._lock = threading.Lock()
        self.total = 0

    def _bucket(self, ms: float) -> int:
        if ms <= self.MIN_MS:
            return 0
        b = int(math.log2(ms / self.MIN_MS) * 2)  # half-octave buckets
        return min(max(b, 0), self.N_BUCKETS - 1)

    def observe(self, seconds: float) -> None:
        with self._lock:
            self._counts[self._bucket(seconds * 1e3)] += 1
            self.total += 1

    def _bucket_upper_ms(self, b: int) -> float:
        return self.MIN_MS * 2 ** ((b + 1) / 2)

    def quantile(self, q: float) -> float:
        """Approximate quantile in milliseconds (bucket upper bound)."""
        with self._lock:
            if self.total == 0:
                return 0.0
            target = q * self.total
            acc = 0
            for b in range(self.N_BUCKETS):
                acc += self._counts[b]
                if acc >= target:
                    return self._bucket_upper_ms(b)
        return self._bucket_upper_ms(self.N_BUCKETS - 1)

    def summary(self) -> dict:
        return {
            "count": self.total,
            "p50Ms": self.quantile(0.50),
            "p90Ms": self.quantile(0.90),
            "p99Ms": self.quantile(0.99),
        }
