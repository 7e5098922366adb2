"""Everything a run draws from ``--seed``, in NumPy only.

The load generator, the float64 reference and the chip process all import
this module, so it must not import JAX or the program under test.
"""

from __future__ import annotations

import concurrent.futures

import numpy as np

# sub-streams of one run's seed, so that no two draws share a generator
STREAM_USER_FACTORS = 1
STREAM_ITEM_FACTORS = 2
STREAM_TRAFFIC = 3
STREAM_AUDIT = 4
STREAM_RUNGS = 5

_FACTOR_CHUNKS = 16


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named sub-stream of the run's seed (any
    non-negative whole number, beyond 2**31 too)."""
    return np.random.default_rng([int(seed), *[int(s) for s in stream]])


def make_factors(seed: int, stream: int, rows: int, rank: int) -> np.ndarray:
    """Seeded ``N(0, 1/rank)`` float32 factors, as ``train_als`` initialises
    them.  Filled chunk by chunk, each chunk from its own generator, so the
    result does not depend on how many threads fill it."""
    out = np.empty((rows, rank), np.float32)
    bounds = np.linspace(0, rows, _FACTOR_CHUNKS + 1).astype(np.int64)
    scale = np.float32(1.0 / np.sqrt(rank))

    def fill(c: int) -> None:
        view = out[bounds[c]:bounds[c + 1]]
        rng(seed, stream, c).standard_normal(
            view.shape, dtype=np.float32, out=view)
        view *= scale

    with concurrent.futures.ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(_FACTOR_CHUNKS)))
    return out


def zipf_mandelbrot_weights(n: int, s: float, q: float) -> np.ndarray:
    """Zipf-Mandelbrot pmf ``P(k) ~ (k+q)^-s`` over ranks ``[0, n)`` (a copy
    of ``predictionio_tpu.tools.loadtest.zipf_mandelbrot_weights``)."""
    p = (np.arange(1, n + 1, dtype=np.float64) + q) ** -s
    return p / p.sum()


def zipf_mandelbrot_sample(gen: np.random.Generator, n: int, size: int,
                           s: float, q: float) -> np.ndarray:
    """``size`` ranks in ``[0, n)`` from that pmf, by inverse CDF."""
    cdf = np.cumsum(zipf_mandelbrot_weights(n, s, q))
    return np.minimum(np.searchsorted(cdf, gen.random(size)), n - 1)
