"""The one general traffic generator: a traffic file's parameters, a rate and
a seed in; a fixed schedule of requests out.  NumPy and the standard library
only (the load generator process imports this and must not load JAX).

Every seed gets the SAME amount of work — the same number of requests, the
same multiset of ``num`` values, the same number of bursts — in another
order and at other instants, so that two runs differ by their seed's
arrangement and not by how much they were asked to do.

A traffic file (``benchmark/traffic/<name>.json``) of kind
``serve_open_loop`` has:

* ``rate_fraction_of_knee`` — the offered rate, as a share of the
  configuration's measured ``knee_rps``;
* ``arrivals.background_share`` — share of requests arriving one by one
  (uniform order statistics over the window: a Poisson process given its
  count); the rest come in bursts of ``arrivals.burst_size`` requests sent
  within ``arrivals.burst_within_ms``; the bursts start one per equal slice
  of the window, each moved from its slice's middle by a seeded share of
  the slice up to ``arrivals.burst_jitter`` either way (so no two bursts
  pile up: a pile-up of three is a different amount of work, and a tail
  made of pile-ups swings from seed to seed);
* ``users`` — ``{"dist": "zipf_mandelbrot", "s": .., "q": ..}`` over the
  configuration's users (every user is known to the model);
* ``num`` — ``{"values": [..], "weights": [..]}``, the answer lengths.
"""

from __future__ import annotations

import numpy as np

from pio_bench import seeded


def _exact_counts(n: int, weights) -> list[int]:
    w = np.asarray(weights, np.float64)
    counts = np.floor(w / w.sum() * n).astype(np.int64)
    counts[0] += n - counts.sum()
    return counts.tolist()


def build(traffic: dict, n_users: int, rate_rps: float, seconds: float,
          seed: int) -> dict:
    """Arrays ``due_s`` (sorted, seconds from the window's start), ``user``
    and ``num``, one entry per request due in ``[0, seconds)``."""
    if traffic.get("kind") != "serve_open_loop":
        raise ValueError(f"not an open-loop serving mix: {traffic.get('kind')}")
    gen = seeded.rng(seed, seeded.STREAM_TRAFFIC)
    n = max(1, int(round(rate_rps * seconds)))
    arr = traffic["arrivals"]
    size = int(arr.get("burst_size", 0) or 0)
    n_bursts = 0
    if size and arr["background_share"] < 1.0:
        n_bursts = int(round((1.0 - arr["background_share"]) * n / size))
    n_bg = n - n_bursts * size
    parts = [gen.random(n_bg) * seconds]
    if n_bursts:
        within = arr["burst_within_ms"] / 1e3
        jitter = float(arr["burst_jitter"])
        slice_s = (seconds - within) / n_bursts
        starts = (np.arange(n_bursts) + 0.5
                  + gen.uniform(-jitter, jitter, n_bursts)) * slice_s
        parts.append(
            (starts[:, None] + gen.random((n_bursts, size)) * within).ravel())
    due = np.sort(np.concatenate(parts))
    u = traffic["users"]
    if u["dist"] != "zipf_mandelbrot":
        raise ValueError(f"unknown user distribution {u['dist']!r}")
    # popularity rank -> user index by a seeded rotation: which users are the
    # popular ones changes with the seed, how skewed the traffic is does not
    ranks = seeded.zipf_mandelbrot_sample(gen, n_users, n, u["s"], u["q"])
    users = (ranks + int(gen.integers(0, n_users))) % n_users
    nums = np.repeat(np.asarray(traffic["num"]["values"], np.int64),
                     _exact_counts(n, traffic["num"]["weights"]))
    gen.shuffle(nums)
    return {"due_s": due, "user": users.astype(np.int64), "num": nums}
