"""Small helpers the per-layer metric readers share.  A reader is
``read(ctx) -> float | None``; ``None`` means it found nothing to read and
the harness leaves the metric out of the line.

``ctx`` keys: ``cfg``, ``traffic``, ``records`` (every request: due, sent,
done, status), ``good`` (the answered ones), ``traces`` (the server's
request traces, ``stagesMs`` per request), ``counters_before`` /
``counters_after`` (batcher and fast-path counts around the window),
``device_trace`` (``xplane.reduce_planes`` of the traced slice plus its
``dispatches``), ``peaks``, ``costs``, ``window_s``.
"""

from __future__ import annotations

import numpy as np


def stage_values(ctx, stages) -> list:
    """Per traced request with status 200: the sum of the named stages, ms."""
    out = []
    for t in ctx["traces"]:
        if t.get("status") == 200 and t.get("stagesMs"):
            out.append(sum(t["stagesMs"].get(s, 0.0) for s in stages))
    return out


def pct(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def delta(ctx, key, sub=None):
    a, b = ctx["counters_after"].get(key), ctx["counters_before"].get(key)
    if a is None or b is None:
        return None
    if sub is not None:
        return {k: a.get(k, 0) - b.get(k, 0) for k in a}
    return a - b


def score_program_seconds(ctx):
    """Device seconds and executions of the score programs in the traced
    slice: the modules whose name holds the fast path's jitted ``fn``."""
    mods = ctx["device_trace"]["modules"]
    hit = {n: m for n, m in mods.items() if "jit_fn" in n}
    if not hit:
        return None, 0
    return (sum(m["seconds"] for m in hit.values()),
            sum(m["count"] for m in hit.values()))


def load_reader(name: str):
    """The reader of the per-layer metric ``name``:
    ``benchmark/metrics/<name>.py``'s ``read``."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def answered(records) -> list:
    """The requests that got an answer to judge: status 200, not degraded."""
    return [r for r in records
            if r["status"] == 200 and not r.get("degraded")]


def latencies_ms(records) -> list:
    """Answer received minus the instant the request was DUE, ms."""
    return [(r["done"] - r["due"]) * 1e3 for r in records]


def lateness_ms(records) -> list:
    """Actually sent minus due, ms, in the generator process."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("sent") is not None]


def peak_inflight(records) -> int:
    """The most requests sent and not yet answered at one instant, by the
    generator's clock: what the server's admission gate (``max_inflight``,
    256 by default) sees, and how far a run was from shedding."""
    events = sorted([(r["sent"], 1) for r in records
                     if r.get("sent") is not None]
                    + [(r["done"], -1) for r in records
                       if r.get("sent") is not None])
    peak = now = 0
    for _, step in events:
        now += step
        peak = max(peak, now)
    return peak


def longest_silence(records):
    """(seconds, instant): the longest stretch of the window in which no
    answer came back although requests were in flight, and when it ended.  A
    dispatch takes a quarter of a second; a stretch far longer is a stall."""
    done = sorted(r["done"] for r in records if r.get("sent") is not None)
    gaps = [(b - a, b) for a, b in zip(done, done[1:])]
    return max(gaps) if gaps else (0.0, 0.0)
