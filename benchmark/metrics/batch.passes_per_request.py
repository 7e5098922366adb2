"""Dispatches a request waited through, its own included (1 inline, 2 behind
the run in flight, 3 when the bucket cut carried it): mean of the `passes`
the batcher notes on each traced request answered 200."""


def read(ctx):
    vals = [t["meta"]["passes"] for t in ctx["traces"]
            if t.get("status") == 200 and "passes" in (t.get("meta") or {})]
    return sum(vals) / len(vals) if vals else None
