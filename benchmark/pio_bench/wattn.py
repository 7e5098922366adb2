"""What the two attention rooflines of the window/global family share: the
least time for one kind of layer's attention over the device time of that
kind's kernel, BOTH over the traced slice's own dispatches.

The (query, key) pairs of a dispatch grow with the square of a history's
length and the cell's lengths are heavy-tailed, so the window's mean pairs
a dispatch says little of the few dispatches a three-second slice holds: a
slice without a long row, held against the window's mean, read 123 % (my
chip run, PR 39).  So the work is counted for the dispatches that ARE in
the slice: each ``pio.device_compute`` span the slice's programs were
joined to carries its dispatch's ``seq`` (``hostjoin``), every request's
trace names the dispatch it rode (``meta.dispatch_seq``), the generator's
request id names the user, and the configuration's length law is a fixed
function of the user index.  A dispatch whose span began before the session
did is not joined: its ops are in the time and its pairs are not in the
work, so the share can read low by an edge dispatch, never high.
"""
from pio_bench import costs_wmoe, hostjoin
from pio_bench.xplane_named import op_seconds


def history_lengths(cfg) -> "np.ndarray":
    """The events of every user's history that a query reads."""
    import numpy as np

    from pio_bench.engines.gdn_hybrid_sequence import fixed_lengths

    return np.minimum(fixed_lengths(cfg["users"], cfg["history"]),
                      cfg["serving"]["max_len"])


def pairs(n: int, window=None) -> int:
    """(query, key) pairs one layer's mask shows a history of ``n`` events:
    ``min(position + 1, window)`` summed over its positions."""
    if window is None or n <= window:
        return n * (n + 1) // 2
    return window * (window + 1) // 2 + (n - window) * window


def slice_work(ctx, window=None):
    """(pairs of ONE layer under ``window``, tokens) summed over the
    requests that rode the dispatches joined in the traced slice; None
    where the trace names no dispatch or no request rode one."""
    got = hostjoin.analyse(ctx["device_trace"].get("trace_dir"))
    seqs = {int(d["seq"]) for d in got.get("dispatches", ())
            if d.get("seq") is not None}
    user_of = {f"bench-{r['i']}": r["user"] for r in ctx["records"]}
    lengths = None
    n_pairs = n_tokens = 0
    for t in ctx["traces"]:
        seq = (t.get("meta") or {}).get("dispatch_seq")
        if (t.get("status") != 200 or seq is None or int(seq) not in seqs
                or t.get("requestId") not in user_of):
            continue
        if lengths is None:
            lengths = history_lengths(ctx["cfg"])
        n = int(lengths[user_of[t["requestId"]]])
        n_pairs += pairs(n, window)
        n_tokens += n
    return (n_pairs, n_tokens) if n_tokens else None


def roofline(ctx, op: str, kind: str, windowed: bool):
    seconds, _ = op_seconds(ctx, op)
    if not seconds:
        return None
    cfg = ctx["cfg"]
    work = slice_work(ctx, cfg["sliding_window"] if windowed else None)
    if work is None:
        return None
    first = cfg["stage"]["first_layer"]
    layers = cfg["layer_types"][first:first + cfg["num_hidden_layers"]].count(
        kind)
    cost = costs_wmoe.windowed_attention(
        layers * work[0], work[1], layers, cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"])
    least, _ = ctx["costs"].least_seconds(
        cost, ctx["peaks"], "bf16_flops_per_s")
    return 100.0 * least / seconds
