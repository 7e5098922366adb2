"""A second reducer beside ``xplane.reduce_planes``: device seconds of the
operations that carry a NAME the program gave them, and executions and
seconds of a named program (module).

On a TPU an event of the ``XLA Ops`` line is named by its whole HLO
instruction (``%pio.mla_attention.8 = bf16[...] custom-call(...)``), so the
instruction's OWN name is what stands before `` = ``; its operands' names
must not match.  A ``jax.named_scope`` names a Pallas call that way; an XLA
fusion inside the scope keeps its generic name (``%fusion.47``) and its
stats hold no scope either (seen on the chip, PR 27), so only kernels are
found by name.
"""

from __future__ import annotations

import glob
import os

from pio_bench import xplane

_memo: dict = {}


def own_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0]


def load_named(trace_dir: str) -> dict:
    """{"ops": [(own name, duration_s)], "modules": [(name, duration_s)]} of
    the first device plane; memoised per trace directory (five readers
    share one parse)."""
    if trace_dir in _memo:
        return _memo[trace_dir]
    import jax

    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    out = {"ops": [], "modules": []}
    if found:
        data = jax.profiler.ProfileData.from_file(found[-1])
        planes = sorted((p for p in data.planes
                         if p.name.startswith(xplane.DEVICE_PREFIX)),
                        key=lambda p: p.name)
        for line in (planes[0].lines if planes else ()):
            if line.name == xplane.OPS_LINE:
                out["ops"] = [(own_name(e.name), e.duration_ns / 1e9)
                              for e in line.events]
            elif line.name == xplane.MODULES_LINE:
                out["modules"] = [(e.name, e.duration_ns / 1e9)
                                  for e in line.events]
    _memo.clear()
    _memo[trace_dir] = out
    return out


def op_seconds(ctx, needle: str):
    """(device seconds, events) of the ops named ``needle`` in the traced
    slice; (None, 0) where the trace has none."""
    trace_dir = ctx["device_trace"].get("trace_dir")
    if not trace_dir:
        return None, 0
    hits = [d for name, d in load_named(trace_dir)["ops"] if needle in name]
    return (sum(hits), len(hits)) if hits else (None, 0)


def program_seconds(ctx, needle: str = "pio_seq_forward"):
    """(device seconds, executions) of the sequence program in the slice."""
    mods = ctx["device_trace"]["modules"]
    hit = [m for n, m in mods.items() if needle in n]
    if not hit:
        return None, 0
    return (sum(m["seconds"] for m in hit), sum(m["count"] for m in hit))


def per_dispatch(ctx, key: str):
    """A scorer counter's mean per dispatch over the window."""
    from pio_bench.readers import delta

    total, calls = delta(ctx, key), delta(ctx, "fastpath.calls")
    return total / calls if total is not None and calls else None
