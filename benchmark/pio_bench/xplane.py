"""From a profiler trace (``.xplane.pb``) to device numbers, with nothing
but ``jax.profiler.ProfileData``.

A TPU device plane is named ``/device:TPU:<n>``.  Its line ``XLA Modules``
holds one event per executed program (the jitted function's name), its line
``XLA Ops`` one event per device operation inside them.  Busy time is the
union of the op intervals (of the module intervals where a plane has no op
line); idle gaps are the stretches of the traced slice in which no
operation ran.  Host planes (``/host:CPU``) hold one line per thread; a gap
is named by the host events that overlap it most.
"""

from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def _events(line) -> list:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def load(path: str) -> dict:
    """{plane name: {line name: [(event name, start_ns, duration_ns)]}}"""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: dict = {}
    for plane in data.planes:
        lines = out.setdefault(plane.name, {})
        for line in plane.lines:
            lines.setdefault(line.name, []).extend(_events(line))
    return out


def union_seconds(intervals) -> tuple:
    """(total covered ns, merged [(start, end)]) of (start, duration) pairs."""
    merged = []
    for s, d in sorted(intervals):
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_planes(planes: dict, window_s: float,
                  device_prefix: str = DEVICE_PREFIX) -> dict:
    """Device busy seconds (mean over device planes), the operations that
    took most device time, program (module) executions by name, and the
    longest idle gaps named by what the host was doing."""
    dev = {n: l for n, l in planes.items() if n.startswith(device_prefix)}
    if not dev:
        raise ValueError("the trace holds no TPU device plane")
    busy, op_time, op_count, modules = [], {}, {}, {}
    gaps = []
    for name, lines in sorted(dev.items()):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if ops is None:  # a CPU rehearsal: every line of the host plane
            ops = [e for evs in lines.values() for e in evs]
        covered, merged = union_seconds((s, d) for _, s, d in ops)
        busy.append(covered / 1e9)
        for n, _, d in ops:
            op_time[n] = op_time.get(n, 0.0) + d / 1e9
            op_count[n] = op_count.get(n, 0) + 1
        for n, _, d in lines.get(MODULES_LINE, []):
            m = modules.setdefault(n, {"seconds": 0.0, "count": 0})
            m["seconds"] += d / 1e9
            m["count"] += 1
        if name == min(dev):
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    n_dev = len(dev)
    host = []
    for pname, lines in planes.items():
        if pname.startswith("/host:"):
            for lname, evs in lines.items():
                host.extend((f"{lname}: {n}", s, s + d) for n, s, d in evs
                            if d > 0)
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
    named = []
    for g0, g1 in longest:
        best, best_ov = "host idle or untraced", 0.0
        for n, s, e in host:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = n, ov
        named.append([best[:120], (g1 - g0) / 1e9])
    top = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": window_s,
        "devices": n_dev,
        "top_ops": [[n[:120], t / n_dev] for n, t in top[:10]],
        "op_seconds": {n: t / n_dev for n, t in top},
        "op_counts": op_count,
        "modules": modules,
        "idle_gaps": named,
        "gap_count": len(gaps),
    }


def find(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, window_s: float,
               device_prefix: str = DEVICE_PREFIX) -> dict:
    return reduce_planes(load(find(trace_dir)), window_s, device_prefix)
