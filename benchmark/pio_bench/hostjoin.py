"""The host's spans joined to the device's programs, on the profiler's one
clock (ISSUE 37).  ``jax.profiler.ProfileData`` only.

What the program writes (``predictionio_tpu/obs/tracing.py``):

* ``pio_req.parse`` and ``pio_req.handle(id=)``: a request is in the server,
  from its request line to its answer's last byte;
* ``pio.<stage>(seq=, rung=)``: the stages of dispatch ``seq``, of which
  ``pio.device_compute`` holds the jitted call (``pio.launch``, the enqueue)
  and the wait for the device;
* the device plane's ``XLA Modules`` line: one event per executed program.

The join: a module event belongs to the ``pio.device_compute`` span it
overlaps most (spans of different dispatches never overlap: one run holds
the batcher at a time).  Per dispatch, with ``first`` / ``last`` the start of
its first and the end of its last module event,

    launch = first - span start,  device = last - first,  wake = span end - last

tile the span exactly.  **The device's clock is not the host's**: the
profiler converts device timestamps with an error of 0.4-1.3 ms, constant
over a slice of seconds (my chip runs, PR 37: in the ALS cell every program
"starts" 0.5-0.8 ms BEFORE the call that launches it, and in every cell
before the runtime's own ``DoEnqueueProgram`` hands it to the device).
``device`` and ``launch + wake`` do not depend on it; the split between
``launch`` and ``wake`` does.  So ``clock_shift_ns`` moves the device's
events by the LEAST shift that makes every dispatch causal: no program
starts before the runtime's ``DoEnqueueProgram`` inside its span does (the
launch span's start, else the span's, where the trace has no such event),
none ends after the ``ReadSyncFlag`` that notices its end (else the span's
end); it is 0 where they all are as recorded.  After a shift the quickest
pick-up of the slice (enqueue -> first op, tens of microseconds) reads 0:
``launch`` is low and ``wake`` high by that much.  ``contained`` says how
many programs lay inside their span as recorded, ``contained_shifted``
after the shift.

The partition: of the slice between the first and the last device op, the
device is busy (the union of the ``XLA Ops`` intervals), idle with a request
in hand (some ``pio_req.*`` span open) or idle with none.  A span that was
open when the session started, or still open when it stopped, is not in the
trace: a request's worth of presence is missed at either end of the slice.
"""

from __future__ import annotations

import bisect
import os
import statistics
import sys

if __name__ == "__main__":  # run as a script: find pio_bench as run.py does
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from pio_bench import xplane  # noqa: E402

# the TPU runtime's own host events (host_tracer_level 2): the program is
# handed to the device's queue; the host notices that it has finished
ENQUEUE, NOTICED = "DoEnqueueProgram", "ReadSyncFlag"

_memo: dict = {}

# the innermost open span that a held-idle second is charged to, most
# specific first; the worker's stages before a handler thread's
_LABELS = (
    "launch", "device_compute before the first op",
    "device_compute between ops", "device_compute after the last op",
    "device_compute with no program seen", "h2d", "batch_assembly", "d2h",
    "postprocess", "resolve", "collect", "decode", "serialize",
    "pio_req.parse", "in hand under no stage",
)


def load_planes(path: str) -> dict:
    """{"modules": [(start_ns, end_ns, name)], "ops": [(start_ns, end_ns)],
    "spans": [(name, start_ns, end_ns, stats)], "runtime": [(name, start_ns,
    end_ns)]}: the first device plane's programs and ops, every host plane's
    ``pio.*`` / ``pio_req.*`` events with their stats (``seq``, ``rung``,
    ``id``), and the runtime's enqueue and completion events."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"modules": [], "ops": [], "spans": [], "runtime": []}
    device = sorted((p for p in data.planes
                     if p.name.startswith(xplane.DEVICE_PREFIX)),
                    key=lambda p: p.name)
    for line in (device[0].lines if device else ()):
        if line.name == xplane.MODULES_LINE:
            out["modules"] = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                              for e in line.events]
        elif line.name == xplane.OPS_LINE:
            out["ops"] = [(e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events]
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(("pio.", "pio_req.")):
                        out["spans"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
                    elif e.name in (ENQUEUE, NOTICED):
                        out["runtime"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    return out


def _first_in(times: list, lo: float, hi: float, default: float) -> float:
    """The first of the sorted ``times`` in [lo, hi), else ``default``."""
    k = bisect.bisect_left(times, lo)
    return times[k] if k < len(times) and times[k] < hi else default


def join(planes: dict) -> dict:
    """Each ``pio.device_compute`` span with the module events it overlaps
    most, on the shifted device clock.  ``dispatches`` holds, per joined
    span, ``seq``, ``rung``, ``span`` (start, end), ``first``, ``last`` and
    ``launch_ms`` / ``device_ms`` / ``wake_ms``; ``unjoined_modules`` the
    programs under no span (the warm-up's tail, another scorer's),
    ``contained`` the share of joined programs that lay inside their span
    as recorded, ``clock_shift_ns`` what was added to the device's times."""
    spans = sorted((s, e, st) for n, s, e, st in planes["spans"]
                   if n == "pio.device_compute" and e > s)
    launched = sorted(s for n, s, _, _ in planes["spans"] if n == "pio.launch")
    groups: dict = {}
    unjoined = 0
    for ms, me, _ in planes["modules"]:
        best, best_ov = None, 0.0
        for i, (s, e, _) in enumerate(spans):
            ov = min(e, me) - max(s, ms)
            if ov > best_ov:
                best, best_ov = i, ov
        if best is None:
            unjoined += 1
        else:
            groups.setdefault(best, []).append((ms, me))
    # the least shift of the device clock that makes every dispatch causal:
    # lo <= shift <= hi, and 0 if that is allowed (where lo > hi no constant
    # shift fits every dispatch: starts stay causal, some wake reads < 0)
    runtime = planes.get("runtime", ())
    enqueued = sorted(s for n, s, _ in runtime if n == ENQUEUE)
    noticed = sorted(e for n, _, e in runtime if n == NOTICED)
    ends = {i: (min(m[0] for m in mods), max(m[1] for m in mods))
            for i, mods in groups.items()}
    lo, hi = float("-inf"), float("inf")
    for i, (first, last) in ends.items():
        s, e, _ = spans[i]
        handed = _first_in(enqueued, s, e, _first_in(launched, s, e, s))
        k = bisect.bisect_right(noticed, e) - 1
        seen = noticed[k] if k >= 0 and noticed[k] > handed else e
        lo, hi = max(lo, handed - first), min(hi, seen - last)
    joined = sum(len(mods) for mods in groups.values())
    shift = 0.0 if lo <= 0.0 <= hi else (lo if lo > 0.0 else hi)

    def inside(by: float) -> float:
        return sum(1 for i, mods in groups.items() for ms, me in mods
                   if ms + by >= spans[i][0] and me + by <= spans[i][1])

    dispatches = []
    for i in sorted(ends):
        s, e, st = spans[i]
        first, last = ends[i][0] + shift, ends[i][1] + shift
        dispatches.append({
            "seq": st.get("seq"), "rung": st.get("rung"), "span": (s, e),
            "first": first, "last": last,
            "launch_ms": (first - s) / 1e6,
            "device_ms": (last - first) / 1e6,
            "wake_ms": (e - last) / 1e6,
        })
    return {
        "dispatches": dispatches,
        "spans_without_program": len(spans) - len(groups),
        "unjoined_modules": unjoined,
        "contained": inside(0.0) / joined if joined else None,
        "contained_shifted": inside(shift) / joined if joined else None,
        "clock_shift_ns": shift,
        "shift_bounds_ns": (lo, hi),
    }


def partition(planes: dict, joined: dict) -> dict:
    """Seconds of the slice (first device op -> last) in which the device
    was busy, idle with a request in hand, idle with none; and the held-idle
    seconds by the innermost open span.  ``present`` is False where the
    program writes no ``pio_req.*`` span."""
    shift = joined["clock_shift_ns"]
    _, busy = xplane.union_seconds(
        (s + shift, e - s) for s, e, *_ in planes["ops"] or planes["modules"])
    if not busy:
        return {}
    t0, t1 = busy[0][0], busy[-1][1]
    by_span = {d["span"]: d for d in joined["dispatches"]}
    marks = [(s, 0, "busy") for s, _ in busy] + [(e, 1, "busy") for _, e in busy]
    have_presence = False

    def mark(label, s, e):
        if e > s:
            marks.append((s, 0, label))
            marks.append((e, 1, label))

    for name, s, e, st in planes["spans"]:
        if name.startswith("pio_req."):
            have_presence = True
            mark("present", s, e)
            if name == "pio_req.parse":
                mark(name, s, e)
        elif name == "pio.device_compute":
            d = by_span.get((s, e))
            if d is None:
                mark("device_compute with no program seen", s, e)
            else:
                mark("device_compute before the first op", s, d["first"])
                mark("device_compute between ops", d["first"], d["last"])
                mark("device_compute after the last op", d["last"], e)
        else:
            mark(name[len("pio."):], s, e)
    marks.sort(key=lambda m: (m[0], m[1]))
    open_now = dict.fromkeys(("busy", "present") + _LABELS, 0)
    out = {"slice_s": (t1 - t0) / 1e9, "busy_s": 0.0, "held_s": 0.0,
           "empty_s": 0.0, "held_by": dict.fromkeys(_LABELS, 0.0),
           "present": have_presence}
    prev = t0
    for t, closing, label in marks:
        a, b = max(prev, t0), min(t, t1)
        if b > a:
            dt = (b - a) / 1e9
            if open_now["busy"]:
                out["busy_s"] += dt
            elif open_now["present"]:
                out["held_s"] += dt
                out["held_by"][next(
                    (l for l in _LABELS if open_now.get(l)), _LABELS[-1])] += dt
            else:
                out["empty_s"] += dt
        prev = max(prev, t)
        if label in open_now:
            open_now[label] += -1 if closing else 1
    return out


def analyse(trace_dir: str) -> dict:
    """``join`` and ``partition`` of the trace under ``trace_dir``; memoised
    per directory (five readers share one parse).  {} where there is no
    trace or no device plane (a CPU rehearsal)."""
    if trace_dir not in _memo:
        _memo.clear()
        try:
            planes = load_planes(xplane.find(trace_dir or ""))
        except FileNotFoundError:
            planes = {"modules": []}
        if planes["modules"]:
            joined = join(planes)
            _memo[trace_dir] = {**joined, **partition(planes, joined)}
        else:
            _memo[trace_dir] = {}
    return _memo[trace_dir]


# -- what the metric readers call ---------------------------------------------


def dispatch_median(ctx, key: str):
    """Median over the slice's joined dispatches of ``launch_ms``,
    ``device_ms`` or ``wake_ms``."""
    got = analyse(ctx["device_trace"].get("trace_dir"))
    vals = [d[key] for d in got.get("dispatches", ())]
    return statistics.median(vals) if vals else None


def idle_share(ctx, key: str):
    """``held_s`` or ``empty_s`` as a share of the slice, %; None where the
    program marks no request presence (the split cannot be made)."""
    got = analyse(ctx["device_trace"].get("trace_dir"))
    if not got.get("present") or not got.get("slice_s"):
        return None
    return 100.0 * got[key] / got["slice_s"]


def meta_values(ctx, key: str) -> dict:
    """{request id: ``meta[key]``} of the answered traced requests that
    carry the key."""
    return {t["requestId"]: t["meta"][key] for t in ctx["traces"]
            if t.get("status") == 200 and key in t.get("meta", {})}


def unseen_ms(ctx) -> list:
    """Per answered request, what no server span covers: the client's
    (done - sent) less the trace's wall less ``meta.parse_ms``, joined on
    the generator's ``X-Request-Id: bench-<i>``."""
    parse = meta_values(ctx, "parse_ms")
    wall = {t["requestId"]: t["wallMs"] for t in ctx["traces"]
            if t.get("wallMs") is not None}
    out = []
    for r in ctx["good"]:
        rid = f"bench-{r['i']}"
        if rid in parse and rid in wall and r.get("sent") is not None:
            out.append((r["done"] - r["sent"]) * 1e3 - wall[rid] - parse[rid])
    return out


def main(argv) -> int:
    """``python3 benchmark/pio_bench/hostjoin.py <kept trace dir>``: the
    join's account of one kept trace (``PIO_BENCH_KEEP_TRACE``)."""
    if len(argv) != 2:
        print(main.__doc__, file=sys.stderr)
        return 4
    got = analyse(argv[1])
    if not got:
        print(f"hostjoin: no device plane under {argv[1]}", file=sys.stderr)
        return 1
    ds = got["dispatches"]
    print(f"dispatches joined {len(ds)}; spans without a program "
          f"{got['spans_without_program']}; programs under no span "
          f"{got['unjoined_modules']}; programs inside their span as recorded "
          f"{got['contained']:.4f}, after the device clock is shifted by "
          f"{got['clock_shift_ns'] / 1e6:+.4f} ms {got['contained_shifted']:.4f}"
          " (every dispatch is causal for a shift between %+.4f and %+.4f ms)"
          % tuple(b / 1e6 for b in got["shift_bounds_ns"]))
    print("per rung, medians in ms: n / launch / device / wake / span")
    for rung in sorted({d["rung"] for d in ds}, key=lambda r: (r is None, r)):
        rows = [d for d in ds if d["rung"] == rung]
        med = [statistics.median(d[k] for d in rows)
               for k in ("launch_ms", "device_ms", "wake_ms")]
        span = statistics.median(
            (d["span"][1] - d["span"][0]) / 1e6 for d in rows)
        print(f"  rung {rung}: {len(rows)} / {med[0]:.4f} / {med[1]:.4f} / "
              f"{med[2]:.4f} / {span:.4f}")
    if got.get("slice_s"):
        total = got["slice_s"]
        print(f"slice {total:.4f} s: busy {100 * got['busy_s'] / total:.2f} %"
              + (f", held {100 * got['held_s'] / total:.2f} %, empty "
                 f"{100 * got['empty_s'] / total:.2f} %"
                 if got["present"] else ", no request presence marked"))
        if got["present"] and got["held_s"]:
            print("held-idle seconds by the innermost open span:")
            for label, sec in sorted(got["held_by"].items(),
                                     key=lambda kv: -kv[1]):
                if sec:
                    print(f"  {label:40s} {sec:9.5f} s  "
                          f"{100 * sec / got['held_s']:6.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
