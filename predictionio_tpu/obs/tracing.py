"""Request-scoped tracing: per-stage breakdown, sampling, bounded ring.

A :class:`Trace` is born in ``common/http.py``'s ``_handle`` once the
request's headers are parsed and its body is read (the time before that,
from the request line's arrival on, rides on it as ``meta.parse_ms``), goes
with the request through the serving pipeline, and lands in a bounded
in-memory ring exposed at ``GET /trace/recent.json``.  Stages recorded on
the query path:

``decode`` → ``queue_wait`` (MicroBatcher) → ``batch_assembly`` → ``h2d``
→ ``device_compute`` → ``d2h`` → ``postprocess`` → ``serialize``; whatever
wall time the named stages don't cover lands in an explicit ``other``
remainder so the stage sum always reconciles with wall time.

The micro-batcher keeps one :class:`Dispatch` record per batch run whether
or not a sampled request rides it; :func:`stage` charges the shared stages
to that record too, and enters ``jax.profiler.TraceAnnotation("pio.<name>")``
so a profiler session shows the host stages on the device ops' clock.

One clock, one chain of identifiers: ``pio_req.parse`` and
``pio_req.handle(id=<request id>)`` (``common/http.py``) say on the
profiler's clock that a request is in the server; the request's
:class:`Trace` has the same id and names the dispatch that carried it
(``meta.dispatch_seq``); that dispatch's spans carry ``seq`` (and ``rung``);
its device program is the ``XLA Modules`` event inside its
``pio.device_compute`` span, whose enqueue is ``pio.launch``
(:func:`launch`).  The ``pio_req.`` prefix keeps request presence out of
readers that union the ``pio.`` stages.

Propagation contract (documented in docs/observability.md):

* The ``X-Request-Id`` header carries the trace id.  A request that
  ARRIVES with one is always sampled (upstream already decided), and the
  id is propagated by the NetworkStorage client on every outgoing call so
  a query's storage round-trips correlate across services.  The response
  echoes the id back.
* Requests without the header are head-sampled at ``PIO_TRACE_SAMPLE``
  (deterministic every-Nth admission — no RNG in the hot path).
* Finished traces are additionally TAIL-sampled: walls above a rolling
  quantile (``PIO_SLOW_TRACE_QUANTILE``) land in a second bounded ring
  (``PIO_SLOW_TRACE_RING``) at ``GET /trace/slow.json`` — the flight
  recorder that explains the p99 instead of merely counting it.

Cross-thread attribution: the micro-batcher executes ONE batch for many
requests, so the worker thread installs every batch member's trace as
"active" (:func:`scope`) and shared stages (``h2d``, ``device_compute``)
are charged to each of them — the per-request view stays truthful about
where its wall time went even when the work was amortized.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
import time
import uuid
from collections import deque
from typing import Optional, Sequence

TRACE_HEADER = "X-Request-Id"

DEFAULT_SAMPLE_RATE = 0.1
DEFAULT_RING_SIZE = 256
# flight recorder (tail sampling): retain traces whose wall exceeds this
# rolling quantile of recent request walls, in a ring of this size
DEFAULT_SLOW_QUANTILE = 0.99
DEFAULT_SLOW_RING_SIZE = 64
# wall-time reservoir backing the rolling quantile; threshold is
# recomputed every _SLOW_RECOMPUTE records so the hot path stays O(1)
_SLOW_RESERVOIR = 512
_SLOW_RECOMPUTE = 16
# tail sampling stays off until the reservoir has seen this many walls —
# with two data points "the 99th percentile" would just be the max
_SLOW_MIN_SAMPLES = 16


class Trace:
    """One sampled request: stage durations + identity. Thread-safe."""

    __slots__ = (
        "request_id", "name", "start_unix", "_t0", "stages", "meta",
        "wall_s", "status", "_lock",
    )

    def __init__(self, request_id: str, name: str = ""):
        self.request_id = request_id
        self.name = name
        self.start_unix = time.time()
        self._t0 = time.perf_counter()
        self.stages: dict[str, float] = {}
        self.meta: dict = {}
        self.wall_s: Optional[float] = None
        self.status: Optional[int] = None
        self._lock = threading.Lock()

    def add_stage(self, stage: str, seconds: float) -> None:
        """Accumulate time into a named stage (re-entry adds, not replaces)."""
        if seconds < 0:
            seconds = 0.0
        with self._lock:
            self.stages[stage] = self.stages.get(stage, 0.0) + seconds

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add_stage(name, time.perf_counter() - t0)

    def annotate(self, **kv) -> None:
        """Attach request context (bucket, batch size, cache disposition…)
        to the trace — the flight recorder's "why was this slow" fields."""
        with self._lock:
            self.meta.update(kv)

    def finish(self, status: Optional[int] = None) -> None:
        wall = time.perf_counter() - self._t0
        with self._lock:
            self.wall_s = wall
            self.status = status
            # the explicit remainder: stage sum ≡ wall by construction, so
            # a reader never wonders whether missing time means missing
            # instrumentation or missing truth
            covered = sum(self.stages.values())
            self.stages["other"] = max(0.0, wall - covered)

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "requestId": self.request_id,
                "name": self.name,
                "startUnix": round(self.start_unix, 6),
                "wallMs": (
                    None if self.wall_s is None
                    else round(self.wall_s * 1e3, 4)
                ),
                "status": self.status,
                "stagesMs": {
                    k: round(v * 1e3, 4) for k, v in self.stages.items()
                },
                **({"meta": dict(self.meta)} if self.meta else {}),
            }


class Dispatch:
    """One batch run of the micro-batcher: a fixed-size record.

    Written only by the thread that runs it (no lock; two runs may overlap,
    each on its own thread with its own record); the stage keys exist from
    the start, so a reader that serializes a run still in flight never sees
    the dict change size.  ``collect`` and ``resolve`` are set by the
    batcher, the rest by :func:`stage`; ``postprocess`` also takes whatever
    part of the run no stage covered, so the stages tile the record's wall.

    The scorer tells the batcher when the run's device program is enqueued
    through the record: it sets ``rung`` and ``lag`` (and ``more``, while
    launches of the same run are still to come) before :func:`launch`, which stamps
    ``t_launch`` / ``t_enqueued`` and calls ``on_launch``.  A run launched
    while another was in flight has that run's record as ``behind`` until
    its own end.
    """

    STAGES = (
        "collect", "batch_assembly", "h2d", "device_compute", "d2h",
        "postprocess", "resolve",
    )

    __slots__ = (
        "seq", "start_unix", "t_run", "thread", "thread_id", "inline",
        "rows", "rung", "merge_passes", "carried", "padded", "depth_end",
        "stages",
        "dc_start", "dc_end", "wall_s", "error", "slow_after_s",
        "more", "lag", "t_launch", "t_enqueued", "behind", "ahead_s",
        "on_launch",
    )

    def __init__(self, seq: int, inline: bool, rows: int, carried: int,
                 t_run: float, collect_s: float, slow_after_s: float,
                 padded: int = 0):
        th = threading.current_thread()
        self.seq = seq
        self.t_run = t_run  # the run's start; the record's is collect_s earlier
        self.slow_after_s = slow_after_s  # a run longer than this is slow
        self.start_unix = time.time() - collect_s
        self.thread, self.thread_id = th.name, th.ident
        self.inline, self.rows, self.carried = inline, rows, carried
        # rows short of the batcher's rung: what a bucketed scorer pads
        self.padded = padded
        self.rung: Optional[int] = None
        # the score kernel's merge passes that inserted, over the run's
        # device programs (0 where the program does not count them)
        self.merge_passes = 0
        self.depth_end: Optional[int] = None
        self.stages = dict.fromkeys(self.STAGES, 0.0)
        self.stages["collect"] = collect_s
        # first launch / last completion of the device program: what the
        # batcher's turnaround counter is measured between
        self.dc_start: Optional[float] = None
        self.dc_end: Optional[float] = None
        self.wall_s: Optional[float] = None
        self.error: Optional[str] = None
        # the scorer has further launches to make in this run (rows past
        # the top rung): no estimate of the run's end can be made from
        # this one, so it is not stamped
        self.more = False
        # how long after a program's end the host hears of it, as the
        # scorer measured it at warm-up (0: it did not say)
        self.lag = 0.0
        # the last launch of the run: the jitted call entered / returned
        # (the program is enqueued).  None until then, and for good where
        # nothing launches through :func:`launch`
        self.t_launch: Optional[float] = None
        self.t_enqueued: Optional[float] = None
        # the run in flight this one was cut and launched behind (dropped
        # at its end, or the records would chain for ever); set at the
        # end: how long before that run's device_compute returned its
        # program was launched (positive: ahead of it); the batcher's
        # wake-up of whoever waits to launch behind THIS one
        self.behind: Optional["Dispatch"] = None
        self.ahead_s: Optional[float] = None
        self.on_launch = None

    def add_stage(self, name: str, t0: float, t1: float) -> None:
        # one writer: the thread that runs this dispatch
        self.stages[name] = self.stages.get(name, 0.0) + (t1 - t0)  # pio: ignore[race-unguarded-rmw]
        if name == "device_compute":
            if self.dc_start is None:
                self.dc_start = t0
            self.dc_end = t1

    def to_dict(self) -> dict:
        return {
            "seq": self.seq,
            "startUnix": round(self.start_unix, 6),
            "startMonotonic": round(
                self.t_run - self.stages["collect"], 6
            ),
            "thread": self.thread,
            "threadId": f"{self.thread_id:#018x}",
            "inline": self.inline,
            "rows": self.rows,
            "rung": self.rung,
            "mergePasses": self.merge_passes,
            "carriedRows": self.carried,
            "paddedRows": self.padded,
            "depthAtEnd": self.depth_end,
            # in flight: it was launched behind a run; after: ahead of it
            "launchedAhead": (
                self.behind is not None if self.ahead_s is None
                else self.ahead_s > 0
            ),
            "aheadMs": (
                None if self.ahead_s is None
                else round(self.ahead_s * 1e3, 4)
            ),
            "slowAfterMs": round(self.slow_after_s * 1e3, 4),
            "wallMs": (
                None if self.wall_s is None
                else round(self.wall_s * 1e3, 4)
            ),
            "stagesMs": {
                k: round(v * 1e3, 4) for k, v in self.stages.items()
            },
            **({"error": self.error} if self.error else {}),
        }


# -- active-trace propagation (thread-local) ---------------------------------

_active = threading.local()


def active_traces() -> Sequence[Trace]:
    return getattr(_active, "traces", ())


def active_dispatch() -> Optional[Dispatch]:
    return getattr(_active, "dispatch", None)


@contextlib.contextmanager
def scope(
    traces: Sequence[Optional[Trace]], dispatch: Optional[Dispatch] = None
):
    """Install traces (and the batch run they ride) as this thread's
    active set for the duration.

    The HTTP thread scopes its single request trace around dispatch; the
    micro-batcher scopes the whole batch's traces and its
    :class:`Dispatch` record around execute.
    """
    prev = getattr(_active, "traces", ()), getattr(_active, "dispatch", None)
    _active.traces = tuple(t for t in traces if t is not None)
    _active.dispatch = dispatch
    try:
        yield
    finally:
        _active.traces, _active.dispatch = prev


_TraceAnnotation = None
_NO_SPAN = contextlib.nullcontext()


def annotation(name: str, **kv):
    """``jax.profiler.TraceAnnotation(name, **kv)``: a host span on the
    profiler's clock.  Without a profiler session entering one is a flag
    test; a process that never loaded jax has no session to write to."""
    global _TraceAnnotation
    if _TraceAnnotation is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return _NO_SPAN
        _TraceAnnotation = jax.profiler.TraceAnnotation
    return _TraceAnnotation(name, **kv)


def _ids(disp: Optional[Dispatch]) -> dict:
    """What joins a dispatch's spans to its record: ``seq``, and ``rung``
    once the scorer has set it."""
    if disp is None:
        return {}
    if disp.rung is None:
        return {"seq": disp.seq}
    return {"seq": disp.seq, "rung": disp.rung}


@contextlib.contextmanager
def launch():
    """``pio.launch(seq=, rung=)``: a scorer's jitted call until it returns,
    which is the enqueue; the wait for the device is the rest of the
    ``pio.device_compute`` stage it lies in.  On the profiler's clock only:
    it is charged to no trace and no stage.  The active dispatch record is
    told both instants (unless the scorer said ``more`` launches follow),
    and whoever waits to launch behind this run is woken."""
    disp = getattr(_active, "dispatch", None)
    last = disp is not None and not disp.more
    if last:
        disp.t_enqueued = None
        disp.t_launch = time.perf_counter()
    with annotation("pio.launch", **_ids(disp)):
        yield
    if last:
        disp.t_enqueued = time.perf_counter()
        if disp.on_launch is not None:
            disp.on_launch()


@contextlib.contextmanager
def stage(name: str):
    """Charge the enclosed wall time to ``name`` on every active trace and
    on the active dispatch record, as ``pio.<name>`` on the profiler's
    clock.

    With neither active it is three attribute lookups and allocates
    nothing — cheap enough to leave in hot loops permanently.
    """
    traces = getattr(_active, "traces", ())
    disp = getattr(_active, "dispatch", None)
    if not traces and disp is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        with annotation("pio." + name, **_ids(disp)):
            yield
    finally:
        t1 = time.perf_counter()
        for t in traces:
            t.add_stage(name, t1 - t0)
        if disp is not None:
            disp.add_stage(name, t0, t1)


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


class Tracer:
    """Head sampler + bounded ring of finished traces + flight recorder.

    The flight recorder is TAIL-based: after a sampled trace finishes,
    its wall time is compared against a rolling quantile
    (``PIO_SLOW_TRACE_QUANTILE``) of recent walls, and outliers are
    retained — with their full stage breakdown and meta — in a second
    bounded ring (``PIO_SLOW_TRACE_RING``) served at
    ``GET /trace/slow.json``.  The p99 is explained, not just counted.
    """

    def __init__(
        self,
        sample_rate: Optional[float] = None,
        ring_size: Optional[int] = None,
        slow_quantile: Optional[float] = None,
        slow_ring_size: Optional[int] = None,
    ):
        if sample_rate is None:
            sample_rate = float(
                os.environ.get("PIO_TRACE_SAMPLE", DEFAULT_SAMPLE_RATE)
            )
        if ring_size is None:
            ring_size = int(
                os.environ.get("PIO_TRACE_RING", DEFAULT_RING_SIZE)
            )
        if slow_quantile is None:
            slow_quantile = float(
                os.environ.get(
                    "PIO_SLOW_TRACE_QUANTILE", DEFAULT_SLOW_QUANTILE
                )
            )
        if slow_ring_size is None:
            slow_ring_size = int(
                os.environ.get(
                    "PIO_SLOW_TRACE_RING", DEFAULT_SLOW_RING_SIZE
                )
            )
        self.sample_rate = min(1.0, max(0.0, float(sample_rate)))
        self.ring_max = max(1, int(ring_size))
        self.ring: deque = deque(maxlen=self.ring_max)
        self.seen = 0
        self.sampled = 0
        self._acc = 0.0
        self._lock = threading.Lock()
        # flight recorder state (slow_quantile <= 0 disables retention)
        self.slow_quantile = min(1.0, float(slow_quantile))
        self.slow_ring_max = max(1, int(slow_ring_size))
        self.slow_ring: deque = deque(maxlen=self.slow_ring_max)
        self.slow_retained = 0
        self._walls: deque = deque(maxlen=_SLOW_RESERVOIR)
        self._slow_threshold: Optional[float] = None
        self._since_recompute = 0

    def begin(
        self,
        request_id: Optional[str] = None,
        name: str = "",
    ) -> Optional[Trace]:
        """Head-sampling decision; returns a live Trace or None.

        An explicit ``request_id`` (the header arrived) always samples —
        upstream made the decision and cross-service stitching needs the
        downstream half.  Otherwise a deterministic every-Nth accumulator
        admits ``sample_rate`` of traffic with zero RNG cost.
        """
        with self._lock:
            self.seen += 1
            if request_id is None:
                self._acc += self.sample_rate
                if self._acc < 1.0:
                    return None
                self._acc -= 1.0
            self.sampled += 1
        return Trace(request_id or new_request_id(), name=name)

    def record(self, trace: Trace) -> None:
        self.ring.append(trace)  # deque append is atomic
        wall = trace.wall_s
        if wall is None or self.slow_quantile <= 0.0:
            return
        with self._lock:
            # threshold from the reservoir BEFORE admitting this wall, so
            # a request is never judged against a sample that includes it
            thr = self._slow_threshold
            retain = (
                thr is not None
                and len(self._walls) >= _SLOW_MIN_SAMPLES
                and wall > thr
            )
            self._walls.append(wall)
            self._since_recompute += 1
            if (
                self._slow_threshold is None
                or self._since_recompute >= _SLOW_RECOMPUTE
            ):
                self._since_recompute = 0
                ordered = sorted(self._walls)
                i = min(
                    len(ordered) - 1,
                    int(self.slow_quantile * len(ordered)),
                )
                self._slow_threshold = ordered[i]
            if retain:
                self.slow_retained += 1
                self.slow_ring.append(trace)

    def slow_threshold_s(self) -> Optional[float]:
        """Current rolling-quantile wall threshold (None until warmed)."""
        with self._lock:
            if len(self._walls) < _SLOW_MIN_SAMPLES:
                return None
            return self._slow_threshold

    def recent(self, limit: Optional[int] = None) -> list:
        traces = list(self.ring)
        if limit:
            traces = traces[-limit:]
        return [t.to_dict() for t in reversed(traces)]

    def slow_recent(self, limit: Optional[int] = None) -> list:
        """Retained slow-request exemplars, newest first."""
        traces = list(self.slow_ring)
        if limit:
            traces = traces[-limit:]
        return [t.to_dict() for t in reversed(traces)]
