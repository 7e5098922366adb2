"""Request micro-batching: coalesce concurrent queries into one device pass.

TPU serving throughput comes from batching: a single (B, rank)×(rank, items)
scoring pass costs barely more than B=1, and every device round trip has
a fixed latency floor.  The reference has no analogue
(its predict path is per-request JVM work, ``CreateServer.scala:508``).

:class:`MicroBatcher` sits between HTTP handler threads and the engine:
handlers enqueue (query, event) pairs and block; a worker drains the queue,
coalesces a batch, routes it through ``Algorithm.batch_predict`` (which
engines like ALS vectorize on device), and wakes each handler with its
result.  Errors are delivered per-request.

There is NO accumulation window: a free device is never held.

* TRICKLE BYPASS: a request that finds nothing waiting anywhere (queued,
  carried by the cut, or in the worker's hand) and no run in flight
  executes inline on its own handler thread — zero added latency over
  the unbatched path.  Batches form exactly when they can help: while a
  run is in flight, arrivals queue up and dispatch together.
* A WORKER WAITS FOR THE RUN IN FLIGHT, never for a clock.  With a
  first row in hand it takes ``_busy`` — which blocks exactly while a run
  is in flight, the only time waiting is free — drains what queued up
  meanwhile and runs.  With the device free that is immediate: a row's
  wait is the rest of the run in flight, the hand-off, its own run.
* LAUNCH-AHEAD: THE NEXT RUN IS LAUNCHED BEFORE THE ONE IN FLIGHT ENDS.
  JAX's dispatch is asynchronous and the device runs enqueued programs in
  order, so with rows waiting the next run is collected, cut and launched
  by the OTHER of two workers shortly before the run in flight is due —
  (when its program could start) + (what ``device_compute`` has been
  taking at the rung its scorer named) − (what a worker has been taking
  from the go to the enqueue), each the least of its newest ``RUNS_KEPT``
  readings, aimed at the program's END by the lag the scorer measured
  between an end and the host hearing of it
  (:meth:`MicroBatcher._behind_in`) — and the host's handling of
  the earlier run (wake-up, readback, ``postprocess``, ``resolve``)
  overlaps the later one's device time instead of preceding its launch.
  The cut stays as late as it can; at most ONE run is queued behind the
  one in flight (:class:`_Flight`); a rung with no estimate yet, or a run
  whose scorer launches nothing through ``obs.tracing.launch()``, is
  waited out as before, and a lone request on a free device still runs
  inline.  Whether two programs' temporaries fit the device together is
  the scorer's to know (``serving/launch_gate.py``).
* A NEWCOMER JOINS THE ROWS THAT WAIT.  One that finds the device free
  but older rows waiting (the instant of a hand-off) queues behind them
  and leaves in their dispatch (``joined_rows``): no dispatch runs while
  an older row waits outside it that its rung had room for (FIFO).
* THE CUT is decided from the batcher's own run times.  Rows in hand
  that fall between two rungs of the compile-cache ladder
  (``serving/fastpath.py``) either all run now, one dispatch rounded up to
  the next rung (the scorer pads it), or are cut at the rung below with
  the tail carried to lead the next batch (FIFO) — whichever finishes the
  waiting rows sooner by the time a run at each rung has been taking
  (:meth:`MicroBatcher._cut`).  Where a rung costs about the same as the
  one below it (the ALS score program: 9.2 ms at rung 1, 9.7 at rung 8)
  2–7 waiting rows run together; where time goes with the rows (host
  code, or 33 rows against rungs 32 / 64) the batch is cut as before.  A
  ladder with every row count never falls between rungs.

SINGLE-FLIGHT COALESCING (opt-in via ``submit(key=...)``): identical
in-flight queries — same canonical fingerprint — attach to ONE pending
slot.  The first arrival is the leader and occupies a device row; later
identical arrivals become followers and never enter the queue at all.
When the leader's batch delivers, the one result fans out to every
follower (errors too: a failed batch fails all attached waiters, none
hang).  Under Zipf traffic a hot key therefore costs one device slot per
batch regardless of popularity.  If the leader's deadline lapses before
dispatch, a live follower is PROMOTED to leader so the survivors don't
inherit a 504 they didn't earn.

ONE RECORD PER DISPATCH, always on: every batch run gets a sequence
number and an :class:`obs.tracing.Dispatch` (who ran it, rows, rung, rows
the cut carried or the rung padded, whether it was launched ahead and by
how much, the wall of each stage), kept in a
bounded ring that ``GET /trace/dispatches.json`` serves and summed into
:meth:`stats`.  A run
that holds the batcher past ``max(SLOW_FLOOR_S, SLOW_MULT x EWMA(run))``
has every thread's stack written to the server's log by
``faulthandler``'s watchdog (a C thread: it fires even if the stalled
thread never releases the GIL; ONE timer a process, kept armed for
whichever run in flight is due first) and its record kept in a second
ring.
"""

from __future__ import annotations

import bisect
import collections
import faulthandler
import logging
import queue
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from time import monotonic as _monotonic
from typing import Any, Callable, Optional

from predictionio_tpu.common.resilience import Deadline, DeadlineExceeded
from predictionio_tpu.obs import tracing as _tracing

logger = logging.getLogger(__name__)

# default ladder mirrors serving/fastpath.BUCKETS without importing jax here
_DEFAULT_BUCKETS = (1, 8, 16, 32, 64)


@dataclass
class _Pending:
    query: Any
    deadline: Optional[Deadline] = None
    event: threading.Event = field(default_factory=threading.Event)
    result: Any = None
    error: Optional[BaseException] = None
    # obs trace riding this query (captured from the submitting thread's
    # active scope) + enqueue stamp for the queue_wait stage + the last
    # dispatch that had finished by then (its own seq minus this = passes)
    trace: Any = None
    t_enq: float = 0.0
    done_at_enq: int = 0
    # when _resolve set the event (traced pendings only): the hand-back to
    # the handler thread is measured from it
    t_set: float = 0.0
    # single-flight: the coalescing key this pending leads (None = not
    # coalescable) and the identical-query followers its result fans out to
    key: Any = None
    followers: list = field(default_factory=list)


class _Flight:
    """The right to launch a run: the lock ``_busy`` was, made for two.

    Lock-shaped (``acquire`` / ``release`` / ``locked``), and a lock for
    everyone but a worker: an arrival's non-blocking try succeeds only
    with no run in flight, and every run releases once at its end, on
    whatever thread.  A BLOCKING acquire also succeeds behind ONE run in
    flight, at the instant ``behind_in`` names for it (seconds from now and
    that run's record, or None while nothing can be said): it then returns
    the record instead of True, and two runs are in flight until either
    ends.  Never three: one program queued behind the one running.
    ``turn`` is notified at every release, and by the batcher when a run
    in flight has launched.
    """

    def __init__(self, behind_in: Callable[[], Optional[tuple]]):
        self.turn = threading.Condition()
        self._behind_in = behind_in
        self._runs = 0

    def locked(self) -> bool:
        return self._runs > 0

    def acquire(self, blocking: bool = True, timeout: float = -1):
        with self.turn:
            end = None if timeout < 0 else _monotonic() + timeout
            while self._runs:
                if not blocking:
                    return False
                plan = self._behind_in() if self._runs == 1 else None
                if plan is not None and plan[0] <= 0:
                    self._runs = 2
                    return plan[1]
                wait = None if plan is None else plan[0]
                if end is not None:
                    left = end - _monotonic()
                    if left <= 0:
                        return False
                    wait = left if wait is None else min(wait, left)
                self.turn.wait(wait)
            self._runs = 1
            return True

    def release(self) -> None:
        with self.turn:
            self._runs -= 1
            self.turn.notify_all()


class MicroBatcher:
    # EWMA smoothing of the run-time estimator
    ALPHA = 0.2
    # dispatch records kept for GET /trace/dispatches.json (at 4 dispatches
    # a second, a minute), and the slow ones ordinary traffic never evicts
    RING = 256
    SLOW_RING = 16
    # a run that holds the batcher past max(floor, mult x EWMA(run)) is
    # slow: stacks are dumped, it is counted and its record is kept
    SLOW_FLOOR_S = 2.0
    SLOW_MULT = 8.0
    # where the watchdog writes the stacks: None is the server's log
    # (sys.stderr when the run is armed); it must have a file descriptor
    SLOW_DUMP_FILE = None
    # the cut's estimate of a run at a rung (and of the gap between two
    # runs) is the LEAST of this many newest ones
    RUNS_KEPT = 5

    def __init__(
        self,
        run_batch: Callable[[list], list],
        max_batch: int = 64,
        buckets=_DEFAULT_BUCKETS,
    ):
        self._run_batch = run_batch
        self.max_batch = max_batch
        self.buckets = tuple(
            sorted({b for b in buckets if b <= max_batch} | {max_batch})
        )
        self._queue: "queue.Queue[_Pending]" = queue.Queue()
        self._carry: collections.deque[_Pending] = collections.deque()
        self._stop = threading.Event()
        # rows submitted and not yet in a dispatch: queued, carried by the
        # cut, or in the worker's hand.  Counted from the put on, so a row
        # the worker has just taken is never overtaken.  _arr_lock guards
        # the count with the put and the drain; it is taken after _busy,
        # never before it
        self._arr_lock = threading.Lock()
        self._waiting = 0
        self._n_joined = 0  # arrivals that found _busy free, rows waiting
        # what a run has been taking (the slow-run threshold reads it); 0
        # until the first run returns
        self._ewma_run = 0.0
        # the right to launch: held by every batch run (worker or inline)
        # for its duration, by two at once only where the second was
        # launched ahead (_Flight); _turn is its condition
        self._busy = _Flight(self._behind_in)
        self._turn = self._busy.turn
        # one worker collects (first row, the wait for _busy, drain, cut)
        # while the other may still be in its run
        self._collecting = threading.Lock()
        # single-flight: key → leader pending currently in flight.  The
        # lock guards the map AND every leader's followers list; delivery
        # pops the key first, so a follower can never attach to a pending
        # whose result already fanned out.
        self._key_lock = threading.Lock()
        self._inflight_keys: dict[Any, _Pending] = {}
        # counters (read by stats())
        self._stats_lock = threading.Lock()
        self._n_batches = 0
        self._n_queries = 0
        self._n_inline = 0
        self._n_coalesced = 0  # followers served by a leader's device slot
        self._n_expired = 0  # pendings dropped un-executed (deadline lapsed)
        self._size_hist: collections.Counter = collections.Counter()
        self._wait_s_total = 0.0
        # dispatch records.  A record is written by the thread that runs
        # it; everything below that two overlapping runs share moves under
        # _stats_lock
        self._seq = 0  # dispatches started == seq of the newest
        self._done = 0  # the highest seq whose run has returned
        self._ring: collections.deque = collections.deque(maxlen=self.RING)
        self._slow_ring: collections.deque = collections.deque(
            maxlen=self.SLOW_RING
        )
        # the runs in flight, oldest first: one, or two where the second
        # was launched ahead
        self._flying: list[_tracing.Dispatch] = []
        # of the newest run (by seq) that has ended: what the next one,
        # started on a free device, measures its turnaround and gap from
        self._prev_seq = 0
        self._prev_dc_end: Optional[float] = None
        self._prev_left_work = False
        self._carried_rows = 0
        # what the cut decides from (see _cut): the newest runs at each
        # rung as (seq, seconds), and the newest gaps between a run's end
        # and the next one's start with rows waiting (under _stats_lock)
        self._rung_runs = {
            b: collections.deque(maxlen=self.RUNS_KEPT) for b in self.buckets
        }
        self._run_gaps: collections.deque = collections.deque(
            maxlen=self.RUNS_KEPT
        )
        self._prev_run_end: Optional[float] = None
        # what launch-ahead decides from (see _behind_in): per rung AS THE
        # SCORER NAMED IT (a token count for the packed families, which the
        # row-count rungs above mix), the newest (seq, seconds) its program
        # took on the device; and the newest times a worker took from the
        # go to the enqueue
        self._launch_runs: dict[int, collections.deque] = {}
        self._leads: collections.deque = collections.deque(
            maxlen=self.RUNS_KEPT
        )
        self._planned = False  # the collecting worker saw a launch instant
        self._n_ahead = 0
        self._n_ahead_missed = 0
        self._n_rounded_up = 0
        self._padded_rows = 0
        self._run_s_sum = 0.0
        self._run_s_max = 0.0
        self._run_max_seq = 0
        self._turnaround_s_sum = 0.0
        self._turnaround_n = 0
        self._n_slow = 0
        self._workers = [
            threading.Thread(
                target=self._loop, name="query-microbatcher", daemon=True
            )
            for _ in range(2)
        ]
        for w in self._workers:
            w.start()

    def submit(
        self,
        query: Any,
        timeout: float = 30.0,
        deadline: Optional[Deadline] = None,
        key: Any = None,
    ) -> Any:
        """Enqueue one query; block until its batch runs or the deadline
        passes.

        The effective deadline is ``min(request deadline, now + timeout)``
        and travels WITH the pending: a request whose deadline lapses while
        queued is dropped at dispatch (never executed on device — the
        waiter already gave up, running it would burn a device pass on an
        answer nobody reads) and its waiter gets :class:`DeadlineExceeded`.

        ``key`` opts this query into single-flight coalescing: when an
        identical key is already in flight, this call attaches to the
        leader's pending and shares its result instead of occupying a
        device row of its own.  The key the server passes is the
        tenant-NAMESPACED canonical fingerprint (tenant + variant +
        engine instance prefix — ``result_cache.canonical_fingerprint``):
        two tenants sending byte-identical bodies must never share a
        leader slot, or one tenant's answer leaks to the other.
        """
        now = time.perf_counter()
        eff = Deadline.min(deadline, Deadline.after_ms(timeout * 1e3))
        active = _tracing.active_traces()
        p = _Pending(
            query, deadline=eff,
            trace=active[0] if active else None, t_enq=now,
            done_at_enq=self._done, key=key,
        )
        if eff.expired():
            # already over budget at arrival: shed before any queue/device
            # work (the admission layer normally catches this first)
            with self._stats_lock:
                self._n_expired += 1
            raise DeadlineExceeded("query deadline expired before dispatch")
        if key is not None:
            with self._key_lock:
                leader = self._inflight_keys.get(key)
                if leader is not None:
                    # FOLLOWER: ride the leader's device slot; its delivery
                    # fans the one result (or error) out to us
                    leader.followers.append(p)
                else:
                    self._inflight_keys[key] = p
            if leader is not None:
                with self._stats_lock:
                    self._n_coalesced += 1
                if p.trace is not None:
                    # flight-recorder context: this request rode another
                    # identical query's device slot — its trace must NOT
                    # carry device stages (charged once, to the leader)
                    p.trace.annotate(coalesce="follower")
                if not p.event.wait(eff.remaining_s()):
                    # the leader's batch will still resolve this pending
                    # (harmlessly, after we've gone) — nothing dangles
                    raise DeadlineExceeded("coalesced query timed out")
                if p.error is not None:
                    raise p.error
                return p.result
        # TRICKLE BYPASS: nothing waits anywhere and no run is in flight —
        # execute the singleton inline on this handler thread.  A lone
        # request then pays exactly the direct-path cost (no worker hop),
        # while coalescing still happens whenever a run IS in flight:
        # arrivals pile into the queue and the worker drains them as one
        # batch.  With older rows waiting the free device is theirs: this
        # one queues behind them while it still holds _busy, so the worker
        # (which takes _busy before it drains) finds it in their dispatch.
        free = self._busy.acquire(blocking=False)
        try:
            with self._arr_lock:
                inline = free and self._waiting == 0
                if not inline:
                    self._n_joined += free
                    self._waiting += 1
                    self._queue.put(p)
            if inline:
                self._execute([p], waited=0.0, inline=True)
        finally:
            if free:
                self._busy.release()
        if not inline:
            if not p.event.wait(eff.remaining_s()):
                # the pending stays queued, but its deadline has passed —
                # the worker is GUARANTEED to drop it at dispatch (same
                # monotonic clock), so the device never runs an abandoned
                # query
                raise DeadlineExceeded("batched query timed out")
            if p.t_set:
                # the hand-back: the worker set the event -> this thread
                # runs again (the GIL's turn-taking; an inline run has none)
                p.trace.annotate(handback_ms=round(
                    (time.perf_counter() - p.t_set) * 1e3, 4))
        if p.error is not None:
            raise p.error
        return p.result

    def stop(self) -> None:
        self._stop.set()
        for w in self._workers:
            w.join(timeout=5)
        # wake anything still waiting so handlers fail fast, not on
        # timeout (a worker has put back what it had in hand; runs in
        # flight deliver to their own waiters when they return)
        with self._arr_lock:
            pending = list(self._carry)
            self._carry.clear()
            while True:
                try:
                    pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            self._waiting = 0
        err = RuntimeError("server shutting down")
        for p in pending:
            self._resolve(p, error=err)

    def depth(self) -> int:
        """Rows waiting for a dispatch: queued, carried, or in the worker's
        hand (admission-control signal)."""
        return self._waiting

    def stats(self) -> dict:
        """Per-batch latency/size/occupancy counters (``GET /`` stats)."""
        with self._stats_lock:
            n_b, n_q = self._n_batches, self._n_queries
            # a run past its threshold counts while it still holds the
            # batcher: a stall shows here before (or without) its end
            now = time.perf_counter()
            stalled = sum(
                now - r.t_run > r.slow_after_s for r in self._flying
            )
            return {
                "batches": n_b,
                "queries": n_q,
                "inline_batches": self._n_inline,
                "coalesced": self._n_coalesced,
                "expired_dropped": self._n_expired,
                "depth": self.depth(),
                "avg_batch": round(n_q / n_b, 3) if n_b else None,
                "batch_sizes": {str(k): v for k, v in sorted(self._size_hist.items())},
                "avg_window_wait_ms": round(self._wait_s_total / n_b * 1e3, 4)
                if n_b
                else None,
                "ewma_run_ms": round(self._ewma_run * 1e3, 4),
                # monotone sums over the dispatch records
                "carried_rows": self._carried_rows,
                # arrivals that found the device free but older rows
                # waiting, and left in their dispatch instead of inline
                "joined_rows": self._n_joined,
                # dispatches that ran short of their rung, the rows they
                # were short by, and the estimates the cut decides from
                "rounded_up_batches": self._n_rounded_up,
                "padded_rows": self._padded_rows,
                "rung_run_ms": {
                    str(r): round(t * 1e3, 4)
                    for r in self.buckets
                    if (t := self._rung_s(r)) is not None
                },
                "run_gap_ms": round(min(self._run_gaps, default=0.0) * 1e3, 4),
                # launch-ahead: dispatches whose program was launched
                # before the previous device_compute returned; times a
                # row waited and a launch instant was known but the run
                # in flight ended first; and what the instant is made of:
                # per scorer rung a program there, and a worker's lead
                "ahead_batches": self._n_ahead,
                "ahead_missed": self._n_ahead_missed,
                "launch_run_ms": {
                    str(r): round(t * 1e3, 4)
                    for r in sorted(self._launch_runs)
                    if (t := self._launch_s(r)) is not None
                },
                "launch_lead_ms": round(
                    min(self._leads, default=0.0) * 1e3, 4),
                "run_ms_sum": round(self._run_s_sum * 1e3, 4),
                "run_ms_max": round(self._run_s_max * 1e3, 4),
                "run_ms_max_seq": self._run_max_seq,
                "turnaround_ms_sum": round(self._turnaround_s_sum * 1e3, 4),
                "turnaround_n": self._turnaround_n,
                "slow_dispatches": self._n_slow + stalled,
            }

    def dispatches(self, limit: Optional[int] = None) -> dict:
        """The dispatch rings, newest first (``GET /trace/dispatches.json``).

        ``inFlight`` is the oldest run in flight right now (the one whose
        program the device has), ``inFlightAhead`` the run launched behind
        it, if any: each with the stages it has finished and, read here on
        the caller's thread, the stack its thread sits in.
        """
        recent, slow = list(self._ring), list(self._slow_ring)
        if limit:
            recent = recent[-limit:]
        with self._stats_lock:
            flying = list(self._flying)
        frames = sys._current_frames() if flying else {}
        in_flight = [None, None]
        for i, rec in enumerate(flying[:2]):
            view = in_flight[i] = rec.to_dict()
            view["heldMs"] = round(
                (time.perf_counter() - rec.t_run) * 1e3, 4
            )
            frame = frames.get(rec.thread_id)
            if frame is not None:
                view["stack"] = [
                    line.rstrip() for line in traceback.format_stack(frame)
                ]
        return {
            "ringSize": self.RING,
            "slowRingSize": self.SLOW_RING,
            "started": self._seq,
            "dispatches": [r.to_dict() for r in reversed(recent)],
            "slow": [r.to_dict() for r in reversed(slow)],
            "inFlight": in_flight[0],
            "inFlightAhead": in_flight[1],
        }

    # -- worker -------------------------------------------------------------
    def _next(self, idle_s: float = 0.0) -> Optional[_Pending]:
        """Carried tail first (FIFO), then the live queue, for which an
        empty-handed worker waits up to ``idle_s``."""
        if self._carry:
            return self._carry.popleft()
        try:
            if idle_s <= 0:
                return self._queue.get_nowait()
            return self._queue.get(timeout=idle_s)
        except queue.Empty:
            return None

    def _rung_of(self, n: int) -> int:
        """Smallest ladder rung ≥ n: what a bucketed scorer pads ``n`` rows
        to (``n`` never passes ``max_batch``, the top rung)."""
        return self.buckets[bisect.bisect_left(self.buckets, n)]

    def _rung_s(self, rung: int) -> Optional[float]:
        """What a run at ``rung`` takes, by the batcher's own clock: the
        LEAST of its newest ``RUNS_KEPT`` runs there, inline ones too.

        The least, because a machine pause must not capture it: one run in
        a few hundred stands still for 0.1–15 s, and a mean that swallowed
        such a run at rung 8 would stop rounding up to it and, never
        running it again, never relearn.  A pause can only leave the least
        alone; a rung that really got slower shows after ``RUNS_KEPT``
        runs.  None while the rung has not run within the last ``RING``
        dispatches: it then counts as never run, and :meth:`_cut` tries
        it once — which is also how an estimate whose only sample was a
        pause is replaced.
        """
        return self._least(self._rung_runs[rung])

    def _least(self, runs) -> Optional[float]:
        if not runs or runs[-1][0] <= self._seq - self.RING:
            return None
        return min(dt for _, dt in runs)

    def _launch_s(self, rung) -> Optional[float]:
        """What a program at ``rung``, as the SCORER names it, takes on the
        device: the least of the newest ``RUNS_KEPT`` readings, as
        :meth:`_rung_s` and for its reasons; None for a rung not run
        within the last ``RING`` dispatches (it is then waited out, which
        teaches it)."""
        return self._least(self._launch_runs.get(rung))

    def _behind_in(self) -> Optional[tuple]:
        """(seconds until a run may be launched behind the ONE in flight,
        that run's record), or None while no instant can be named: its
        program is not enqueued yet (or its scorer has more launches to
        make, or launches nothing through ``obs.tracing.launch()``), or
        its rung has no estimate.

        The instant is as late as it can be, so that the cut is: (when the
        program in flight could start) + (what a program at its rung
        takes) − (what a worker has been taking from here to its own
        enqueue).  That aims the enqueue at the END of the program in
        flight.  The host never sees an end: it hears of it ``lag`` later
        (the scorer's measurement, on the record).  So a program queued
        behind the run before it could start a lag before that run's
        RETURN; one enqueued on a free device is counted from its enqueue
        (its readings have the lag taken out).  A run that ends earlier costs
        nothing against waiting it out; one that ends later (a pause)
        leaves the next one queued on the device behind it, already cut:
        bounded by one run.  Called by :class:`_Flight` under its
        condition, on the collecting worker's thread.
        """
        with self._stats_lock:
            if len(self._flying) != 1:
                return None
            cur = self._flying[0]
            start = cur.t_enqueued
            run_s = None if start is None else self._launch_s(cur.rung)
            if run_s is None:
                return None
            before = cur.behind
            if before is not None and before.dc_end is not None:
                start = max(start, before.dc_end - cur.lag)
            lead = min(self._leads, default=0.0)
        self._planned = True
        return start + run_s - lead - time.perf_counter(), cur

    def _cut(self, n: int) -> int:
        """How many of ``n`` rows in hand to run now; the rest is carried.

        With ``lo`` the largest rung ≤ n and ``hi`` the smallest ≥ n: on a
        rung (``n == lo``) everything runs.  Between two, the summed
        completion times of the waiting rows decide.  All ``n`` as one
        dispatch rounded up to ``hi`` are done after ``t(hi)`` each; cut,
        ``lo`` rows are done after ``t(lo)`` and the other ``n - lo`` after
        ``t(lo)``, the gap between two runs and a run at their own rung.
        ``t`` is :meth:`_rung_s`.  A rung with no estimate is tried: ``hi``
        by running everything once, ``lo`` by cutting.  Nothing compiles
        here: every rung was warmed at deploy.
        """
        i = bisect.bisect_left(self.buckets, n)
        if self.buckets[i] == n or i == 0:
            return n
        lo, hi = self.buckets[i - 1], self.buckets[i]
        with self._stats_lock:  # a run in flight may end meanwhile
            t_lo, t_hi = self._rung_s(lo), self._rung_s(hi)
            t_rest = self._rung_s(self._rung_of(n - lo))
            gap = min(self._run_gaps, default=0.0)
        if t_hi is None:
            return n
        if t_lo is None:
            return lo
        # the rest's rung is at most lo: until it has run, t(lo) bounds it
        if t_rest is None:
            t_rest = t_lo
        together = n * t_hi
        apart = lo * t_lo + (n - lo) * (t_lo + gap + t_rest)
        return n if together <= apart else lo

    def _loop(self) -> None:
        while not self._stop.is_set():
            with self._collecting:
                got = self._collect()
            if got is not None:
                try:
                    self._execute(*got)
                finally:
                    self._busy.release()

    def _collect(self) -> Optional[tuple]:
        """Take the next batch in hand and the right to launch it:
        :meth:`_execute`'s arguments, or None (nothing arrived, or
        ``stop()``).  One worker at a time (``_collecting``)."""
        first = self._next(idle_s=0.1)
        if first is None:
            return None
        t_first = time.perf_counter()
        batch = [first]
        self._planned = False
        # a run in flight is all a worker waits for, and arrivals pile up
        # behind it; with the device free this is immediate, and behind ONE
        # run in flight it ends at the launch instant (_behind_in).  The
        # timeout is stop()'s, which fails the rows put back.  On the
        # profiler's clock: first row taken -> _busy held
        with _tracing.annotation("pio.collect"):
            while not (got := self._busy.acquire(timeout=0.1)):
                if self._stop.is_set():
                    self._carry.extendleft(reversed(batch))
                    return None
        t_go = time.perf_counter()
        behind = None if got is True else got
        if behind is None and self._planned:
            # an instant was known, and the run ended before it came
            with self._stats_lock:
                self._n_ahead_missed += 1
        # against the arrivals' decision: a row is either drained here or
        # put once this run is in flight
        with self._arr_lock:
            while len(batch) < self.max_batch:
                nxt = self._next()
                if nxt is None:
                    break
                batch.append(nxt)
            # run them all, rounded up to the next rung, or cut at the
            # rung below: the tail then leads the next batch
            size = self._cut(len(batch))
            carried = len(batch) - size
            self._carry.extendleft(reversed(batch[size:]))
            batch = batch[:size]
            self._waiting -= size
        waited = time.perf_counter() - t_first
        return batch, waited, False, carried, behind, t_go

    def _resolve(
        self,
        p: _Pending,
        result: Any = None,
        error: Optional[BaseException] = None,
    ) -> None:
        """Deliver one outcome to a pending AND its coalesced followers.

        The key is detached from the in-flight map FIRST (under the key
        lock), so no new follower can attach to a pending whose result has
        already fanned out — late identical arrivals become fresh leaders.
        A shared error fails every attached waiter; nobody hangs.
        """
        followers: list[_Pending] = []
        if p.key is not None:
            with self._key_lock:
                if self._inflight_keys.get(p.key) is p:
                    del self._inflight_keys[p.key]
                followers, p.followers = p.followers, []
        for waiter in [p, *followers]:
            waiter.result = result
            waiter.error = error
            if waiter.trace is not None:
                waiter.t_set = time.perf_counter()
            waiter.event.set()

    def _expire_leader(self, p: _Pending) -> Optional[_Pending]:
        """An expired coalescing leader's followers must not inherit its
        504: promote the first still-live follower to leader (it takes the
        batch slot and the remaining followers) and return it; expired
        followers fail with the leader.  None when nobody survives."""
        with self._key_lock:
            owns_key = self._inflight_keys.get(p.key) is p
            followers, p.followers = p.followers, []
            promoted = None
            for i, f in enumerate(followers):
                if f.deadline is None or not f.deadline.expired():
                    promoted = f
                    promoted.followers = followers[i + 1:]
                    dead = followers[:i]
                    break
            else:
                dead = followers
            if owns_key:
                if promoted is not None:
                    self._inflight_keys[p.key] = promoted
                else:
                    del self._inflight_keys[p.key]
        if promoted is not None and promoted.trace is not None:
            # flight-recorder: this request entered as a follower and took
            # over an abandoned leader's batch slot — `coalesce` flips to
            # "leader" at dispatch, `promoted` records why (the routing
            # tier hedges leaders away; the invariant test pins that the
            # device is still charged exactly once, to the promoted trace)
            promoted.trace.annotate(promoted=True)
        err = DeadlineExceeded("query deadline expired in queue")
        for waiter in [p, *dead]:
            waiter.result = None
            waiter.error = err
            waiter.event.set()
        with self._stats_lock:
            self._n_expired += 1 + len(dead)
        return promoted

    def _watch(self) -> bool:
        """Have every thread's stack written once if a run in flight is
        still going when its threshold passes: (re-)arm the watchdog for
        whichever is due first, or cancel it with none left to watch.
        faulthandler's is a C thread, so it fires even when the stalled
        thread holds the GIL; it is ONE timer a process (a second
        batcher's run re-arms it), which is why two overlapping runs share
        it here, under ``_stats_lock``; and it lists the newest 100
        threads.  A run already past its threshold has had its dump."""
        now = time.perf_counter()
        due = [d for r in self._flying
               if (d := r.t_run + r.slow_after_s - now) > 0]
        try:
            faulthandler.cancel_dump_traceback_later()
            if due:
                faulthandler.dump_traceback_later(
                    min(due), file=self.SLOW_DUMP_FILE or sys.stderr
                )
        except (AttributeError, OSError, ValueError):
            return False  # the log has no file descriptor: count, no dump
        return True

    def _execute(
        self, batch: list, waited: float, inline: bool = False,
        carried: int = 0, behind: Optional[_tracing.Dispatch] = None,
        t_go: Optional[float] = None,
    ) -> None:
        """Run one batch and deliver results/errors to every waiter.

        Expired pendings are dropped HERE, at dispatch: their waiters have
        already raised (or are about to), so executing them would spend a
        device pass on a result nobody will read.

        The caller holds ``_busy`` for this run, alone or (``behind``: the
        record of the run in flight it was launched behind, at ``t_go``)
        with that run.  The record has this thread as its only writer;
        what two overlapping runs share moves under ``_stats_lock``.
        """
        t_in = time.perf_counter()
        live, expired = [], []
        for p in batch:
            if p.deadline is not None and p.deadline.expired():
                expired.append(p)
            else:
                live.append(p)
        for p in expired:
            if p.key is not None:
                promoted = self._expire_leader(p)
                if promoted is not None:
                    live.append(promoted)
            else:
                p.error = DeadlineExceeded("query deadline expired in queue")
                p.event.set()
                with self._stats_lock:
                    self._n_expired += 1
        batch = live
        if not batch:
            return
        t_run = time.perf_counter()
        # collect: first row taken -> the run starts (the wait for _busy,
        # the drain and the cut)
        threshold = max(self.SLOW_FLOOR_S, self.SLOW_MULT * self._ewma_run)
        # the rung these rows round up to (after the deadline drop)
        rung = self._rung_of(len(batch))
        with self._stats_lock:
            # one thread at a time is between taking _busy and its launch
            # (a second may take it only behind a run that HAS launched),
            # so seq follows the order of the launches
            seq = self._seq = self._seq + 1
            rec = _tracing.Dispatch(
                seq, inline, len(batch), carried, t_run,
                collect_s=waited + (t_run - t_in), slow_after_s=threshold,
                padded=rung - len(batch),
            )
            rec.behind = behind
            rec.on_launch = self._launched
            self._flying.append(rec)
            armed = self._watch()
            # what a run on a free device follows, as of NOW: a run launched
            # behind this one may end, and move them, before this one does
            prev_dc_end, prev_run_end = self._prev_dc_end, self._prev_run_end
            prev_left_work = self._prev_left_work
        traces = [p.trace for p in batch if p.trace is not None]
        for p in batch:
            if p.trace is not None:
                # time between enqueue and dispatch: the run in flight it
                # waited out (≈0 on the inline bypass)
                p.trace.add_stage("queue_wait", t_run - p.t_enq)
                # flight-recorder context: how this request's batch formed,
                # which dispatch ran it and how many it waited through (1
                # inline, 2 behind the run in flight, 3 when the cut
                # carried it or the run in flight had one queued behind it)
                p.trace.annotate(
                    batch=len(batch),
                    dispatch="inline" if inline else "window",
                    dispatch_seq=seq,
                    passes=seq - p.done_at_enq,
                    **({"coalesce": "leader"} if p.key is not None else {}),
                )
        results: Optional[list] = None
        run_error: Optional[BaseException] = None
        try:
            # the worker thread runs ONE batch for many requests: install
            # every member's trace so shared stages (assembly, h2d, device
            # compute, d2h) are charged to each of them, and to the record
            with _tracing.scope(traces, dispatch=rec):
                results = self._run_batch([p.query for p in batch])
            if len(results) != len(batch):
                raise RuntimeError(
                    f"batch_predict returned {len(results)} results for "
                    f"{len(batch)} queries"
                )
        except BaseException as e:  # propagate to EVERY waiter
            run_error = e
            rec.error = type(e).__name__
        t_end = time.perf_counter()
        run_dt = t_end - t_run
        # postprocess takes what no stage of the run covered (the rest of
        # batch_predict, serving.serve), so `other` on a request is a true
        # remainder and a record's stages tile its wall
        staged = sum(rec.stages.values()) - rec.stages["collect"]
        rest = max(0.0, run_dt - staged)
        rec.stages["postprocess"] += rest
        for t in traces:
            t.add_stage("postprocess", rest)
        with self._stats_lock:
            # before the waiters wake: what they ask next was enqueued
            # after this run returned
            self._done = max(self._done, seq)
        with _tracing.annotation("pio.resolve", seq=seq):
            for i, p in enumerate(batch):
                if run_error is not None:
                    self._resolve(p, error=run_error)
                else:
                    self._resolve(p, result=results[i])
        t_done = time.perf_counter()
        rec.stages["resolve"] = t_done - t_end
        rec.wall_s = rec.stages["collect"] + (t_done - t_run)
        # rows waiting as this run ends: queued, carried, or already in a
        # worker's hand
        rec.depth_end = self.depth()
        slow = run_dt > threshold
        # launched behind a run in flight: how long before that run's
        # device_compute returned, which is the time its program sat queued
        # on the device and no part of what a run at its rung takes.  What
        # the PROGRAM took: the host heard of its end a lag after it, and
        # it started a lag before the host heard of the other's, or (on a
        # free device, or enqueued later than that) a lag after its own
        # enqueue as the host counts
        launch_t = rec.dc_start if rec.t_launch is None else rec.t_launch
        started = None if rec.t_enqueued is None else rec.t_enqueued + rec.lag
        if behind is not None and None not in (behind.dc_end, launch_t):
            rec.ahead_s = behind.dc_end - launch_t
            if started is not None:
                started = max(started, behind.dc_end)
        queued = max(0.0, rec.ahead_s or 0.0)
        with self._stats_lock:
            rec.behind = None  # _behind_in reads it of a run in flight
            self._ewma_run += self.ALPHA * (run_dt - self._ewma_run)
            self._n_batches += 1
            self._n_queries += len(batch)
            self._size_hist[len(batch)] += 1
            self._wait_s_total += waited
            if inline:
                self._n_inline += 1
            self._carried_rows += carried
            self._n_rounded_up += rec.padded > 0
            self._padded_rows += rec.padded
            self._n_ahead += (rec.ahead_s or 0.0) > 0
            # the estimates; a failed run says nothing of a rung
            if run_error is None:
                self._rung_runs[rung].append((seq, run_dt - queued))
                if started is not None and rec.dc_end is not None:
                    self._launch_runs.setdefault(
                        rec.rung, collections.deque(maxlen=self.RUNS_KEPT),
                    ).append((seq, rec.dc_end - started))
                    if t_go is not None:
                        self._leads.append(rec.t_enqueued - t_go)
            self._run_s_sum += run_dt
            if run_dt > self._run_s_max:
                self._run_s_max, self._run_max_seq = run_dt, seq
            # turnaround: the device program of the previous dispatch
            # returned -> this one's is launched: the time the device
            # waited for the host with work at hand.  Behind a run in
            # flight the work was at hand by definition, and a program
            # launched before that run returned kept the device waiting 0;
            # on a free device it counts when the run before left rows
            # queued or carried.  The gap between two runs likewise
            if behind is not None:
                self._run_gaps.append(0.0)
                if rec.ahead_s is not None:
                    self._turnaround_s_sum += max(0.0, -rec.ahead_s)
                    self._turnaround_n += 1
            elif prev_left_work:
                if prev_run_end is not None:
                    self._run_gaps.append(t_run - prev_run_end)
                if None not in (prev_dc_end, rec.dc_start):
                    self._turnaround_s_sum += rec.dc_start - prev_dc_end
                    self._turnaround_n += 1
            # two runs in flight may end in either order: the newest by
            # seq is the one the next run on a free device follows
            if seq > self._prev_seq:
                self._prev_seq = seq
                self._prev_dc_end = rec.dc_end
                self._prev_run_end = t_end
                self._prev_left_work = rec.depth_end > 0
            # with the count, so that stats() never sees the run both as
            # in flight past its threshold and as counted
            self._n_slow += slow
            self._flying.remove(rec)
            self._watch()
        self._ring.append(rec)
        if slow:
            self._slow_ring.append(rec)
            logger.warning(
                "dispatch %d held the batcher for %.2f s (slow past %.2f s)"
                " on thread %s (%#x): stages ms %s%s",
                seq, run_dt, threshold, rec.thread, rec.thread_id,
                rec.to_dict()["stagesMs"],
                "; every thread's stack was written when the threshold "
                "passed" if armed else "",
            )

    def _launched(self) -> None:
        """A run in flight has enqueued its program (``Dispatch.on_launch``,
        on its thread): a worker waiting to launch behind it can name its
        instant now."""
        with self._turn:
            self._turn.notify_all()
