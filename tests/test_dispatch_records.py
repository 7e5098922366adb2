"""One record per dispatch, kept by the batcher itself (ISSUE 25).

The batcher tests drive a fake ``run_batch`` whose runs are gated by
events, on a clock the test moves by hand, so every wall below is exact.
"""

import glob
import inspect
import json
import os
import sys
import threading
import time
import types
import urllib.error
import urllib.request
import uuid

import numpy as np
import pytest

from predictionio_tpu.obs import tracing
from predictionio_tpu.serving import batching
from predictionio_tpu.serving.batching import MicroBatcher

WAIT_S = 10.0


class Clock:
    """perf_counter under the test's hand."""

    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, seconds):
        self.t += seconds


@pytest.fixture()
def clock(monkeypatch):
    clk = Clock()
    shim = types.SimpleNamespace(perf_counter=clk, time=time.time)
    monkeypatch.setattr(batching, "time", shim)
    monkeypatch.setattr(tracing, "time", shim)
    return clk


class Runs:
    """A ``run_batch`` that charges the stages the fast path would, for as
    long as the test's clock says, and can be held at a gate."""

    def __init__(self, clock=None, h2d_s=0.0, device_s=0.0, d2h_s=0.0,
                 post_s=0.0, device_s_by_rung=None):
        self.clock = clock
        self.walls = (("h2d", h2d_s), ("device_compute", device_s),
                      ("d2h", d2h_s))
        # {rung: seconds} in place of the one device_s: the batch's rows
        # round up to the smallest rung that holds them
        self.device_s_by_rung = device_s_by_rung
        self.post_s = post_s
        self.batches = []
        self.gate = threading.Event()
        self.gate.set()
        self.entered = threading.Event()

    def __call__(self, queries):
        self.batches.append(list(queries))
        self.entered.set()
        assert self.gate.wait(WAIT_S)
        for name, seconds in self.walls:
            if name == "device_compute" and self.device_s_by_rung:
                seconds = self.device_s_by_rung[min(
                    r for r in self.device_s_by_rung if r >= len(queries))]
            with tracing.stage(name):
                if self.clock is not None:
                    self.clock.advance(seconds)
        if self.clock is not None:
            self.clock.advance(self.post_s)
        return [("answer", q) for q in queries]


def submit_traced(mb, query, key=None):
    """Submit on a thread of its own under a sampled trace; returns the
    thread and the trace (finished when the thread ends)."""
    tr = tracing.Trace(f"req-{query}")

    def go():
        with tracing.scope((tr,)):
            mb.submit(query, key=key)
        tr.finish(200)

    th = threading.Thread(target=go, daemon=True)
    th.start()
    return th, tr


def held_then(mb, runs, first, waiting):
    """``first`` runs inline and is held; ``waiting`` queue up behind it,
    in order; then the gate opens and everybody is answered.  Returns the
    traces by query."""
    before = mb.stats()["queries"]
    runs.entered.clear()
    runs.gate.clear()
    submitted = [submit_traced(mb, first)]
    assert runs.entered.wait(WAIT_S)
    for n, q in enumerate(waiting, start=1):
        submitted.append(submit_traced(mb, q))
        wait_until(lambda: mb.depth() == n, f"{q} queued")
    if waiting:
        # the worker stamps "first row taken" on the clock the held run is
        # about to move: let it take the row before the gate opens
        wait_until(lambda: mb._queue.qsize() < len(waiting),
                   "the worker holds the first waiting row")
    runs.gate.set()
    for th, _ in submitted:
        th.join(WAIT_S)
        assert not th.is_alive()
    wait_until(
        lambda: mb.stats()["queries"] == before + 1 + len(waiting),
        "every row counted")
    return dict(zip([first, *waiting], (tr for _, tr in submitted)))


def wait_until(cond, what):
    end = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < end, what
        time.sleep(0.002)


@pytest.fixture()
def three_dispatches(clock):
    """An inline request, two that queue behind it, one the cut carries.

    Ladder 1/2/4: A runs inline and is held; B, C, D arrive meanwhile; the
    worker then dispatches B + C (the rung under three rows) and carries D
    to a third dispatch, because a run at rung 4 is known to take four
    times one at rungs 1 and 2: three rows rounded up would be done after
    3 x 1,006 ms, cut they are done after 2 x 256 + (256 + 256).  Every run
    spends 3 ms in h2d, 250 ms on the device at rungs 1 and 2, 1 ms reading
    back and 2 ms building answers; the host takes 5 ms between A's device
    program returning and the next one's launch.
    """
    runs = Runs(clock, h2d_s=0.003, d2h_s=0.001, post_s=0.002,
                device_s_by_rung={1: 0.250, 2: 0.250, 4: 1.000})
    mb = MicroBatcher(runs, max_batch=4, buckets=(1, 2, 4))
    # one earlier run at rungs 2 and 4, as the batcher would have kept it
    for rung, run_s in ((2, 0.256), (4, 1.006)):
        mb._rung_runs[rung].append((0, run_s))
    try:
        traces = held_then(mb, runs, "A", "BCD")
        wait_until(lambda: mb.stats()["batches"] == 3, "three dispatches")
        yield mb, runs, traces
    finally:
        runs.gate.set()
        mb.stop()


# -- what a request waited through -------------------------------------------


@pytest.mark.parametrize("query, passes, seq, how", [
    ("A", 1, 1, "inline"),   # nothing in flight: its own dispatch only
    ("B", 2, 2, "window"),   # waited for the run in flight, rode the next
    ("D", 3, 3, "window"),   # the cut carried it one dispatch further
])
def test_a_trace_names_its_dispatch_and_the_passes_it_waited(
        three_dispatches, query, passes, seq, how):
    _, runs, traces = three_dispatches
    assert [len(b) for b in runs.batches] == [1, 2, 1]
    meta = traces[query].to_dict()["meta"]
    assert meta["passes"] == passes
    assert meta["dispatch_seq"] == seq
    assert meta["dispatch"] == how


def test_counters_move_as_defined(three_dispatches):
    mb, _, _ = three_dispatches
    s = mb.stats()
    assert (s["batches"], s["queries"], s["inline_batches"]) == (3, 4, 1)
    # the cut carried D once
    assert s["carried_rows"] == 1
    # every run: 3 + 250 + 1 + 2 ms
    assert s["run_ms_sum"] == pytest.approx(3 * 256.0, abs=1e-6)
    assert s["run_ms_max"] == pytest.approx(256.0, abs=1e-6)
    assert s["run_ms_max_seq"] == 1  # the first to reach it keeps it
    # A ended with rows waiting, so did B + C (D was carried); after D
    # nothing waited.  Device returned -> next launch: d2h 1 + answers 2 +
    # h2d 3 ms, twice.
    assert s["turnaround_n"] == 2
    assert s["turnaround_ms_sum"] == pytest.approx(2 * 6.0, abs=1e-6)
    assert s["slow_dispatches"] == 0


def test_a_run_with_nothing_waiting_at_its_end_counts_no_turnaround(clock):
    runs = Runs(clock, device_s=0.25)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        mb.submit("one")
        clock.advance(5.0)  # the device idles, but no row waits for it
        mb.submit("two")
        s = mb.stats()
        assert s["batches"] == s["inline_batches"] == 2
        assert s["turnaround_n"] == 0 and s["turnaround_ms_sum"] == 0
    finally:
        mb.stop()


def test_a_slower_run_takes_the_maximum_and_its_seq(clock):
    runs = Runs(clock, device_s=0.25)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        mb.submit("one")
        runs.walls = (("device_compute", 0.40),)
        mb.submit("two")
        runs.walls = (("device_compute", 0.10),)
        mb.submit("three")
        s = mb.stats()
        assert s["run_ms_max"] == pytest.approx(400.0, abs=1e-6)
        assert s["run_ms_max_seq"] == 2
        assert s["run_ms_sum"] == pytest.approx(750.0, abs=1e-6)
    finally:
        mb.stop()


# -- the cut ---------------------------------------------------------------------


def test_rows_between_rungs_run_as_one_dispatch_and_are_counted(clock):
    """The device takes the same 10 ms at rungs 1 and 8: B and C, waiting
    behind A, run together, rounded up to rung 8 -- the first time because
    rung 8 has never run, afterwards because 2 x 10 ms beats 10 + 20."""
    runs = Runs(clock, device_s_by_rung={1: 0.010, 8: 0.010})
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        assert mb.stats()["rung_run_ms"] == {}
        for _ in range(2):
            traces = held_then(mb, runs, "A", "BC")
            assert traces["C"].to_dict()["meta"]["passes"] == 2
        assert [len(b) for b in runs.batches] == [1, 2, 1, 2]
        s = mb.stats()
        assert s["rounded_up_batches"] == 2 and s["padded_rows"] == 2 * 6
        assert s["carried_rows"] == 0
        assert s["rung_run_ms"] == {"1": 10.0, "8": 10.0}
        assert s["run_gap_ms"] == 0.0  # this clock moves inside runs only
        recs = {r["seq"]: r for r in mb.dispatches()["dispatches"]}
        assert [recs[n]["paddedRows"] for n in (1, 2, 3, 4)] == [0, 6, 0, 6]
        assert [recs[n]["carriedRows"] for n in (1, 2, 3, 4)] == [0] * 4
    finally:
        runs.gate.set()
        mb.stop()


def test_an_unmeasured_rung_is_tried_once(clock):
    """A run at rung 4 takes ten times one at rung 1.  Nobody knows until
    it has run: the first three waiting rows are handed over together;
    the next three are cut, one row a dispatch, each carried in turn."""
    runs = Runs(clock, device_s_by_rung={1: 0.1, 4: 1.0})
    mb = MicroBatcher(runs, max_batch=4, buckets=(1, 4))
    try:
        held_then(mb, runs, "A", "BCD")
        assert [len(b) for b in runs.batches] == [1, 3]
        traces = held_then(mb, runs, "E", "FGH")
        assert runs.batches[2:] == [["E"], ["F"], ["G"], ["H"]]
        assert traces["H"].to_dict()["meta"]["passes"] == 4
        s = mb.stats()
        assert s["rounded_up_batches"] == 1 and s["padded_rows"] == 1
        assert s["carried_rows"] == 2 + 1
        assert s["rung_run_ms"] == {"1": 100.0, "4": 1000.0}
    finally:
        runs.gate.set()
        mb.stop()


def test_one_run_of_three_seconds_does_not_stop_rounding_up(clock):
    """A machine pause inside one rung-8 run must not capture the
    estimate: it is the least of the newest runs, so it stays 10 ms and
    the next waiting rows are rounded up as before."""
    by_rung = {1: 0.010, 8: 0.010}
    runs = Runs(clock, device_s_by_rung=by_rung)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        held_then(mb, runs, "A", "BC")       # teaches rung 8
        by_rung[8] = 3.0                     # the pause
        held_then(mb, runs, "D", "EF")
        by_rung[8] = 0.010
        s = mb.stats()
        assert s["run_ms_max"] == pytest.approx(3000.0)
        assert s["slow_dispatches"] == 1     # past max(2 s, 8 x ewma_run)
        assert s["rung_run_ms"]["8"] == pytest.approx(10.0)
        held_then(mb, runs, "G", "HI")
        assert [len(b) for b in runs.batches] == [1, 2, 1, 2, 1, 2]
        assert mb.stats()["rounded_up_batches"] == 3
        assert mb.stats()["carried_rows"] == 0
    finally:
        runs.gate.set()
        mb.stop()


def test_an_estimate_whose_only_run_was_a_pause_is_replaced(clock):
    """Rung 8's first run ever meets the pause, so the batcher cuts; once
    that run has left the horizon of RING dispatches the rung counts as
    never run, is tried again, and rounding up resumes."""
    by_rung = {1: 0.010, 8: 3.0}
    runs = Runs(clock, device_s_by_rung=by_rung)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        held_then(mb, runs, "A", "BC")       # tried: 3 s
        by_rung[8] = 0.010
        held_then(mb, runs, "D", "EF")
        assert [len(b) for b in runs.batches] == [1, 2, 1, 1, 1]
        assert mb.stats()["rung_run_ms"]["8"] == pytest.approx(3000.0)
        for i in range(MicroBatcher.RING):
            mb.submit(i)
        assert "8" not in mb.stats()["rung_run_ms"]
        held_then(mb, runs, "G", "HI")
        assert [len(b) for b in runs.batches[-2:]] == [1, 2]
        assert mb.stats()["rung_run_ms"]["8"] == pytest.approx(10.0)
    finally:
        runs.gate.set()
        mb.stop()


def test_a_failed_run_teaches_no_estimate(clock):
    def broken(queries):
        raise ValueError("no scores today")

    mb = MicroBatcher(broken, buckets=(1, 8))
    try:
        with pytest.raises(ValueError):
            mb.submit("q")
        assert mb.stats()["rung_run_ms"] == {}
    finally:
        mb.stop()


def test_a_ladder_of_every_row_count_neither_pads_nor_carries(clock):
    """The sequence family's ladder: whatever waits is a rung, so the cut
    never decides and the device time (here growing with the rows) is
    not asked."""
    runs = Runs(clock, device_s_by_rung={n: 0.01 * n for n in range(1, 9)})
    mb = MicroBatcher(runs, max_batch=8, buckets=tuple(range(1, 9)))
    try:
        held_then(mb, runs, "A", "BCDEF")
        held_then(mb, runs, "G", "HIJ")
        assert [len(b) for b in runs.batches] == [1, 5, 1, 3]
        s = mb.stats()
        assert (s["rounded_up_batches"], s["padded_rows"],
                s["carried_rows"]) == (0, 0, 0)
        assert all(r["paddedRows"] == 0
                   for r in mb.dispatches()["dispatches"])
    finally:
        runs.gate.set()
        mb.stop()


def test_a_row_whose_deadline_lapsed_is_dropped_from_a_rounded_up_batch(
        clock):
    from predictionio_tpu.common.resilience import Deadline, DeadlineExceeded

    runs = Runs(clock, device_s_by_rung={1: 0.010, 8: 0.010})
    mb = MicroBatcher(runs, buckets=(1, 8))
    outcome = {}

    def impatient():
        try:
            outcome["C"] = mb.submit("C", deadline=Deadline.after_ms(30))
        except DeadlineExceeded as e:
            outcome["C"] = e

    try:
        runs.gate.clear()
        th_a, _ = submit_traced(mb, "A")
        assert runs.entered.wait(WAIT_S)
        th_b, _ = submit_traced(mb, "B")
        wait_until(lambda: mb.depth() == 1, "B queued")
        th_c = threading.Thread(target=impatient, daemon=True)
        th_c.start()
        wait_until(lambda: mb.depth() == 2, "C queued")
        th_d, _ = submit_traced(mb, "D")
        wait_until(lambda: mb.depth() == 3, "D queued")
        th_c.join(WAIT_S)  # C gives up while A still holds the batcher
        assert isinstance(outcome["C"], DeadlineExceeded)
        runs.gate.set()
        for th in (th_a, th_b, th_d):
            th.join(WAIT_S)
            assert not th.is_alive()
        assert runs.batches == [["A"], ["B", "D"]]  # C never ran
        s = mb.stats()
        assert s["expired_dropped"] == 1 and s["queries"] == 3
        # the record says what ran: two rows, six short of rung 8
        (rec, _) = mb.dispatches()["dispatches"]
        assert (rec["rows"], rec["paddedRows"]) == (2, 6)
        assert s["padded_rows"] == 6
    finally:
        runs.gate.set()
        mb.stop()


def test_the_batchers_new_counters_reach_the_registry_under_the_contract():
    """`rounded_up_batches`, `padded_rows` and `rung_run_ms` are bridged
    like the other batcher counters, and the repo's metric catalog
    (`analysis/metrics_contract`) has them."""
    from predictionio_tpu import analysis
    from predictionio_tpu.obs import bridges
    from predictionio_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    bridges.bridge_batcher(reg, lambda: {
        "batches": 5, "rounded_up_batches": 2, "padded_rows": 11,
        "rung_run_ms": {"1": 9.2, "8": 9.7}})
    text = reg.render_prometheus()
    assert "pio_batcher_rounded_up_batches_total 2" in text
    assert "pio_batcher_padded_rows_total 11" in text
    assert 'pio_batcher_rung_run_ms{rung="8"} 9.7' in text
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    report = analysis.run(root, analyzers=["metrics"])
    assert [f for f in report.findings
            if f.symbol.startswith("pio_batcher")] == []


# -- a free device is never held (ISSUE 33) -----------------------------------------


class HandOff:
    """``_busy`` with the instant of a hand-off under the test's hand: the
    worker (the one blocking acquirer) stands at ``gate`` with its rows in
    hand before it may take the lock; arrivals' non-blocking tries pass."""

    def __init__(self, lock):
        self.lock = lock
        self.gate = threading.Event()
        self.gate.set()
        self.worker_waits = threading.Event()

    def acquire(self, blocking=True, timeout=-1):
        if blocking:
            self.worker_waits.set()
            assert self.gate.wait(WAIT_S)
        return self.lock.acquire(blocking, timeout)

    def release(self):
        self.lock.release()

    def locked(self):
        return self.lock.locked()


def test_rows_in_hand_run_with_no_timed_wait_while_the_device_is_free(clock):
    """A held; B, C, D queue behind it; the cut runs B + C and carries D.
    D finds the device free in a worker's hand: its dispatch starts the
    moment B + C's run ends (on a clock that moves inside runs only its
    ``collect`` is at most that run, where B + C's is the run they waited
    out), and at no time did a worker sit out a ``queue.get`` timeout with
    a row waiting."""
    runs = Runs(clock, h2d_s=0.003, d2h_s=0.001, post_s=0.002,
                device_s_by_rung={1: 0.250, 2: 0.250, 4: 1.000})
    mb = MicroBatcher(runs, max_batch=4, buckets=(1, 2, 4))
    for rung, run_s in ((2, 0.256), (4, 1.006)):
        mb._rung_runs[rung].append((0, run_s))
    real_get, sat_out = mb._queue.get, []

    def get(block=True, timeout=None):
        waiting = mb.depth()
        try:
            return real_get(block, timeout)
        except batching.queue.Empty:
            if block and timeout and waiting:
                sat_out.append((timeout, waiting))
            raise

    mb._queue.get = get
    try:
        held_then(mb, runs, "A", "BCD")
        wait_until(lambda: mb.stats()["batches"] == 3, "three dispatches")
        assert runs.batches == [["A"], ["B", "C"], ["D"]]
        assert sat_out == []
        recs = {r["seq"]: r for r in mb.dispatches()["dispatches"]}
        assert recs[2]["stagesMs"]["collect"] == pytest.approx(256.0, abs=1e-6)
        # D is in the OTHER worker's hand from the cut on (ISSUE 40: two
        # workers), so its collect is whatever of B + C's run the clock had
        # still to move when it was taken: nothing to all of it
        d_collect = recs[3]["stagesMs"]["collect"]
        assert 0.0 <= d_collect <= 256.0 + 1e-6
        # first row taken -> run starts, a mean over the three dispatches
        assert mb.stats()["avg_window_wait_ms"] == pytest.approx(
            (256.0 + d_collect) / 3, abs=1e-3)
        assert mb.stats()["joined_rows"] == 0  # a run was in flight for all
    finally:
        runs.gate.set()
        mb.stop()


def test_a_newcomer_joins_the_rows_in_the_workers_hand(clock):
    """B waited out A's run in the worker's hand; A has returned, the
    device is free and the worker has not yet taken it.  N arrives in that
    instant: it leaves in B's dispatch, behind B, not inline past it."""
    runs = Runs(clock, device_s_by_rung={1: 0.010, 8: 0.010})
    mb = MicroBatcher(runs, buckets=(1, 8))
    hand_off = mb._busy = HandOff(mb._busy)
    try:
        hand_off.gate.clear()
        runs.gate.clear()
        th_a, _ = submit_traced(mb, "A")
        assert runs.entered.wait(WAIT_S)
        th_b, tr_b = submit_traced(mb, "B")
        assert hand_off.worker_waits.wait(WAIT_S)  # B is in its hand
        runs.gate.set()
        th_a.join(WAIT_S)
        assert not th_a.is_alive() and not hand_off.locked()
        assert mb.depth() == 1
        th_n, tr_n = submit_traced(mb, "N")
        wait_until(lambda: mb.depth() == 2, "N queued behind B")
        assert runs.batches == [["A"]]  # nobody took the free device
        hand_off.gate.set()
        for th in (th_b, th_n):
            th.join(WAIT_S)
            assert not th.is_alive()
        assert runs.batches == [["A"], ["B", "N"]]
        s = mb.stats()
        assert s["joined_rows"] == 1
        assert (s["batches"], s["inline_batches"], s["depth"]) == (2, 1, 0)
        for tr in (tr_b, tr_n):
            meta = tr.to_dict()["meta"]
            assert (meta["dispatch"], meta["dispatch_seq"]) == ("window", 2)
    finally:
        hand_off.gate.set()
        runs.gate.set()
        mb.stop()


def test_an_arrival_during_a_run_is_not_counted_as_joined(clock):
    runs = Runs(clock, device_s_by_rung={1: 0.010, 8: 0.010})
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        held_then(mb, runs, "A", "BC")
        mb.submit("lone")  # free device, nothing waiting: inline
        s = mb.stats()
        assert s["joined_rows"] == 0 and s["inline_batches"] == 2
        assert "ewma_gap_ms" not in s
    finally:
        runs.gate.set()
        mb.stop()


def test_stop_fails_every_waiting_row_fast_the_one_in_hand_too(clock):
    runs = Runs(clock, device_s=0.010)
    mb = MicroBatcher(runs, buckets=(1, 8))
    outcomes = {}

    def ask(q):
        try:
            outcomes[q] = mb.submit(q)
        except RuntimeError as e:
            outcomes[q] = e

    try:
        runs.gate.clear()
        threads = {q: threading.Thread(target=ask, args=(q,), daemon=True)
                   for q in "ABCD"}
        threads["A"].start()
        assert runs.entered.wait(WAIT_S)
        for n, q in enumerate("BCD", start=1):
            threads[q].start()
            wait_until(lambda: mb.depth() == n, f"{q} queued")
        t0 = time.monotonic()
        mb.stop()  # A still holds the batcher; B is in the worker's hand
        for q in "BCD":
            threads[q].join(WAIT_S)
            assert not threads[q].is_alive()
        assert time.monotonic() - t0 < 2.0
        assert not any(w.is_alive() for w in mb._workers)
        assert mb.depth() == 0
        for q in "BCD":
            assert isinstance(outcomes[q], RuntimeError)
            assert "shutting down" in str(outcomes[q])
        runs.gate.set()
        threads["A"].join(WAIT_S)
        assert outcomes["A"] == ("answer", "A")
        assert runs.batches == [["A"]]
    finally:
        runs.gate.set()
        mb.stop()


def test_under_contention_no_row_is_lost_doubled_or_overtaken_in_the_queue():
    """More submitters than cores, a short switch interval: every query is
    answered once with its own answer, the count of waiting rows returns
    to zero (a lost update would leave it off), and the worker's rows run
    in the order they were queued (a ladder of every count: no carry)."""
    put_order, ran = [], []
    lock = threading.Lock()

    def run(queries):
        if threading.current_thread().name == "query-microbatcher":
            ran.extend(queries)
        time.sleep(0.0005)
        return [q * 2 for q in queries]

    mb = MicroBatcher(run, max_batch=16, buckets=tuple(range(1, 17)))
    real_put = mb._queue.put

    def put(p, *a, **kw):  # called under the batcher's arrival lock
        put_order.append(p.query)
        return real_put(p, *a, **kw)

    mb._queue.put = put
    n_threads, per_thread = 24, 40
    results = {}

    def client(t):
        for i in range(per_thread):
            q = t * per_thread + i
            r = mb.submit(q)
            with lock:
                results[q] = r

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=client, args=(t,), daemon=True)
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
        mb.stop()
    n = n_threads * per_thread
    assert results == {q: q * 2 for q in range(n)}
    s = mb.stats()
    assert s["queries"] == n and s["depth"] == 0 and s["carried_rows"] == 0
    assert ran == put_order
    assert s["batches"] - s["inline_batches"] <= len(put_order)


@pytest.mark.parametrize("cls, gone", [
    ("MicroBatcher", "window_ms"), ("QueryServer", "batch_window_ms")])
def test_the_window_is_no_longer_an_argument(cls, gone):
    from predictionio_tpu.serving import query_server

    owner = {"MicroBatcher": MicroBatcher,
             "QueryServer": query_server.QueryServer}[cls]
    assert gone not in inspect.signature(owner.__init__).parameters
    for name in ("GAP_MULT", "window_s"):
        assert not hasattr(MicroBatcher, name)


def test_joined_rows_reaches_the_registry_and_the_gap_gauge_has_left():
    from predictionio_tpu import analysis
    from predictionio_tpu.obs import bridges
    from predictionio_tpu.obs.metrics import MetricsRegistry

    mb = MicroBatcher(lambda qs: qs, buckets=(1, 8))
    try:
        mb.submit("q")
        stats = mb.stats()
    finally:
        mb.stop()
    assert stats["joined_rows"] == 0
    reg = MetricsRegistry()
    bridges.bridge_batcher(reg, lambda: dict(stats, joined_rows=7))
    text = reg.render_prometheus()
    assert "pio_batcher_joined_rows_total 7" in text
    assert "ewma_gap" not in text
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for doc in ("docs/observability.md", "predictionio_tpu/obs/bridges.py"):
        with open(os.path.join(root, doc)) as f:
            body = f.read()
        assert "joined_rows" in body and "ewma_gap" not in body
    report = analysis.run(root, analyzers=["metrics"])
    assert [f for f in report.findings
            if f.symbol.startswith("pio_batcher")] == []


# -- the records ---------------------------------------------------------------


def test_a_record_says_who_ran_what_and_its_stages_tile_its_wall(
        three_dispatches):
    mb, _, _ = three_dispatches
    doc = mb.dispatches()
    recs = doc["dispatches"]
    assert [r["seq"] for r in recs] == [3, 2, 1]  # newest first
    by_seq = {r["seq"]: r for r in recs}
    assert by_seq[1]["inline"] and not by_seq[2]["inline"]
    assert by_seq[2]["thread"] == "query-microbatcher"
    assert [by_seq[n]["rows"] for n in (1, 2, 3)] == [1, 2, 1]
    assert [by_seq[n]["carriedRows"] for n in (1, 2, 3)] == [0, 1, 0]
    # rows waiting as each run ended: B, C, D; then D; then none
    assert [by_seq[n]["depthAtEnd"] for n in (1, 2, 3)] == [3, 1, 0]
    for r in recs:
        assert set(r["stagesMs"]) == set(tracing.Dispatch.STAGES)
        assert sum(r["stagesMs"].values()) == pytest.approx(
            r["wallMs"], abs=1e-3)
        assert r["stagesMs"]["h2d"] == pytest.approx(3.0, abs=1e-6)
        assert r["stagesMs"]["device_compute"] == pytest.approx(250.0,
                                                                abs=1e-6)
        assert r["stagesMs"]["d2h"] == pytest.approx(1.0, abs=1e-6)
        # what no stage of the run covered
        assert r["stagesMs"]["postprocess"] == pytest.approx(2.0, abs=1e-6)
    assert doc["inFlight"] is None and doc["slow"] == []


def test_dispatch_stages_are_recorded_without_any_sampled_request(clock):
    runs = Runs(clock, h2d_s=0.004, device_s=0.2)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        assert not tracing.active_traces()
        mb.submit("unsampled")
        (rec,) = mb.dispatches()["dispatches"]
        assert rec["stagesMs"]["h2d"] == pytest.approx(4.0, abs=1e-6)
        assert rec["stagesMs"]["device_compute"] == pytest.approx(
            200.0, abs=1e-6)
        assert tracing.active_dispatch() is None  # the scope is restored
    finally:
        mb.stop()


def test_the_ring_is_bounded_and_seq_has_no_holes():
    mb = MicroBatcher(lambda qs: qs, buckets=(1, 8))
    try:
        n = MicroBatcher.RING + 44
        for i in range(n):
            mb.submit(i)
        doc = mb.dispatches()
        seqs = [r["seq"] for r in doc["dispatches"]]
        assert seqs == list(range(n, n - MicroBatcher.RING, -1))
        assert doc["started"] == n and doc["ringSize"] == MicroBatcher.RING
        assert [r["seq"] for r in mb.dispatches(limit=3)["dispatches"]] == [
            n, n - 1, n - 2]
    finally:
        mb.stop()


def test_a_failed_run_is_recorded_with_its_error():
    def broken(queries):
        raise ValueError("no scores today")

    mb = MicroBatcher(broken, buckets=(1, 8))
    try:
        with pytest.raises(ValueError):
            mb.submit("q")
        (rec,) = mb.dispatches()["dispatches"]
        assert rec["error"] == "ValueError" and rec["seq"] == 1
        assert mb.stats()["batches"] == 1
    finally:
        mb.stop()


# -- request traces -------------------------------------------------------------


def test_a_request_trace_sums_to_its_wall_with_d2h_and_postprocess(
        three_dispatches):
    _, _, traces = three_dispatches
    for tr in traces.values():
        d = tr.to_dict()
        assert {"queue_wait", "h2d", "device_compute", "d2h",
                "postprocess", "other"} <= set(d["stagesMs"])
        assert d["stagesMs"]["d2h"] == pytest.approx(1.0, abs=1e-3)
        assert d["stagesMs"]["postprocess"] == pytest.approx(2.0, abs=1e-3)
        assert "resolve" not in d["stagesMs"]  # the dispatch's, not a request's
        assert sum(d["stagesMs"].values()) == pytest.approx(
            d["wallMs"], abs=1e-2)


@pytest.mark.parametrize("query, queue_wait_ms", [
    ("A", 0.0), ("B", 256.0), ("D", 512.0)])
def test_a_requests_stages_are_what_they_were(three_dispatches, query,
                                              queue_wait_ms):
    """ISSUE 37 adds `meta` keys and profiler spans, no stage: on the
    test's clock a request's stages read what they read before it."""
    _, _, traces = three_dispatches
    d = traces[query].to_dict()
    stages = dict(d["stagesMs"])
    other = stages.pop("other")
    assert stages == {"queue_wait": queue_wait_ms, "h2d": 3.0,
                      "device_compute": 250.0, "d2h": 1.0, "postprocess": 2.0}
    assert d["wallMs"] == pytest.approx(sum(stages.values()) + other,
                                        abs=1e-3)
    if query == "D":  # the last run: nobody moves the clock after it
        assert (d["wallMs"], other) == (768.0, 0.0)


@pytest.mark.parametrize("query, handed_back", [
    ("A", False),  # inline: it ran on its own thread, nothing to hand back
    ("B", True),   # queued behind the run in flight
    ("D", True),   # carried by the cut
])
def test_a_queued_request_carries_its_hand_back_an_inline_one_none(
        three_dispatches, query, handed_back):
    _, _, traces = three_dispatches
    meta = traces[query].to_dict()["meta"]
    assert ("handback_ms" in meta) == handed_back
    if query == "D":
        assert meta["handback_ms"] == 0.0  # the test's clock stood still
    elif handed_back:
        assert meta["handback_ms"] >= 0.0
    # beside the keys that were there, not in place of one
    assert {"passes", "dispatch_seq", "dispatch", "batch"} <= set(meta)
    assert "handback" not in " ".join(traces[query].to_dict()["stagesMs"])


def test_resolve_stamps_a_traced_pending_only_and_submit_reads_the_stamp(
        clock):
    mb = MicroBatcher(lambda qs: qs, buckets=(1, 8))
    try:
        traced = batching._Pending("q", trace=tracing.Trace("req-q"))
        plain = batching._Pending("q")
        clock.advance(0.5)
        for p in (traced, plain):
            mb._resolve(p, result="r")
            assert p.event.is_set() and p.result == "r"
        assert traced.t_set == clock.t and plain.t_set == 0.0
    finally:
        mb.stop()


def test_the_hand_back_is_the_time_from_the_set_to_the_waking(clock):
    """The run is held; the waiter's event is set by hand 7 ms of the test's
    clock before it is let go, which is what `handback_ms` then reads."""
    runs = Runs(clock, device_s=0.25)
    mb = MicroBatcher(runs, buckets=(1, 8))
    woken = threading.Event()
    real_wait = threading.Event.wait

    class Held(threading.Event):
        def wait(self, timeout=None):  # the handler thread's wake-up, held
            ok = real_wait(self, timeout)
            assert real_wait(woken, WAIT_S)
            return ok

    try:
        runs.gate.clear()
        first = submit_traced(mb, "first")
        assert runs.entered.wait(WAIT_S)
        tr = tracing.Trace("req-second")
        p_event = Held()
        real_pending = batching._Pending

        def pending(*a, **kw):
            p = real_pending(*a, **kw)
            if p.query == "second":
                p.event = p_event
            return p

        batching._Pending = pending
        try:
            def go():
                with tracing.scope((tr,)):
                    mb.submit("second")

            th = threading.Thread(target=go, daemon=True)
            th.start()
            wait_until(lambda: mb.depth() == 1, "second queued")
        finally:
            batching._Pending = real_pending
        runs.gate.set()
        wait_until(lambda: mb.stats()["batches"] == 2, "both ran")
        clock.advance(0.007)
        woken.set()
        for t in (first[0], th):
            t.join(WAIT_S)
            assert not t.is_alive()
        assert tr.to_dict()["meta"]["handback_ms"] == pytest.approx(
            7.0, abs=1e-6)
    finally:
        runs.gate.set()
        woken.set()
        mb.stop()


def test_a_follower_of_a_coalesced_leader_carries_no_device_stages(clock):
    runs = Runs(clock, h2d_s=0.003, device_s=0.25, d2h_s=0.001)
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        runs.gate.clear()
        th_l, leader = submit_traced(mb, "same", key="k")
        assert runs.entered.wait(WAIT_S)
        th_f, follower = submit_traced(mb, "same", key="k")
        wait_until(lambda: mb.stats()["coalesced"] == 1, "follower attached")
        runs.gate.set()
        for th in (th_l, th_f):
            th.join(WAIT_S)
            assert not th.is_alive()
        assert len(runs.batches) == 1  # one device row for both
        lead, foll = leader.to_dict(), follower.to_dict()
        assert lead["meta"]["coalesce"] == "leader"
        assert lead["meta"]["dispatch_seq"] == 1
        assert lead["stagesMs"]["device_compute"] == pytest.approx(250.0,
                                                                   abs=1e-3)
        assert foll["meta"] == {"coalesce": "follower"}
        assert set(foll["stagesMs"]) == {"other"}
    finally:
        runs.gate.set()
        mb.stop()


# -- a run that holds the batcher -------------------------------------------------


@pytest.fixture()
def short_threshold(monkeypatch, tmp_path):
    dump = open(tmp_path / "stacks.txt", "w+")
    monkeypatch.setattr(MicroBatcher, "SLOW_FLOOR_S", 0.15)
    monkeypatch.setattr(MicroBatcher, "SLOW_DUMP_FILE", dump)
    yield dump
    dump.close()


def read_back(dump) -> str:
    dump.flush()
    dump.seek(0)
    return dump.read()


def test_a_run_past_the_threshold_leaves_one_dump_one_count_one_record(
        short_threshold, caplog):
    seen = {}

    def sleeps_past_the_threshold(queries):
        time.sleep(0.2)  # past the threshold: the dump is written now
        seen["stats"] = mb.stats()
        seen["doc"] = mb.dispatches()
        time.sleep(0.2)
        return queries

    mb = MicroBatcher(sleeps_past_the_threshold, buckets=(1, 8))
    try:
        with caplog.at_level("WARNING", logger=batching.__name__):
            mb.submit("q")
        text = read_back(short_threshold)
        assert text.count("Timeout (") == 1  # written once, not again
        assert "sleeps_past_the_threshold" in text
        assert mb.stats()["slow_dispatches"] == 1
        doc = mb.dispatches()
        (kept,) = doc["slow"]
        assert kept["seq"] == 1 and kept["wallMs"] > 150
        assert kept["threadId"] == f"{threading.get_ident():#018x}"
        # while the run still held the batcher it already counted, and the
        # in-flight view named the frame it sat in
        assert seen["stats"]["slow_dispatches"] == 1
        inflight = seen["doc"]["inFlight"]
        assert inflight["seq"] == 1 and inflight["heldMs"] > 150
        assert any("sleeps_past_the_threshold" in line
                   for line in inflight["stack"])
        assert sum("held the batcher" in r.message
                   for r in caplog.records) == 1
    finally:
        mb.stop()


def test_an_ordinary_run_leaves_no_dump_no_count_and_no_timer(
        short_threshold):
    mb = MicroBatcher(lambda qs: qs, buckets=(1, 8))
    try:
        for i in range(5):
            mb.submit(i)
        time.sleep(0.3)  # a timer left armed would fire by now
        assert read_back(short_threshold) == ""
        assert mb.stats()["slow_dispatches"] == 0
        assert mb.dispatches()["slow"] == []
    finally:
        mb.stop()


def test_slow_records_outlive_ordinary_traffic(short_threshold):
    slow_once = {"left": 1}

    def run(queries):
        if slow_once["left"]:
            slow_once["left"] -= 1
            time.sleep(0.25)
        return queries

    mb = MicroBatcher(run, buckets=(1, 8))
    try:
        for i in range(MicroBatcher.RING + 10):
            mb.submit(i)
        doc = mb.dispatches()
        assert 1 not in [r["seq"] for r in doc["dispatches"]]  # evicted
        assert [r["seq"] for r in doc["slow"]] == [1]  # kept
        assert read_back(short_threshold).count("Timeout") == 1
    finally:
        mb.stop()


def test_a_log_without_a_file_descriptor_still_counts(monkeypatch):
    import io

    monkeypatch.setattr(MicroBatcher, "SLOW_FLOOR_S", 0.05)
    monkeypatch.setattr(MicroBatcher, "SLOW_DUMP_FILE", io.StringIO())

    def run(queries):
        time.sleep(0.1)
        return queries

    mb = MicroBatcher(run, buckets=(1, 8))
    try:
        mb.submit("q")
        assert mb.stats()["slow_dispatches"] == 1
    finally:
        mb.stop()


# -- the profiler's clock ------------------------------------------------------------


def test_stages_reach_a_profiler_session_as_pio_annotations(tmp_path):
    import jax

    runs = Runs()  # real clock; the stages are entered and left at once
    mb = MicroBatcher(runs, buckets=(1, 8))
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            mb.submit("q")
        finally:
            jax.profiler.stop_trace()
    finally:
        mb.stop()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"pio.h2d", "pio.device_compute", "pio.d2h",
            "pio.resolve"} <= names


def test_the_launch_span_carries_the_dispatchs_seq_and_rung(monkeypatch):
    seen = []
    monkeypatch.setattr(
        tracing, "annotation",
        lambda name, **kv: seen.append((name, kv)) or tracing._NO_SPAN)
    rec = tracing.Dispatch(7, False, 2, 0, t_run=0.0, collect_s=0.0,
                           slow_after_s=2.0)
    with tracing.launch():  # no dispatch active: a bare span
        pass
    with tracing.scope((), dispatch=rec):
        with tracing.stage("h2d"):  # before the scorer names its rung
            pass
        rec.rung = 8
        with tracing.stage("device_compute"):
            with tracing.launch():
                pass
    assert seen == [
        ("pio.launch", {}),
        ("pio.h2d", {"seq": 7}),
        ("pio.device_compute", {"seq": 7, "rung": 8}),
        ("pio.launch", {"seq": 7, "rung": 8}),
    ]
    # launch is on the profiler's clock only: charged to no record
    assert set(rec.stages) == set(tracing.Dispatch.STAGES)


def test_add_stage_is_gone_and_one_module_touches_the_profilers_spans():
    assert not hasattr(tracing, "add_stage")
    assert hasattr(tracing.Trace, "add_stage")  # the method stays
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(tracing.__file__)))
    users = set()
    for path in glob.glob(os.path.join(pkg, "**", "*.py"), recursive=True):
        with open(path) as f:
            if "TraceAnnotation(" in f.read():
                users.add(os.path.relpath(path, pkg))
    assert users == {os.path.join("obs", "tracing.py")}


def test_the_score_program_carries_its_scope():
    import jax
    import jax.numpy as jnp

    from predictionio_tpu.ops import topk

    def fn(U, V, idx):
        return topk.gather_score_topk(U, V, idx, 3, backend="reference")

    text = jax.jit(fn).lower(
        jnp.zeros((4, 2)), jnp.zeros((8, 2)), jnp.zeros((2,), jnp.int32)
    ).as_text(debug_info=True)
    assert "jit(fn)/" + topk.SCORE_SCOPE in text
    assert "module @jit_fn " in text  # the name the benchmark's readers match


def test_the_dense_train_step_carries_its_scope(monkeypatch):
    from predictionio_tpu.models import als
    from predictionio_tpu.data.bimap import BiMap
    from predictionio_tpu.data.batch import Interactions
    from predictionio_tpu.parallel.mesh import MeshContext

    users, items = np.nonzero(np.random.default_rng(0).random((20, 12)) < 0.5)
    ratings = Interactions(
        user=users.astype(np.int32), item=items.astype(np.int32),
        rating=np.ones(len(users), np.float32), t=np.zeros(len(users)),
        user_map=BiMap.string_int(f"u{i}" for i in range(20)),
        item_map=BiMap.string_int(f"i{i}" for i in range(12)))
    real, seen = als._make_dense_step, {}

    def lowered_once(*a, **kw):
        step = real(*a, **kw)

        def spy(*args):
            if not seen:
                seen["text"] = step.lower(*args).as_text(debug_info=True)
            return step(*args)

        return spy

    monkeypatch.setattr(als, "_make_dense_step", lowered_once)
    als.train_als(MeshContext.create(), ratings,
                  als.ALSConfig(rank=2, iterations=1, solver="dense"))
    assert "jit(step)/pio.als_half_step/" in seen["text"]


def test_profiling_trace_has_no_stage_mode_left():
    from predictionio_tpu.serving import fastpath
    from predictionio_tpu.utils import profiling

    assert list(inspect.signature(profiling.trace).parameters) == ["log_dir"]
    assert not hasattr(fastpath, "_profiling")


# -- over HTTP ------------------------------------------------------------------------


def _http(url, body=None, headers=None):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read().decode())


@pytest.fixture()
def served(storage):
    from predictionio_tpu.core.workflow import run_train
    from predictionio_tpu.data import Event
    from predictionio_tpu.data import store as store_mod
    from predictionio_tpu.data.storage import App
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving.query_server import QueryServer
    from predictionio_tpu.templates.recommendation import RecommendationEngine

    store_mod.set_storage(storage)
    app_id = storage.get_meta_data_apps().insert(App(0, "dispapp"))
    le = storage.get_l_events()
    le.init(app_id)
    rng = np.random.default_rng(25)
    le.batch_insert(
        [Event(event="rate", entity_type="user", entity_id=f"u{u}",
               target_entity_type="item", target_entity_id=f"i{i}",
               properties={"rating": float(rng.integers(1, 6))})
         for u in range(10) for i in rng.choice(10, size=4, replace=False)],
        app_id)
    engine = RecommendationEngine.apply()
    ep = engine.params_from_variant({
        "datasource": {"params": {"appName": "dispapp"}},
        "algorithms": [{"name": "als",
                        "params": {"rank": 2, "numIterations": 2}}]})
    ctx = MeshContext.create()
    run_train(engine, ep, "disp", storage=storage, ctx=ctx)
    servers = []

    def start(**kw):
        qs = QueryServer(engine, storage=storage, ctx=ctx, **kw)
        servers.append(qs)
        return qs, f"http://127.0.0.1:{qs.start('127.0.0.1', 0)}"

    yield start
    for qs in servers:
        qs.stop()
    store_mod.set_storage(None)


def test_trace_dispatches_json_serves_one_record_per_dispatch(served):
    qs, base = served(batching=True)
    rids = [uuid.uuid4().hex[:16] for _ in range(6)]
    for n, rid in enumerate(rids):
        _http(base + "/queries.json", {"user": f"u{n}", "num": 3},
              headers={tracing.TRACE_HEADER: rid})
    doc = _http(base + "/trace/dispatches.json")
    recs = doc["dispatches"]
    assert [r["seq"] for r in recs] == list(range(len(recs), 0, -1))
    assert len(recs) == qs._batcher.stats()["batches"] == 6
    for r in recs:
        assert r["rung"] == 1 and r["rows"] == 1 and r["inline"]
        assert r["stagesMs"]["device_compute"] > 0
        assert {"h2d", "device_compute", "d2h"} <= set(r["stagesMs"])
        assert sum(r["stagesMs"].values()) == pytest.approx(r["wallMs"],
                                                            abs=1e-3)
    # every traced request names a dispatch that is there, and its own
    # stages still sum to its wall
    mine, end = [], time.monotonic() + 5.0
    while len(mine) < len(rids) and time.monotonic() < end:
        traces = _http(base + "/trace/recent.json")["traces"]
        mine = [t for t in traces if t["requestId"] in rids]
        time.sleep(0.02)
    assert len(mine) == len(rids)
    for t in mine:
        assert t["meta"]["dispatch_seq"] in {r["seq"] for r in recs}
        assert t["meta"]["passes"] == 1
        assert {"d2h", "postprocess", "device_compute"} <= set(t["stagesMs"])
        assert sum(t["stagesMs"].values()) == pytest.approx(t["wallMs"],
                                                            abs=0.05)
    assert len(_http(base + "/trace/dispatches.json?limit=2")["dispatches"]) == 2
    # the counters ride GET / with the rest of the batcher's
    root = _http(base + "/")["batching"]
    assert root["run_ms_max_seq"] >= 1 and root["slow_dispatches"] == 0


def test_one_request_is_joined_from_its_first_byte_to_its_launch(
        served, monkeypatch):
    """Request presence, the dispatch's stages and the launch, as the
    profiler would see them: one id, one seq, one rung."""
    seen, real = [], tracing.annotation
    lock = threading.Lock()

    def spy(name, **kv):
        with lock:
            seen.append((name, kv))
        return real(name, **kv)

    monkeypatch.setattr(tracing, "annotation", spy)
    qs, base = served(batching=True)
    rid = uuid.uuid4().hex[:16]
    _http(base + "/queries.json", {"user": "u1", "num": 3},
          headers={tracing.TRACE_HEADER: rid})
    mine, end = [], time.monotonic() + 5.0
    while not mine and time.monotonic() < end:
        mine = [t for t in _http(base + "/trace/recent.json")["traces"]
                if t["requestId"] == rid]
        time.sleep(0.02)
    (trace,) = mine
    seq = trace["meta"]["dispatch_seq"]
    by_name = {}
    for name, kv in seen:
        by_name.setdefault(name, []).append(kv)
    assert {"id": rid} in by_name["pio_req.handle"]
    assert by_name["pio_req.parse"][0] == {}
    assert by_name["pio.launch"] == [{"seq": seq, "rung": 1}]
    assert by_name["pio.device_compute"] == [{"seq": seq, "rung": 1}]
    # the handle span opens before the request's first stage and the parse
    # span before it; the launch lies inside device_compute
    order = [n for n, kv in seen if kv.get("id", rid) == rid
             and kv.get("seq", seq) == seq]
    assert order.index("pio_req.parse") < order.index("pio_req.handle") \
        < order.index("pio.decode")
    assert order.index("pio.device_compute") < order.index("pio.launch") \
        < order.index("pio.d2h")
    # of the names ISSUE 37 adds only the launch is a `pio.` stage-side
    # span: the benchmark's idle.named_share unions every `pio.` span and
    # must go on reading the stages alone
    old = {"pio." + s for s in tracing.Dispatch.STAGES} | {
        "pio.decode", "pio.serialize"}
    assert set(by_name) - old == {"pio_req.parse", "pio_req.handle",
                                  "pio.launch"}
    # the parse time rides on the trace, outside its wall and its stages
    assert trace["meta"]["parse_ms"] > 0
    assert "handback_ms" not in trace["meta"]  # it ran inline
    assert sum(trace["stagesMs"].values()) == pytest.approx(
        trace["wallMs"], abs=0.05)


def test_trace_dispatches_json_without_batching_is_404(served):
    _, base = served()
    with pytest.raises(urllib.error.HTTPError) as err:
        _http(base + "/trace/dispatches.json")
    assert err.value.code == 404
