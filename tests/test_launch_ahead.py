"""Launch-ahead (ISSUE 40): with rows waiting, the next run is cut and
launched by the batcher's other worker shortly before the run in flight is
due, so that the device goes from one program to the next without the
host's wake-up, hand-off and launch in between.

The "device" here is a thread that runs enqueued programs in order, one at
a time, each for a set time on the real clock: what a chip does behind
JAX's asynchronous dispatch, so overlap is observable on a CPU.  The fake
scorer tells the batcher what a real one does, through the same hooks
(``Dispatch.rung`` / ``.more`` and ``obs.tracing.launch()``).
"""

import queue
import threading
import time

import pytest

from predictionio_tpu.common.resilience import Deadline, DeadlineExceeded
from predictionio_tpu.obs import tracing
from predictionio_tpu.serving.batching import MicroBatcher
from predictionio_tpu.serving.launch_gate import LaunchGate, measure_lag

WAIT_S = 10.0
PROGRAM_S = 0.08  # long against a CPU's scheduling noise, short for tier-1
# the host's work before a launch and after a program's return: what the
# device would wait out between two programs if nothing were launched ahead
HOST_S = 0.01
# how long after a program's end its waiter hears of it (on the chip: the
# wake-up and the outputs' landing); the device is free meanwhile
WAKE_S = 0.02


class Device(threading.Thread):
    """Programs run in the order they were enqueued, one at a time."""

    def __init__(self):
        super().__init__(daemon=True)
        self._programs = queue.Queue()
        self.lock = threading.Lock()
        self.enqueued = 0  # programs enqueued and not yet finished
        self.most_enqueued = 0
        self.ran = []  # (tag, enqueued at, started, ended), by start
        self.start()

    def enqueue(self, tag, seconds) -> threading.Event:
        done = threading.Event()
        with self.lock:
            self.enqueued += 1
            self.most_enqueued = max(self.most_enqueued, self.enqueued)
        self._programs.put((tag, seconds, time.perf_counter(), done))
        return done

    def run(self):
        while True:
            tag, seconds, t_enq, done = self._programs.get()
            t0 = time.perf_counter()
            time.sleep(seconds)
            with self.lock:
                self.ran.append((tag, t_enq, t0, time.perf_counter()))
                self.enqueued -= 1
            threading.Timer(WAKE_S, done.set).start()

    def gaps_ms(self):
        """Device idle between consecutive programs, ms."""
        return [(b[2] - a[3]) * 1e3 for a, b in zip(self.ran, self.ran[1:])]


class Scorer:
    """``run_batch`` as a scorer behind ``Algorithm.batch_predict`` behaves:
    names its rung, launches through ``tracing.launch()``, waits inside the
    ``device_compute`` stage."""

    def __init__(self, device, seconds=PROGRAM_S, rung=lambda n: n):
        self.device, self.seconds, self.rung = device, seconds, rung
        self.batches = []
        self.fail = set()  # queries whose batch raises after its program
        self.hold = {}  # query -> seconds its batch's program takes instead
        self.launches = False  # False: a scorer that launches nothing
        self.lag = WAKE_S  # what a real scorer measures at warm-up

    def __call__(self, queries):
        disp = tracing.active_dispatch()
        self.batches.append(list(queries))
        seconds = max([self.hold.get(q, 0.0) for q in queries]) or self.seconds
        with tracing.stage("batch_assembly"):
            time.sleep(HOST_S)
        with tracing.stage("device_compute"):
            if self.launches:
                disp.rung, disp.more = self.rung(len(queries)), False
                disp.lag = self.lag
                with tracing.launch():
                    done = self.device.enqueue(tuple(queries), seconds)
            else:
                done = self.device.enqueue(tuple(queries), seconds)
            assert done.wait(WAIT_S)
        time.sleep(HOST_S)  # answers built: `postprocess`
        if self.fail & set(queries):
            raise ValueError("this batch fails")
        return [("answer", q) for q in queries]


class Client:
    """``submit`` on a thread of its own; the outcome is kept."""

    def __init__(self, mb, query, **kw):
        self.query, self.outcome = query, None

        def go():
            try:
                self.outcome = mb.submit(query, **kw)
            except BaseException as e:  # kept for the test to look at
                self.outcome = e

        self.thread = threading.Thread(target=go, daemon=True)
        self.thread.start()

    def join(self):
        self.thread.join(WAIT_S)
        assert not self.thread.is_alive(), f"{self.query} hangs"
        return self.outcome


def wait_until(cond, what):
    end = time.monotonic() + WAIT_S
    while not cond():
        assert time.monotonic() < end, what
        time.sleep(0.001)


@pytest.fixture()
def rig():
    """A batcher over a launching scorer, every row count a rung of its
    own (no carry).  Nothing has run: no rung has an estimate."""
    device = Device()
    scorer = Scorer(device)
    scorer.launches = True
    mb = MicroBatcher(scorer, max_batch=8, buckets=tuple(range(1, 9)))
    try:
        yield mb, scorer, device
    finally:
        mb.stop()


def pair(mb, first, second, **kw):
    """``first`` inline on the free device, ``second`` arriving while it is
    in flight; both answered."""
    a = Client(mb, first)
    wait_until(lambda: mb._busy.locked(), f"{first} in flight")
    b = Client(mb, second, **kw)
    return a.join(), b.join()


def teach(mb):
    """One inline run and one by a worker behind a second: rung 1 (one row)
    has an estimate from here on, and so has a worker's lead.  (That first
    worker run knew no lead: it went at the instant its program should
    have been enqueued, so a lead late.)"""
    assert mb.submit("warm") == ("answer", "warm")
    assert pair(mb, "warm.a", "warm.b") == (
        ("answer", "warm.a"), ("answer", "warm.b"))
    s = stats(mb)
    assert "1" in s["launch_run_ms"] and s["launch_lead_ms"] > 0


def settle(mb):
    """Until no run is in flight: a waiter is woken before its run is
    counted and its record kept, the right to launch is given up after."""
    wait_until(lambda: not mb._busy.locked(), "every run has ended")


def stats(mb):
    settle(mb)
    return mb.stats()


def records(mb):
    settle(mb)
    return {r["seq"]: r for r in mb.dispatches()["dispatches"]}


def newest(mb):
    recs = records(mb)
    return recs[max(recs)]


def behind_a_run(rec) -> bool:
    """The run was cut and launched while another was in flight: ahead of
    its return (``launchedAhead``; ``aheadMs`` > 0) or, on a CPU under
    load, a little after it (``aheadMs`` <= 0) — not after its END, which
    reads ``aheadMs`` None."""
    return rec["aheadMs"] is not None


# -- (a) the launch precedes the return, and the cut is late ------------------


def test_with_a_row_waiting_and_an_estimate_the_next_run_is_launched_ahead(
        rig):
    mb, scorer, device = rig
    teach(mb)
    before = stats(mb)
    est_s = before["launch_run_ms"]["1"] / 1e3
    lead_s = before["launch_lead_ms"] / 1e3
    assert est_s == pytest.approx(PROGRAM_S, abs=0.03)
    assert HOST_S <= lead_s < HOST_S + 0.03
    n = len(device.ran)
    a = Client(mb, "A")  # inline on a free device: rung 1
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b = Client(mb, "B")  # waits from the start of A's run
    time.sleep(PROGRAM_S / 2)
    c = Client(mb, "C")  # arrives mid-run: the cut is late enough for it
    for cl in (a, b, c):
        assert cl.join() == ("answer", cl.query)
    assert scorer.batches[-2:] == [["A"], ["B", "C"]]
    (tag_a, _, a_start, a_end), (tag_bc, bc_enq, bc_start, _) = device.ran[n:]
    assert (tag_a, tag_bc) == (("A",), ("B", "C"))
    recs = records(mb)
    rec_a, rec_bc = recs[max(recs) - 1], recs[max(recs)]
    # B + C's program was enqueued while A's ran, before A's run returned
    # (its answers still to build), and started as A's ended
    a_returned = rec_a["startMonotonic"] + rec_a["wallMs"] / 1e3
    assert a_start < bc_enq < a_returned - HOST_S / 2
    assert (bc_start - a_end) * 1e3 < HOST_S * 1e3
    assert rec_a["inline"] and not rec_a["launchedAhead"]
    assert rec_bc["launchedAhead"] and rec_bc["aheadMs"] > 0
    # the cut was made no earlier than the lead before A's estimated end:
    # B + C's run starts (collect over) at A's launch + estimate - lead
    a_launch = rec_a["startMonotonic"] + (
        rec_a["stagesMs"]["collect"] + rec_a["stagesMs"]["batch_assembly"]
    ) / 1e3
    bc_cut = rec_bc["startMonotonic"] + rec_bc["stagesMs"]["collect"] / 1e3
    assert bc_cut >= a_launch + est_s - lead_s - 0.002
    s = stats(mb)
    assert s["ahead_batches"] == before["ahead_batches"] + 1
    assert s["ahead_missed"] == before["ahead_missed"]


def test_a_launched_ahead_run_teaches_the_time_from_the_previous_return(rig):
    """Its ``device_compute`` holds the time its program sat queued; the
    estimate is taught the rest, so it stays what one program takes."""
    mb, scorer, device = rig
    teach(mb)
    for _ in range(3):
        pair(mb, "A", "B")
    s = stats(mb)
    assert s["ahead_batches"] >= 2
    ahead = [r for r in records(mb).values() if r["launchedAhead"]]
    assert all(r["stagesMs"]["device_compute"] > PROGRAM_S * 1e3 + r[
        "aheadMs"] - 5.0 for r in ahead)
    # on a free device or queued, with the host's lag taken out
    assert s["launch_run_ms"]["1"] == pytest.approx(PROGRAM_S * 1e3, abs=10)
    # and the cut's per-rung estimate (a RUN at one row: assembly, program,
    # wake-up, answers) leaves the queued time out as well: no run read
    # longer for having been launched early.  The upper side is that claim
    # and stays where it was (a program queued for its whole wait reads
    # 195); the lower is only "no shorter than the program": a launched-
    # ahead run is timed from the previous return, which a loaded CPU
    # delivers late, so the least of five read 97.6 and 99.8 under xdist
    run_ms = (2 * HOST_S + PROGRAM_S + WAKE_S) * 1e3 - 5
    assert PROGRAM_S * 1e3 < s["rung_run_ms"]["1"] <= run_ms + 15


# -- (b) no estimate, or nothing waiting: as before ------------------------------


def test_without_an_estimate_the_run_in_flight_is_waited_out(rig):
    mb, scorer, device = rig
    a = Client(mb, "A")  # rung 1 has never run: nothing says when it ends
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b, c = Client(mb, "B"), Client(mb, "C")
    wait_until(lambda: mb.depth() == 2, "B and C wait")
    for cl in (a, b, c):
        cl.join()
    (_, _, _, a_end), (_, bc_enq, _, _) = device.ran
    # launched after A's run had ended, answers built and all
    assert bc_enq > a_end + HOST_S
    rec = records(mb)[2]
    assert not rec["launchedAhead"] and rec["aheadMs"] is None
    s = stats(mb)
    assert (s["ahead_batches"], s["ahead_missed"]) == (0, 0)
    assert s["turnaround_n"] == 1 and s["turnaround_ms_sum"] > HOST_S * 1e3
    # that run taught rung 1: the next row behind a one-row run goes ahead
    pair(mb, "A2", "B2")
    assert behind_a_run(newest(mb))


def test_a_scorer_that_launches_nothing_through_the_hook_is_waited_out():
    device = Device()
    scorer = Scorer(device)  # launches = False: never names a rung
    mb = MicroBatcher(scorer, max_batch=8, buckets=tuple(range(1, 9)))
    try:
        for _ in range(3):
            pair(mb, "A", "B")
        s = stats(mb)
        assert (s["ahead_batches"], s["ahead_missed"]) == (0, 0)
        assert s["launch_run_ms"] == {} and device.most_enqueued == 1
    finally:
        mb.stop()


def test_with_nothing_waiting_an_arrival_on_a_free_device_runs_inline(rig):
    mb, scorer, device = rig
    teach(mb)
    before = stats(mb)
    device.most_enqueued = 0
    for q in "ABC":
        assert mb.submit(q) == ("answer", q)
    s = stats(mb)
    assert s["inline_batches"] - before["inline_batches"] == 3
    assert s["batches"] - before["batches"] == 3
    assert s["ahead_batches"] == before["ahead_batches"]
    assert device.most_enqueued == 1
    main = threading.current_thread().name
    recs = records(mb)
    assert [recs[n]["thread"] for n in sorted(recs)[-3:]] == [main] * 3


# -- (c) one program queued behind the one running, never two --------------------


def test_never_more_than_one_run_is_queued_behind_the_one_in_flight(rig):
    mb, scorer, device = rig
    scorer.seconds = 0.02
    scorer.rung = lambda n: 1  # one rung: every run in flight has an estimate
    # a program in seven takes half as long again as its rung's estimate:
    # the one behind it is then on the device's queue while it still runs
    scorer.hold = {i: 0.03 for i in range(0, 80, 7)}
    teach(mb)
    put_order, real_put = [], mb._queue.put

    def put(p, *a, **kw):  # called under the batcher's arrival lock
        put_order.append(p.query)
        return real_put(p, *a, **kw)

    mb._queue.put = put
    clients = []
    for i in range(80):
        clients.append(Client(mb, i))
        time.sleep(0.004)
    for cl in clients:
        assert cl.join() == ("answer", cl.query)
    assert device.most_enqueued == 2
    s = stats(mb)
    assert s["ahead_batches"] >= 8
    # FIFO across every dispatch: the queued rows ran in the order queued
    queued = set(put_order)
    ran = [q for batch in scorer.batches for q in batch if q in queued]
    assert ran == put_order
    # back to back: between two programs of a chain the device idled less
    # than the host's share of a cycle — the wake-up, the answers' building
    # and the next batch's assembly — which it waited out before.  (Not
    # HOST_S alone: these are host-clock gaps between threads, and with six
    # xdist workers busy a wake-up alone comes 10 ms late now and then.)
    ahead = {tuple(b) for b, r in zip(
        scorer.batches, sorted(records(mb).values(), key=lambda r: r["seq"]))
        if r["launchedAhead"]}
    chained = sorted(g for g, r in zip(device.gaps_ms(), device.ran[1:])
                     if r[0] in ahead)
    assert chained[len(chained) // 2] < (WAKE_S + 2 * HOST_S) * 1e3


# -- (d) what the batcher promised before still holds -----------------------------


def test_a_row_whose_deadline_lapses_before_the_launch_is_dropped_not_run(
        rig):
    mb, scorer, device = rig
    teach(mb)
    a = Client(mb, "A")
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b = Client(mb, "B", deadline=Deadline.after_ms(PROGRAM_S * 1e3 / 4))
    c = Client(mb, "C")
    assert isinstance(b.join(), DeadlineExceeded)
    assert a.join() == ("answer", "A") and c.join() == ("answer", "C")
    assert scorer.batches[-2:] == [["A"], ["C"]]  # B was cut and dropped
    assert stats(mb)["expired_dropped"] == 1 and behind_a_run(newest(mb))


def test_single_flight_followers_ride_a_launched_ahead_leader(rig):
    mb, scorer, device = rig
    teach(mb)
    a = Client(mb, "A")
    wait_until(lambda: mb._busy.locked(), "A in flight")
    same = [Client(mb, "B", key="k") for _ in range(4)]
    for cl in [a, *same]:
        assert cl.join() == ("answer", cl.query)
    assert scorer.batches[-2:] == [["A"], ["B"]]
    assert stats(mb)["coalesced"] == 3 and behind_a_run(newest(mb))


def test_a_failed_run_does_not_fail_the_waiters_of_the_one_behind_it(rig):
    mb, scorer, device = rig
    teach(mb)
    taught = sum(len(d) for d in mb._launch_runs.values())
    scorer.fail = {"A"}
    out_a, out_b = pair(mb, "A", "B")
    assert isinstance(out_a, ValueError) and out_b == ("answer", "B")
    recs = records(mb)
    assert recs[max(recs) - 1]["error"] == "ValueError"
    assert "error" not in recs[max(recs)] and behind_a_run(recs[max(recs)])
    # and the other way round: the run behind fails, the one in flight not
    scorer.fail = {"D"}
    out_c, out_d = pair(mb, "C", "D")
    assert out_c == ("answer", "C") and isinstance(out_d, ValueError)
    s = stats(mb)
    assert behind_a_run(newest(mb)) and newest(mb)["error"] == "ValueError"
    # a failed run teaches no estimate: rung 1 heard of B and C only
    assert sum(len(d) for d in mb._launch_runs.values()) == taught + 2
    assert s["launch_run_ms"]["1"] == pytest.approx(
        PROGRAM_S * 1e3, abs=10)


def test_stop_with_two_runs_in_flight_finishes_or_fails_every_waiter(rig):
    mb, scorer, device = rig
    teach(mb)
    scorer.hold = {"A": 0.4, "B": 0.4}  # B is launched at A's estimate
    a = Client(mb, "A")
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b = Client(mb, "B")
    wait_until(lambda: device.enqueued == 2, "B launched behind A")
    waiting = [Client(mb, f"W{i}") for i in range(3)]
    wait_until(lambda: mb.depth() == 3, "three rows wait behind both")
    t0 = time.monotonic()
    mb.stop()
    for cl in waiting:  # queued, or in a worker's hand: failed fast
        out = cl.join()
        assert isinstance(out, RuntimeError) and "shutting down" in str(out)
    assert time.monotonic() - t0 < 2.0
    # the two runs in flight deliver to their own waiters
    assert a.join() == ("answer", "A") and b.join() == ("answer", "B")
    wait_until(lambda: not any(w.is_alive() for w in mb._workers),
               "both workers ended")
    assert mb.depth() == 0 and not mb._busy.locked()


# -- (e) a pause -------------------------------------------------------------------


def test_a_run_that_overruns_its_estimate_tenfold_neither_hangs_nor_teaches(
        rig):
    mb, scorer, device = rig
    teach(mb)
    scorer.hold = {"A": PROGRAM_S * 10}
    a = Client(mb, "A")
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b = Client(mb, "B")
    wait_until(lambda: device.enqueued == 2, "B launched at A's estimate")
    c = Client(mb, "C")  # a third run: not until one of the two has ended
    wait_until(lambda: mb.depth() == 1, "C waits")
    time.sleep(PROGRAM_S * 2)
    assert device.most_enqueued == 2 and c.outcome is None
    for cl in (a, b, c):
        assert cl.join() == ("answer", cl.query)
    assert scorer.batches[-3:] == [["A"], ["B"], ["C"]]
    s = stats(mb)
    # the overrun taught nothing: a program at rung 1 still takes what
    # the others took (the least of the newest five)
    assert s["launch_run_ms"]["1"] == pytest.approx(
        PROGRAM_S * 1e3, abs=10)
    assert s["rung_run_ms"]["1"] < PROGRAM_S * 2e3
    assert s["depth"] == 0 and not mb._busy.locked()


# -- (f) the records ---------------------------------------------------------------


def test_two_overlapping_runs_each_tile_their_wall_and_show_in_flight(rig):
    mb, scorer, device = rig
    teach(mb)
    before = stats(mb)
    scorer.hold = {"A": PROGRAM_S * 2}  # B is enqueued at A's ESTIMATED end
    a = Client(mb, "A")
    wait_until(lambda: mb._busy.locked(), "A in flight")
    b = Client(mb, "B")
    wait_until(lambda: device.enqueued == 2, "B launched behind A")
    doc = mb.dispatches()
    first, second = doc["inFlight"], doc["inFlightAhead"]
    assert first["seq"] + 1 == second["seq"] == doc["started"]
    assert first["inline"] and not second["inline"]
    assert second["thread"] == "query-microbatcher"
    assert second["launchedAhead"] and second["aheadMs"] is None  # not yet
    for view in (first, second):
        assert view["heldMs"] > 0 and any(
            "enqueue" in line or "wait" in line for line in view["stack"])
    a.join(), b.join()
    settle(mb)
    doc = mb.dispatches()
    assert doc["inFlight"] is None and doc["inFlightAhead"] is None
    rec_b, rec_a = doc["dispatches"][:2]
    for r in (rec_a, rec_b):
        assert sum(r["stagesMs"].values()) == pytest.approx(
            r["wallMs"], abs=1e-2)
    # the two walls overlap: B's run began before A's ended
    assert rec_b["startMonotonic"] < rec_a["startMonotonic"] + rec_a[
        "wallMs"] / 1e3
    s = stats(mb)
    # launched before A's device_compute returned: the device waited 0
    assert s["turnaround_n"] == before["turnaround_n"] + 1
    assert s["turnaround_ms_sum"] == before["turnaround_ms_sum"]
    assert s["ahead_batches"] == before["ahead_batches"] + 1
    assert s["batches"] == before["batches"] + 2
    assert s["slow_dispatches"] == 0


def test_a_row_that_comes_as_the_run_ends_is_launched_late_or_counts_missed(
        rig):
    """A row that arrives after the launch instant is launched at once if
    the run is still in flight (``aheadMs`` says how late: the device
    waited that long, and ``turnaround`` counts it) and counted as missed
    if the run ended first.  Nothing is lost, and the counters add up."""
    mb, scorer, device = rig
    teach(mb)
    before = stats(mb)
    pairs = 6
    for i in range(pairs):
        a = Client(mb, f"A{i}")
        wait_until(lambda: mb._busy.locked(), "A in flight")
        # around the end of A's program, and ever later
        time.sleep(HOST_S + PROGRAM_S - 0.004 + i * 0.006)
        b = Client(mb, f"B{i}")
        assert a.join() == ("answer", f"A{i}")
        assert b.join() == ("answer", f"B{i}")
    s = stats(mb)
    recs = [r for r in records(mb).values()
            if not r["inline"] and r["seq"] > before["batches"]]
    assert len(recs) == pairs
    assert s["ahead_batches"] - before["ahead_batches"] == sum(
        r["launchedAhead"] for r in recs)
    behind = [r for r in recs if r["aheadMs"] is not None]
    assert all(r["launchedAhead"] == (r["aheadMs"] > 0) for r in behind)
    assert any(r["aheadMs"] <= 0 for r in behind)  # some came late
    # the device waited as long as each late one was late (and, for a run
    # that was missed and started on the free device, the whole hand-over)
    assert s["turnaround_ms_sum"] - before["turnaround_ms_sum"] >= sum(
        max(0.0, -r["aheadMs"]) for r in behind) - 1e-2
    assert s["turnaround_n"] - before["turnaround_n"] >= len(behind)
    assert len(behind) + s["ahead_missed"] - before["ahead_missed"] <= pairs
    assert s["queries"] - before["queries"] == 2 * pairs


@pytest.mark.parametrize("hold, why", [
    ({"A": 0.3}, "B's start must not disarm A's watch"),
    ({"B": 0.4}, "A's end must leave B's armed"),
])
def test_the_one_watchdog_is_kept_for_whichever_run_is_due_first(
        rig, monkeypatch, tmp_path, hold, why):
    """faulthandler has one timer a process, and two runs overlap."""
    mb, scorer, device = rig
    teach(mb)
    dump = open(tmp_path / "stacks.txt", "w+")
    monkeypatch.setattr(MicroBatcher, "SLOW_FLOOR_S", 0.25)
    monkeypatch.setattr(MicroBatcher, "SLOW_MULT", 0.0)
    monkeypatch.setattr(MicroBatcher, "SLOW_DUMP_FILE", dump)
    try:
        scorer.hold = hold
        pair(mb, "A", "B")  # B is launched at A's estimate, 80 ms in
        assert behind_a_run(newest(mb))
        time.sleep(0.3)  # a timer left armed would fire by now
        dump.flush()
        dump.seek(0)
        assert dump.read().count("Timeout (") == 1, why
        slow = [r["seq"] for r in mb.dispatches()["slow"]]
        assert len(slow) >= 1 and stats(mb)["slow_dispatches"] == len(slow)
    finally:
        dump.close()


# -- the scorers' gate: two programs enqueued only where both fit ------------------


class Chip:
    def __init__(self, limit, in_use):
        self.stats = {"bytes_limit": limit, "bytes_in_use": in_use}

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("need, limit, held", [
    ({1: 10, 2: 10}, 100, 0),      # both fit beside what is resident
    ({1: 10, 2: 25}, 100, 1),      # the second does not: it waits
    ({1: 10, 2: 25}, None, 0),     # a backend that names no limit
])
def test_a_launch_that_does_not_fit_beside_the_one_in_flight_waits(
        need, limit, held):
    chip = Chip(limit, 70)
    if limit is None:
        chip.stats = None
    gate = LaunchGate(chip, need)
    order, first_in = [], threading.Event()
    release = threading.Event()

    def first():
        with gate.flight(1):
            order.append("first in")
            first_in.set()
            assert release.wait(WAIT_S)
            order.append("first out")

    def second():
        with gate.flight(2):
            order.append("second in")

    t1 = threading.Thread(target=first, daemon=True)
    t1.start()
    assert first_in.wait(WAIT_S)
    t2 = threading.Thread(target=second, daemon=True)
    t2.start()
    if held:
        wait_until(lambda: gate.held == 1, "the second launch is held")
        time.sleep(0.02)
        assert order == ["first in"]
    else:
        t2.join(WAIT_S)
        assert order == ["first in", "second in"]
    release.set()
    t1.join(WAIT_S), t2.join(WAIT_S)
    assert not t1.is_alive() and not t2.is_alive()
    assert gate.held == held and "second in" in order
    if held:
        assert order == ["first in", "first out", "second in"]
    # alone, whatever its size, a launch never waits
    with gate.flight(2):
        pass
    assert gate.held == held


def test_the_lag_is_what_a_free_program_takes_beyond_a_queued_one():
    """What the scorers measure at warm-up: one program twice in a row on
    an idle device; the first returns a lag later than the second took."""
    device = Device()
    lag = measure_lag(lambda: device.enqueue("same", 0.03),
                      lambda done: done.wait(WAIT_S), reps=3)
    assert lag == pytest.approx(WAKE_S, abs=0.008)
    assert len(device.ran) == 6 and device.most_enqueued == 2


def test_a_scorer_that_names_no_lag_is_still_launched_behind_the_run(rig):
    """``Dispatch.lag`` 0 (a scorer that did not measure it): nothing
    breaks; the runs are launched behind the run in flight, aimed at its
    return until a program has been read queued behind another, and a
    reading lies between a program and a program with its wake-up."""
    mb, scorer, device = rig
    scorer.lag = 0.0
    teach(mb)
    for i in range(3):
        assert pair(mb, f"A{i}", f"B{i}") == (
            ("answer", f"A{i}"), ("answer", f"B{i}"))
    recs = [r for r in records(mb).values() if not r["inline"]][:3]
    assert all(behind_a_run(r) for r in recs)
    est = stats(mb)["launch_run_ms"]["1"]
    assert PROGRAM_S * 1e3 - 8 <= est <= (PROGRAM_S + WAKE_S) * 1e3 + 8


def test_the_new_counters_reach_the_registry_and_the_catalog():
    import os

    from predictionio_tpu import analysis
    from predictionio_tpu.obs import bridges
    from predictionio_tpu.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    bridges.bridge_batcher(reg, lambda: {
        "batches": 9, "ahead_batches": 4, "ahead_missed": 2})
    text = reg.render_prometheus()
    assert "pio_batcher_ahead_batches_total 4" in text
    assert "pio_batcher_ahead_missed_total 2" in text
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for doc in ("docs/observability.md", "docs/operations.md"):
        with open(os.path.join(root, doc)) as f:
            body = f.read()
        assert "ahead_batches" in body and "ahead_missed" in body
    report = analysis.run(root, analyzers=["metrics"])
    assert [f for f in report.findings
            if f.symbol.startswith("pio_batcher")] == []
