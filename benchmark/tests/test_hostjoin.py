"""The host join (ISSUE 37): on planes written by hand, where every number
can be checked on paper, and on the slice PR 25 recorded on the chip."""

import os

import pytest

from pio_bench import hostjoin

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "wgde-d128.serve-steady.pio-spans.xplane.pb")
MS = 1e6  # ns


def span(name, start_ms, end_ms, **stats):
    return (name, start_ms * MS, end_ms * MS, stats)


@pytest.fixture()
def planes():
    """Two requests and three device programs on one clock, ms:

    request a  |1 ............................ 14|
    dispatch 1      |3 dc ......... 9|                (launch 3-4)
    program            |4.5 .... 8|
    request b                         |20 ............ 40|
    dispatch 2, two chunks of one seq   |22 dc 27| |28 dc 33|
    programs                              |23 26|    |29 32|
    a program under no span                              |50 51|

    So: device busy 3.5 + 3 + 3 + 1; between the first op (4.5) and the last
    (51) request a holds the idle device 8-14, request b 20-23, 26-29 and
    32-40, nobody 14-20 and 40-50.
    """
    return {
        "modules": [(4.5 * MS, 8 * MS, "jit_fn(1)"),
                    (23 * MS, 26 * MS, "jit_fn(1)"),
                    (29 * MS, 32 * MS, "jit_fn(1)"),
                    (50 * MS, 51 * MS, "jit_other(2)")],
        "ops": [(4.5 * MS, 8 * MS), (23 * MS, 26 * MS), (29 * MS, 32 * MS),
                (50 * MS, 51 * MS)],
        "spans": [
            span("pio_req.parse", 1, 1.5),
            span("pio_req.handle", 1.5, 14, id="a"),
            span("pio.h2d", 2, 3, seq=1, rung=1),
            span("pio.device_compute", 3, 9, seq=1, rung=1),
            span("pio.launch", 3, 4, seq=1, rung=1),
            span("pio.d2h", 9, 10, seq=1, rung=1),
            span("pio.serialize", 13, 14),
            span("pio_req.handle", 20, 40, id="b"),
            span("pio.device_compute", 22, 27, seq=2, rung=8),
            span("pio.launch", 22, 22.5, seq=2, rung=8),
            span("pio.device_compute", 28, 33, seq=2, rung=8),
            span("pio.launch", 28, 28.5, seq=2, rung=8),
            span("pio.resolve", 33, 34, seq=2),
        ],
    }


def test_each_program_joins_the_span_it_overlaps_and_one_joins_none(planes):
    got = hostjoin.join(planes)
    assert got["unjoined_modules"] == 1 and got["spans_without_program"] == 0
    assert got["contained"] == 1.0 and got["clock_shift_ns"] == 0.0
    assert [(d["seq"], d["rung"]) for d in got["dispatches"]] == [
        (1, 1), (2, 8), (2, 8)]  # two spans of one seq stay two dispatches
    assert [(d["launch_ms"], d["device_ms"], d["wake_ms"])
            for d in got["dispatches"]] == [
        (1.5, 3.5, 1.0), (1.0, 3.0, 1.0), (1.0, 3.0, 1.0)]
    for d in got["dispatches"]:
        assert d["launch_ms"] + d["device_ms"] + d["wake_ms"] == pytest.approx(
            (d["span"][1] - d["span"][0]) / MS)


def test_the_slice_is_busy_held_or_empty_and_nothing_else(planes):
    part = hostjoin.partition(planes, hostjoin.join(planes))
    assert part["slice_s"] == pytest.approx(46.5e-3)
    assert part["busy_s"] == pytest.approx(10.5e-3)
    assert part["held_s"] == pytest.approx((6 + 3 + 3 + 8) * 1e-3)
    assert part["empty_s"] == pytest.approx((6 + 10) * 1e-3)
    assert part["busy_s"] + part["held_s"] + part["empty_s"] == pytest.approx(
        part["slice_s"])
    held = {k: round(v * 1e3, 6) for k, v in part["held_by"].items() if v}
    assert held == {
        # request a: the wake 8-9, the readback 9-10, nothing 10-13,
        # the answer's write 13-14
        "device_compute after the last op": 1.0 + 1.0 + 1.0,
        "d2h": 1.0, "serialize": 1.0,
        # request b: 20-22 and 27-28 under no stage, the launches 22-22.5 and
        # 28-28.5, then up to each first op; resolve 33-34; 34-40 nothing
        "launch": 1.0, "device_compute before the first op": 1.0,
        "resolve": 1.0, "in hand under no stage": 3.0 + 2.0 + 1.0 + 6.0,
    }
    assert sum(held.values()) == pytest.approx(part["held_s"] * 1e3)


def test_a_device_clock_that_reads_early_is_shifted_by_the_least_that_is_causal(
        planes):
    early = dict(planes)
    early["modules"] = [(s - 2 * MS, e - 2 * MS, n)
                        for s, e, n in planes["modules"]]
    early["ops"] = [(s - 2 * MS, e - 2 * MS) for s, e in planes["ops"]]
    got = hostjoin.join(early)
    # dispatch 2's programs start 1 ms after their launch does: as recorded
    # they lie 1 ms BEFORE it, so 1 ms is the least shift (dispatch 1's
    # would allow 0.5); 2 would be the truth, which no host span shows
    assert got["clock_shift_ns"] == 1.0 * MS
    assert got["contained"] == 0.0  # each starts before its span
    assert got["contained_shifted"] == 1.0
    assert [d["device_ms"] for d in got["dispatches"]] == [3.5, 3.0, 3.0]
    assert [d["launch_ms"] + d["wake_ms"] for d in got["dispatches"]] == [
        2.5, 2.0, 2.0]  # the sum does not depend on the clock
    assert [d["launch_ms"] for d in got["dispatches"]] == [0.5, 0.0, 0.0]
    # the runtime's own enqueue event is the tighter anchor where there is one
    early["runtime"] = [(hostjoin.ENQUEUE, 3.8 * MS, 3.9 * MS),
                        (hostjoin.NOTICED, 8.4 * MS, 8.6 * MS)]
    got = hostjoin.join(early)
    assert got["clock_shift_ns"] == pytest.approx(1.3 * MS)
    assert got["shift_bounds_ns"] == pytest.approx((1.3 * MS, 2.6 * MS))


def test_a_program_without_presence_spans_reads_no_shares(planes):
    parent = dict(planes, spans=[s for s in planes["spans"]
                                 if not s[0].startswith("pio_req.")
                                 and s[0] != "pio.launch"])
    joined = hostjoin.join(parent)
    assert [d["launch_ms"] for d in joined["dispatches"]] == [1.5, 1.0, 1.0]
    part = hostjoin.partition(parent, joined)
    assert part["present"] is False and part["held_s"] == 0.0
    hostjoin._memo["somewhere"] = {**joined, **part}
    ctx = {"device_trace": {"trace_dir": "somewhere"}}
    try:
        assert hostjoin.idle_share(ctx, "held_s") is None
        assert hostjoin.dispatch_median(ctx, "device_ms") == 3.0
        assert hostjoin.dispatch_median(
            {"device_trace": {}}, "device_ms") is None
    finally:
        hostjoin._memo.clear()


def test_front_readers_join_the_client_to_the_servers_trace():
    ctx = {
        "traces": [
            {"requestId": "bench-0", "status": 200, "wallMs": 9.0,
             "meta": {"parse_ms": 0.2, "handback_ms": 0.1}},
            {"requestId": "bench-1", "status": 200, "wallMs": 5.0,
             "meta": {"parse_ms": 0.4}},  # ran inline: no hand-back
            {"requestId": "bench-2", "status": 503, "wallMs": 0.1,
             "meta": {"parse_ms": 0.3}},
            {"requestId": "bench-3", "status": 200, "wallMs": 5.0},  # parent
        ],
        "good": [{"i": 0, "sent": 1.000, "done": 1.010},
                 {"i": 1, "sent": 2.000, "done": 2.006},
                 {"i": 3, "sent": 3.000, "done": 3.006},
                 {"i": 4, "sent": None, "done": 4.0}],
    }
    assert hostjoin.meta_values(ctx, "parse_ms") == {
        "bench-0": 0.2, "bench-1": 0.4}
    assert hostjoin.meta_values(ctx, "handback_ms") == {"bench-0": 0.1}
    assert hostjoin.unseen_ms(ctx) == pytest.approx([0.8, 0.6])


# -- the slice recorded on the chip (PR 25: 255 ms dispatches, no launch span,
#    no request presence: what a parent of ISSUE 37 writes) ----------------------


@pytest.fixture(scope="module")
def chip():
    planes = hostjoin.load_planes(TRACE)
    return planes, hostjoin.join(planes)


def test_on_the_chip_every_program_of_a_whole_dispatch_lands_in_one_span(chip):
    planes, got = chip
    assert len(planes["modules"]) == 12
    # the slice opens on the tail of one program and closes on the head of
    # another, whose spans were open at either end and so are not in it
    assert got["unjoined_modules"] == 2
    assert len(got["dispatches"]) == 10 and got["spans_without_program"] == 0
    assert [d["seq"] for d in got["dispatches"]] == list(range(52, 62))
    for d in got["dispatches"]:
        assert d["launch_ms"] + d["device_ms"] + d["wake_ms"] == pytest.approx(
            (d["span"][1] - d["span"][0]) / MS, abs=1e-9)
        assert d["launch_ms"] >= 0 and d["wake_ms"] >= 0
        assert 244 < d["device_ms"] < 258


def test_on_the_chip_the_device_clock_read_early_and_the_shift_repairs_it(chip):
    _, got = chip
    # as recorded NO program lies inside the call that launched it
    assert got["contained"] == 0.0 and got["contained_shifted"] == 1.0
    lo, hi = got["shift_bounds_ns"]
    assert lo <= got["clock_shift_ns"] <= hi
    assert got["clock_shift_ns"] == pytest.approx(1.413 * MS, abs=0.001 * MS)


def test_on_the_chip_a_trace_without_presence_is_busy_or_unsplit(chip):
    planes, got = chip
    part = hostjoin.partition(planes, got)
    assert part["present"] is False
    assert part["busy_s"] / part["slice_s"] == pytest.approx(0.9656, abs=1e-4)
    assert part["busy_s"] + part["empty_s"] == pytest.approx(part["slice_s"])


# -- a slice with ISSUE 37's own spans (my chip run, PR 37: the ALS cell at
#    58.5 req/s, 2.96 s between the first and the last device op) -------------------

PRESENCE = os.path.join(os.path.dirname(__file__), "data",
                        "wgde-d128.serve-steady.pr37-presence.xplane.pb")


@pytest.fixture(scope="module")
def presence():
    planes = hostjoin.load_planes(PRESENCE)
    joined = hostjoin.join(planes)
    return planes, joined, hostjoin.partition(planes, joined)


def test_with_presence_the_slice_splits_three_ways_and_sums_to_itself(
        presence):
    planes, got, part = presence
    assert len(got["dispatches"]) == 159 and got["unjoined_modules"] == 1
    assert {d["rung"] for d in got["dispatches"]} == {1, 8}
    # the runtime's enqueue and completion events pin the shift to a
    # window a third of a millisecond wide, and every dispatch fits it
    lo, hi = got["shift_bounds_ns"]
    assert got["contained"] == 0.0 and got["contained_shifted"] == 1.0
    assert got["clock_shift_ns"] == lo == pytest.approx(1.2972 * MS, abs=100)
    assert hi == pytest.approx(1.6197 * MS, abs=100)
    assert sum(n == hostjoin.ENQUEUE for n, _, _ in planes["runtime"]) >= 159
    for d in got["dispatches"]:
        assert d["launch_ms"] > 0 and d["wake_ms"] > 0
    total = part["slice_s"]
    assert part["present"] is True
    assert part["busy_s"] + part["held_s"] + part["empty_s"] == pytest.approx(
        total, rel=1e-9)
    assert 100 * part["busy_s"] / total == pytest.approx(23.88, abs=0.01)
    assert 100 * part["held_s"] / total == pytest.approx(22.03, abs=0.01)
    assert sum(part["held_by"].values()) == pytest.approx(part["held_s"])
    # every launch span lies in a device_compute span of its seq and rung
    dc = {(st["seq"], st["rung"]): (s, e) for n, s, e, st in planes["spans"]
          if n == "pio.device_compute"}
    launches = [(s, e, st) for n, s, e, st in planes["spans"]
                if n == "pio.launch"]
    assert len(launches) >= 159
    orphans = [st for _, _, st in launches if (st["seq"], st["rung"]) not in dc]
    # the session stopped inside the last dispatch: its launch had ended and
    # is in the trace, its device_compute span was still open and is not
    # (nor is its program joined: the one module under no span)
    assert [st["seq"] for st in orphans] == [
        max(st["seq"] for _, _, st in launches)]
    for s, e, st in launches:
        if st not in orphans:
            a, b = dc[(st["seq"], st["rung"])]
            assert a <= s and e <= b
