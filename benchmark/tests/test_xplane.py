"""The trace reduction, on a trace recorded on the chip (PR 23: a 3.4 s
slice of ``wgde-d128.serve-steady`` at 150 req/s, one TPU v5e chip)."""

import os

import pytest

from pio_bench import xplane

TRACE = os.path.join(os.path.dirname(__file__), "data",
                     "wgde-d128.serve-steady.slice.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return xplane.load(TRACE)


def test_the_device_plane_and_its_lines_are_found(planes):
    dev = planes["/device:TPU:0"]
    assert len(dev[xplane.MODULES_LINE]) == 12
    assert len(dev[xplane.OPS_LINE]) == 108


def test_busy_time_is_the_union_of_the_op_intervals(planes):
    red = xplane.reduce_planes(planes, 3.405660254)
    assert red["devices"] == 1
    assert red["busy_s"] == pytest.approx(2.57668892, rel=1e-9)
    # twelve executions of the score programs, three rungs
    mods = red["modules"]
    assert sum(m["count"] for m in mods.values()) == 12
    assert all(n.startswith("jit_fn(") for n in mods)
    assert sum(m["seconds"] for m in mods.values()) == pytest.approx(
        2.5766, rel=1e-3)
    # busy never exceeds the slice, and the largest op is the Pallas call
    assert red["busy_s"] < red["window_s"]
    assert "custom-call" in red["top_ops"][0][0]
    assert red["top_ops"][0][1] == pytest.approx(1.5701, rel=1e-3)


def test_idle_gaps_are_named_by_the_host(planes):
    red = xplane.reduce_planes(planes, 3.405660254)
    assert red["gap_count"] >= 11  # between twelve dispatches
    name, seconds = red["idle_gaps"][0]
    assert "shard_args" in name and 0.02 < seconds < 0.04


def test_union_merges_overlapping_intervals():
    total, merged = xplane.union_seconds([(0, 10), (5, 10), (30, 5)])
    assert total == 20 and merged == [[0, 15], [30, 35]]


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce_planes({"/host:CPU": {"python3": [("x", 0.0, 1.0)]}}, 1.0)
