import json
import os

import numpy as np
import pytest

from pio_bench import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["serve-steady", "serve-burst"])
def test_equal_seeds_give_the_same_schedule(name):
    a = schedule.build(mix(name), 10_000, 120.0, 10.0, 2**31 + 5)
    b = schedule.build(mix(name), 10_000, 120.0, 10.0, 2**31 + 5)
    for key in ("due_s", "user", "num"):
        assert np.array_equal(a[key], b[key])


@pytest.mark.parametrize("name", ["serve-steady", "serve-burst"])
def test_other_seeds_rearrange_the_same_work(name):
    a = schedule.build(mix(name), 10_000, 120.0, 10.0, 7)
    b = schedule.build(mix(name), 10_000, 120.0, 10.0, 8)
    assert not np.array_equal(a["due_s"], b["due_s"])
    assert not np.array_equal(a["user"], b["user"])
    # the same amount of work: request count and the multiset of lengths
    assert len(a["due_s"]) == len(b["due_s"]) == 1200
    assert np.array_equal(np.sort(a["num"]), np.sort(b["num"]))
    assert (np.diff(a["due_s"]) >= 0).all() and a["due_s"].max() < 10.0


def test_bursts_put_48_requests_inside_5_ms():
    s = schedule.build(mix("serve-burst"), 10_000, 120.0, 10.0, 11)
    due = s["due_s"]
    # 600 of 1200 requests ride in floor(600/48)=12 or 13 bursts
    in_burst = sum(
        1 for i in range(len(due))
        if np.searchsorted(due, due[i] + 0.005) - i >= 24)
    assert in_burst >= 200
    steady = schedule.build(mix("serve-steady"), 10_000, 120.0, 10.0, 11)
    d = steady["due_s"]
    assert max(np.searchsorted(d, d + 0.005) - np.arange(len(d))) < 12


def test_users_are_skewed_and_in_range():
    s = schedule.build(mix("serve-steady"), 5_000, 2000.0, 10.0, 3)
    assert s["user"].min() >= 0 and s["user"].max() < 5_000
    counts = np.bincount(s["user"], minlength=5_000)
    assert counts.max() > 5 * counts.mean()
