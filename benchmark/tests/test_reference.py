"""The answer check: passes what is right, fails what a lower precision or
a broken top-k would return."""

import ml_dtypes
import numpy as np
import pytest

from pio_bench import reference, seeded

TOL = 1e-6  # the shipped configuration's score_tolerance
N_U, N_I, RANK, K = 400, 20_000, 128, 100


@pytest.fixture(scope="module")
def factors():
    return (seeded.make_factors(5, seeded.STREAM_USER_FACTORS, N_U, RANK),
            seeded.make_factors(5, seeded.STREAM_ITEM_FACTORS, N_I, RANK))


def exact_answers(U, V, users, k=K):
    S = U[users].astype(np.float64) @ V.astype(np.float64).T
    idx = np.argsort(-S, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(S, idx, axis=1)


def judge(U, V, users, idx, vals, k=K):
    return reference.check_topk(U, V, users, idx, vals, [k] * len(users), TOL)


def test_exact_float64_answers_pass(factors):
    U, V = factors
    users = np.arange(32)
    res = judge(U, V, users, *exact_answers(U, V, users))
    assert res["ok"] and res["score_over_tol"] < 1e-6


def test_f32_rounded_scores_pass(factors):
    U, V = factors
    users = np.arange(32)
    idx, vals = exact_answers(U, V, users)
    res = judge(U, V, users, idx, vals.astype(np.float32))
    assert res["ok"] and res["score_over_tol"] < 0.1


def test_a_tie_swap_inside_the_tolerance_passes(factors):
    U, V = factors
    V = V.copy()
    users = np.arange(8)
    idx, _ = exact_answers(U, V, users, k=K + 1)
    # make item k+1 of row 0 an exact tie with item k, then return either
    V[idx[0, K]] = V[idx[0, K - 1]]
    idx, vals = exact_answers(U, V, users)
    swapped = idx.copy()
    full, _ = exact_answers(U, V, users, k=K + 1)
    swapped[0, K - 1] = full[0, K]
    assert swapped[0, K - 1] != idx[0, K - 1]
    assert judge(U, V, users, swapped, vals)["ok"]


def bf16(x):
    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def lower_precision_scores(U, V, users, passes):
    """What an MXU returns for f32 operands at one bf16 pass (DEFAULT) and
    at three (HIGH): operands split into bf16 terms, products summed f32."""
    u, v = U[users], V
    u0, v0 = bf16(u), bf16(v)
    s = u0 @ v0.T
    if passes == 3:
        u1, v1 = bf16(u - u0), bf16(v - v0)
        s = s + u0 @ v1.T + u1 @ v0.T
    return s


@pytest.mark.parametrize("passes", [3, 1])
def test_the_lower_precision_control_fails(factors, passes):
    """The control at this test's size: Precision.HIGH (three bf16 passes),
    the nearest precision under the f32-at-HIGHEST the configuration states,
    and one bf16 pass.  On the chip at the cell's own size they read 5.7-6.9
    and 660-870 tolerances (findings/)."""
    U, V = factors
    users = np.arange(256)
    S = lower_precision_scores(U, V, users, passes)
    idx = np.argsort(-S, axis=1, kind="stable")[:, :K]
    res = judge(U, V, users, idx, np.take_along_axis(S, idx, axis=1))
    assert not res["ok"]
    assert res["score_over_tol"] > (1 if passes == 3 else 100)


def test_scores_rounded_through_bf16_fail(factors):
    U, V = factors
    users = np.arange(8)
    idx, vals = exact_answers(U, V, users)
    assert not judge(U, V, users, idx, bf16(vals.astype(np.float32)))["ok"]


def test_a_duplicated_item_fails(factors):
    U, V = factors
    users = np.arange(8)
    idx, vals = exact_answers(U, V, users)
    idx[3, 10] = idx[3, 9]
    res = judge(U, V, users, idx, vals)
    assert not res["ok"] and res["n_structural"] == 1


def test_a_missing_best_item_fails(factors):
    U, V = factors
    users = np.arange(8)
    idx, vals = exact_answers(U, V, users, k=K + 1)
    res = judge(U, V, users, idx[:, 1:], vals[:, 1:])  # the best left out
    assert not res["ok"] and res["beat_over_tol"] > 1


def test_a_short_answer_fails(factors):
    U, V = factors
    users = np.arange(4)
    idx, vals = exact_answers(U, V, users)
    res = judge(U, V, users, [r[:50] for r in idx], [r[:50] for r in vals])
    assert not res["ok"] and res["n_structural"] == 4


def test_increasing_scores_fail(factors):
    U, V = factors
    users = np.arange(4)
    idx, vals = exact_answers(U, V, users)
    idx[:, [0, 50]] = idx[:, [50, 0]]
    vals[:, [0, 50]] = vals[:, [50, 0]]
    res = judge(U, V, users, idx, vals)
    assert not res["ok"] and res["order_over_tol"] > 1


def test_the_f32_sweep_finds_what_a_float64_sweep_finds(factors):
    U, V = factors
    users = np.arange(64)
    idx, vals = exact_answers(U, V, users, k=K + 1)
    # the best left out, and sound answers: both sweeps must read the same
    for i, v in ((idx[:, 1:], vals[:, 1:]), (idx[:, :K], vals[:, :K])):
        fast = judge(U, V, users, i, v)
        slow = reference.check_topk(U, V, users, i, v, [K] * 64, TOL,
                                    exact_sweep=True)
        for key in ("score_over_tol", "beat_over_tol", "order_over_tol"):
            assert fast[key] == pytest.approx(slow[key], rel=1e-9, abs=1e-12)
