"""The plain float64 reference for top-k answers, and the comparison that
decides ``correct`` for a serving cell.

NumPy only; takes the factors the benchmark itself drew from the seed and
nothing the program made.  An answer is judged by what it SAYS, never by
which items it names: with seeded random factors the k-th and (k+1)-th
scores differ by rounding on some seed, so item identity is not compared.

Three numbers per answer row, each a multiple of that row's tolerance
``tol * |u| * max|v|``:

* ``score``  — the largest |returned score - u.v| over the returned items;
* ``beat``   — how far the best UNRETURNED item's true score lies above the
  smallest true score among the returned ones;
* ``order``  — the largest increase between consecutive returned scores.

and two structural facts: no item twice, the asked number of items.
"""

from __future__ import annotations

import time

import numpy as np

# items per block of the sweep: an (8192 x rows) f32 score tile stays in cache
ITEM_BLOCK = 8_192


def max_row_norm(V: np.ndarray) -> float:
    """max |v| over the item rows — the scale of every tolerance (float32
    squares summed in float64: a scale needs no more)."""
    worst = 0.0
    for lo in range(0, V.shape[0], ITEM_BLOCK):
        blk = V[lo:lo + ITEM_BLOCK]
        worst = max(worst, float(np.einsum(
            "ij,ij->i", blk, blk, dtype=np.float64).max()))
    return float(np.sqrt(worst))


# An f32 dot product of ``rank`` terms errs by at most rank * 2^-24 * |u||v|
# (7.6e-6 of |u||v| at rank 128).  The sweep that LOCATES the best unreturned
# item runs in f32 (sgemm is ~20x dgemm on a skinny 128-deep product, and every
# run pays this); every item within SWEEP_MARGIN * |u| * max|v| of the sweep's
# best — five times twice that error — is then rescored in float64, so the
# number compared is the float64 one.
SWEEP_MARGIN = 4e-5


def true_scores(U: np.ndarray, V: np.ndarray, users, items_per_row,
                vmax: float, exact_sweep: bool = False):
    """For each row r: the float64 scores of ``items_per_row[r]``, the best
    float64 score among all OTHER items, and |u_r|.  ``exact_sweep`` runs
    the whole sweep in float64 (slow; the tests hold the two together)."""
    users = np.asarray(users, np.int64)
    n_rows = len(users)
    Ug = U[users].astype(np.float64)  # (R, d)
    unorm = np.linalg.norm(Ug, axis=1)
    per_row = [V[np.asarray(x, np.int64)].astype(np.float64) @ Ug[r]
               for r, x in enumerate(items_per_row)]
    lens = np.array([len(x) for x in items_per_row], np.int64)
    flat_item = (np.concatenate([np.asarray(x, np.int64)
                                 for x in items_per_row])
                 if lens.sum() else np.zeros(0, np.int64))
    flat_row = np.repeat(np.arange(n_rows), lens)
    order = np.argsort(flat_item, kind="stable")
    s_item, s_row = flat_item[order], flat_row[order]
    sweep_t = np.float64 if exact_sweep else np.float32
    Us = np.ascontiguousarray(Ug.T.astype(sweep_t))  # (d, R)
    margin = (0.0 if exact_sweep else SWEEP_MARGIN) * unorm * vmax
    best = np.full(n_rows, -np.inf)
    cand_item, cand_row, cand_s = [], [], []
    n_items = V.shape[0]
    t_gemm = 0.0
    for lo in range(0, n_items, ITEM_BLOCK):
        hi = min(lo + ITEM_BLOCK, n_items)
        t0 = time.perf_counter()
        S = V[lo:hi].astype(sweep_t, copy=False) @ Us  # (blk, R)
        t_gemm += time.perf_counter() - t0
        a, b = np.searchsorted(s_item, [lo, hi])
        if b > a:
            S[s_item[a:b] - lo, s_row[a:b]] = -np.inf
        colmax = S.max(axis=0)
        np.maximum(best, colmax, out=best)
        # only a (block, row) pair whose block maximum reaches the running
        # best can hold a candidate: a handful of columns per block
        for r in np.flatnonzero(colmax >= best - margin):
            ii = np.flatnonzero(S[:, r] >= best[r] - margin[r])
            cand_item.append(ii + lo)
            cand_row.append(np.full(len(ii), r, np.int64))
            cand_s.append(S[ii, r].astype(np.float64))
    cand_item = np.concatenate(cand_item)
    cand_row = np.concatenate(cand_row)
    keep = np.concatenate(cand_s) >= (best - margin)[cand_row]
    cand_item, cand_row = cand_item[keep], cand_row[keep]
    exact = np.einsum("cd,cd->c", V[cand_item].astype(np.float64),
                      Ug[cand_row])
    rest_max = np.full(n_rows, -np.inf)
    np.maximum.at(rest_max, cand_row, exact)
    return per_row, rest_max, unorm, t_gemm


def check_topk(U, V, users, idx, vals, want_len, tol: float, vmax=None,
               exact_sweep: bool = False):
    """Judge answer rows against float64.  ``idx[r]``/``vals[r]`` are the
    returned item indices and scores of row r, ``want_len[r]`` how many
    were asked for.  Returns a dict with the worst of each number over its
    tolerance, the count of structurally bad rows, and ``ok``."""
    n_items = V.shape[0]
    vmax = max_row_norm(V) if vmax is None else vmax
    structural = []
    clean_idx = []
    for r, got in enumerate(idx):
        got = np.asarray(got, np.int64)
        bad = None
        if len(got) != int(want_len[r]) or len(vals[r]) != len(got):
            bad = f"row {r}: {len(got)} items, {int(want_len[r])} asked"
        elif len(np.unique(got)) != len(got):
            bad = f"row {r}: an item twice"
        elif len(got) and (got.min() < 0 or got.max() >= n_items):
            bad = f"row {r}: an item outside the catalog"
        if bad:
            structural.append(bad)
            got = np.zeros(0, np.int64)
        clean_idx.append(got)
    t0 = time.perf_counter()
    per_row, rest_max, unorm, t_gemm = true_scores(
        U, V, users, clean_idx, vmax, exact_sweep)
    t_sweep = time.perf_counter() - t0
    worst = {"score": -np.inf, "beat": -np.inf, "order": -np.inf}
    worst_row = {"score": -1, "beat": -1, "order": -1}
    for r, got in enumerate(clean_idx):
        if not len(got):
            continue
        row_tol = tol * float(unorm[r]) * vmax
        v = np.asarray(vals[r], np.float64)
        nums = {
            "score": float(np.abs(v - per_row[r]).max()),
            "beat": float(rest_max[r] - per_row[r].min()),
            "order": float(np.max(np.diff(v), initial=-np.inf)),
        }
        for name, x in nums.items():
            if x / row_tol > worst[name]:
                worst[name], worst_row[name] = x / row_tol, r
    ok = not structural and all(x <= 1.0 for x in worst.values())
    return {
        "rows": len(idx), "ok": ok, "structural": structural[:5],
        "n_structural": len(structural), "tolerance": tol,
        "score_over_tol": worst["score"], "beat_over_tol": worst["beat"],
        "order_over_tol": worst["order"], "worst_rows": worst_row,
        "seconds": {"sweep": t_sweep, "of_which_gemm": t_gemm},
    }
