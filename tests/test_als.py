"""ALS correctness over the 8-device virtual mesh.

Parity model: the recommendation templates' use of MLlib ALS (explicit) and
trainImplicit (SURVEY.md §2.6) — asserted here by reconstruction quality and
ranking behavior on synthetic low-rank data, not by implementation details.
"""

import numpy as np
import pytest

from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import als as als_mod
from predictionio_tpu.models.als import (
    ALSConfig,
    ALSModel,
    ALSScorer,
    rmse,
    train_als,
)
from predictionio_tpu.parallel.mesh import MeshContext


def synthetic_explicit(n_users=60, n_items=40, rank=3, density=0.5, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.normal(size=(n_users, rank)) / np.sqrt(rank)
    V = rng.normal(size=(n_items, rank)) / np.sqrt(rank)
    full = U @ V.T
    mask = rng.random((n_users, n_items)) < density
    users, items = np.nonzero(mask)
    ratings = full[users, items].astype(np.float32)
    return Interactions(
        user=users.astype(np.int32),
        item=items.astype(np.int32),
        rating=ratings,
        t=np.zeros(len(users)),
        user_map=BiMap.string_int(f"u{i}" for i in range(n_users)),
        item_map=BiMap.string_int(f"i{i}" for i in range(n_items)),
    )


@pytest.fixture(scope="module")
def ctx():
    return MeshContext.create()


class TestExplicitALS:
    def test_reconstructs_low_rank_matrix(self, ctx):
        inter = synthetic_explicit()
        model = train_als(ctx, inter, ALSConfig(rank=3, iterations=12, reg=0.001))
        err = rmse(model, inter)
        assert err < 0.05, f"rmse {err} too high for exact low-rank data"

    def test_factor_shapes_trimmed(self, ctx):
        inter = synthetic_explicit(n_users=13, n_items=7)  # awkward sizes
        model = train_als(ctx, inter, ALSConfig(rank=4, iterations=3))
        assert model.user_factors.shape == (13, 4)
        assert model.item_factors.shape == (7, 4)

    def test_deterministic_given_seed(self, ctx):
        inter = synthetic_explicit(n_users=20, n_items=15)
        m1 = train_als(ctx, inter, ALSConfig(rank=3, iterations=3, seed=5))
        m2 = train_als(ctx, inter, ALSConfig(rank=3, iterations=3, seed=5))
        np.testing.assert_allclose(m1.user_factors, m2.user_factors, rtol=1e-4)

    def test_bf16_compute_converges(self, ctx):
        inter = synthetic_explicit()
        model = train_als(
            ctx, inter,
            ALSConfig(rank=3, iterations=12, reg=0.001, compute_dtype="bf16"),
        )
        err = rmse(model, inter)
        assert err < 0.08, f"bf16 rmse {err} too high"

    def test_regularization_shrinks_factors(self, ctx):
        inter = synthetic_explicit(n_users=20, n_items=15)
        lo = train_als(ctx, inter, ALSConfig(rank=3, iterations=5, reg=0.001))
        hi = train_als(ctx, inter, ALSConfig(rank=3, iterations=5, reg=10.0))
        assert np.linalg.norm(hi.user_factors) < np.linalg.norm(lo.user_factors)


class TestLoadRebalance:
    """Zipf-skewed catalogs must not pad every shard to the hot block's size.

    VERDICT r2 item 2: range-blocking with contiguous hot ids concentrates
    ratings in one shard; `_balance_permutation` deals entities round-robin
    by popularity so per-shard counts stay near the mean.
    """

    @staticmethod
    def _zipf_ids(rng, n, size, s=1.1, q=20):
        # Zipf-Mandelbrot: the q shift flattens the head the way real
        # catalogs look (ML-25M's hottest movie holds ~0.3% of ratings,
        # not the ~10% a pure Zipf head would)
        ranks = np.arange(1, n + 1, dtype=np.float64)
        p = (ranks + q) ** -s
        p /= p.sum()
        return rng.choice(n, size=size, p=p).astype(np.int64)

    def test_permutation_is_bijection_and_balances(self, ctx):
        from predictionio_tpu.models.als import _balance_permutation

        rng = np.random.default_rng(0)
        n_shards = ctx.axis_size("data")
        n_items, n_ratings = 400, 20_000
        n_pad = ((n_items + n_shards - 1) // n_shards) * n_shards
        items = self._zipf_ids(rng, n_items, n_ratings)
        perm = _balance_permutation(items, n_pad, n_shards)
        assert sorted(perm) == list(range(n_pad))  # bijection
        per_shard = n_pad // n_shards
        shard_counts = np.bincount(perm[items] // per_shard, minlength=n_shards)
        mean = n_ratings / n_shards
        assert shard_counts.max() <= 1.15 * mean, shard_counts

    def test_blocked_padding_shrinks_under_rebalance(self, ctx):
        from predictionio_tpu.models.als import _balance_permutation, _make_blocks

        rng = np.random.default_rng(1)
        n_shards = ctx.axis_size("data")
        n_items, n_ratings = 800, 40_000
        n_pad = ((n_items + n_shards - 1) // n_shards) * n_shards
        items = self._zipf_ids(rng, n_items, n_ratings)
        users = rng.integers(0, 100, n_ratings).astype(np.int64)
        ratings = rng.uniform(1, 5, n_ratings).astype(np.float32)
        raw = _make_blocks(items, users, ratings, n_pad, n_shards)
        perm = _balance_permutation(items, n_pad, n_shards)
        balanced = _make_blocks(perm[items], users, ratings, n_pad, n_shards)
        # hot ids contiguous → raw padding near worst case; balanced within
        # ~15% of the ideal equal split
        assert balanced.length <= 1.15 * (n_ratings / n_shards)
        assert balanced.length < raw.length

    def test_model_invariant_under_rebalance(self, ctx):
        # factors come back in original id order: ranking quality matches
        # the unbalanced path on the same data
        inter = synthetic_explicit(n_users=40, n_items=30)
        cfg = dict(rank=3, iterations=10, reg=0.001)
        on = train_als(ctx, inter, ALSConfig(rebalance=True, **cfg))
        off = train_als(ctx, inter, ALSConfig(rebalance=False, **cfg))
        assert abs(rmse(on, inter) - rmse(off, inter)) < 0.02
        assert rmse(on, inter) < 0.05


def dense_reference_half_step(V, users, items, ratings, n_users, reg,
                              implicit=False, alpha=1.0):
    """Straight-from-the-paper dense solve for U given V (numpy, no jax)."""
    k = V.shape[1]
    U = np.zeros((n_users, k), np.float64)
    Vd = V.astype(np.float64)
    G = Vd.T @ Vd
    for u in range(n_users):
        sel = users == u
        Vi = Vd[items[sel]]
        r = ratings[sel].astype(np.float64)
        if implicit:
            # Hu-Koren-Volinsky: (G + Vi^T (C-I) Vi + reg I) x = Vi^T C 1
            C = alpha * r
            A = G + Vi.T @ (Vi * C[:, None]) + reg * np.eye(k)
            b = Vi.T @ (1.0 + C)
        else:
            # ALS-WR: (Vi^T Vi + reg*n_u I) x = Vi^T r
            A = Vi.T @ Vi + (reg * len(r) + 1e-6) * np.eye(k)
            b = Vi.T @ r
        U[u] = np.linalg.solve(A, b)
    return U


class TestNumericalEquivalence:
    """The sharded half-step equals the textbook dense solve exactly."""

    @pytest.mark.parametrize("implicit", [False, True])
    def test_half_step_matches_dense_reference(self, ctx, implicit):
        from predictionio_tpu.models import als as als_mod

        rng = np.random.default_rng(0)
        n_users, n_items, k = 16, 12, 3
        users = rng.integers(0, n_users, 80).astype(np.int64)
        items = rng.integers(0, n_items, 80).astype(np.int64)
        ratings = rng.uniform(1, 5, 80).astype(np.float32)
        V0 = rng.normal(size=(n_items, k)).astype(np.float32)

        inter = Interactions(
            user=users.astype(np.int32), item=items.astype(np.int32),
            rating=ratings, t=np.zeros(80),
            user_map=BiMap.string_int(f"u{i}" for i in range(n_users)),
            item_map=BiMap.string_int(f"i{i}" for i in range(n_items)),
        )
        cfg = ALSConfig(rank=k, iterations=1, reg=0.1,
                        implicit=implicit, alpha=2.0)
        # run ONE U-half-step through the sharded machinery by seeding V:
        # monkeypatch init so U starts anywhere and V starts at V0, then
        # compare the U produced by iteration 1's first half-step. We can
        # recover it because after a full step U depends only on V0.
        import jax

        n_shards = ctx.axis_size("data")
        n_users_pad = als_mod.pad_to_multiple(n_users, n_shards)
        n_items_pad = als_mod.pad_to_multiple(n_items, n_shards)
        ub = als_mod._make_blocks(users, items, ratings, n_users_pad, n_shards)
        V_pad = np.zeros((n_items_pad, k), np.float32)
        V_pad[:n_items] = V0
        from functools import partial
        from predictionio_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P
        import jax.numpy as jnp

        kernel = partial(
            als_mod._half_step_local, per_shard=ub.per_shard, rank=k,
            reg=cfg.reg, implicit=implicit, alpha=cfg.alpha,
        )
        solve = shard_map(
            kernel, mesh=ctx.mesh,
            in_specs=(P("data"), P("data"), P("data"), P("data"), P(), P()),
            out_specs=P("data", None),
        )
        gram = jnp.asarray(V_pad.T @ V_pad) if implicit else jnp.zeros((k, k))
        U_sharded = np.asarray(
            solve(
                jnp.asarray(ub.local), jnp.asarray(ub.other),
                jnp.asarray(ub.rating), jnp.asarray(ub.mask),
                jnp.asarray(V_pad), gram.astype(jnp.float32),
            )
        )[:n_users]
        U_ref = dense_reference_half_step(
            V0, users, items, ratings, n_users, cfg.reg,
            implicit=implicit, alpha=cfg.alpha,
        )
        # users with no ratings: sharded gives ~0 (eps ridge); exclude
        has = np.isin(np.arange(n_users), users)
        np.testing.assert_allclose(
            U_sharded[has], U_ref[has], rtol=2e-4, atol=2e-5
        )


class TestDenseSolver:
    """The scatter-free degree-bucketed solver (ALSConfig.solver='dense').

    Correctness is proven two ways: structurally (every rating lands in
    exactly one bucket slot) and numerically (one dense half-step equals
    the textbook normal-equation solve; full trains match the segment
    path within f32 reduction-order noise).
    """

    def _zipf_interactions(self, nu=90, ni=50, nr=3000, seed=3):
        rng = np.random.default_rng(seed)
        return Interactions(
            user=rng.integers(0, nu, nr).astype(np.int32),
            item=(rng.zipf(1.5, nr) % ni).astype(np.int32),
            rating=rng.uniform(1, 5, nr).astype(np.float32),
            t=np.zeros(nr),
            user_map=BiMap.string_int(f"u{i}" for i in range(nu)),
            item_map=BiMap.string_int(f"i{i}" for i in range(ni)),
        )

    def test_buckets_hold_every_rating_once_with_bounded_padding(self, ctx):
        from predictionio_tpu.models import als as als_mod

        inter = self._zipf_interactions()
        n_shards = ctx.axis_size("data")
        n_pad = als_mod.pad_to_multiple(inter.n_users, n_shards)
        perm = als_mod._degree_sort_permutation(
            inter.user.astype(np.int64), n_pad, n_shards
        )
        blk = perm[inter.user.astype(np.int64)]
        ub = als_mod._make_dense_blocks(
            blk, inter.item.astype(np.int64), inter.rating, n_pad, n_shards
        )
        # reconstruct the triple multiset from the bucket matrices
        got = []
        cursor = 0
        for b, width in enumerate(ub.widths):
            idx, rat, msk = ub.idx[b], ub.rat[b], ub.msk[b]
            n_b = idx.shape[1]
            for p in range(idx.shape[0]):
                rows, cols = np.nonzero(msk[p])
                ent = p * ub.per_shard + cursor + rows
                got += list(zip(ent, idx[p, rows, cols], rat[p, rows, cols]))
            cursor += n_b
        want = sorted(zip(blk, inter.item, inter.rating))
        assert sorted(got) == want
        # power-of-two bucket discipline bounds padding ≤ 2× + tail floor
        assert ub.padded_ratings <= 2 * len(inter.rating) + 8 * n_pad

    @pytest.mark.parametrize("implicit", [False, True])
    def test_dense_half_step_matches_dense_reference(self, ctx, implicit):
        from functools import partial

        import jax.numpy as jnp
        from predictionio_tpu.parallel.mesh import shard_map
        from jax.sharding import PartitionSpec as P

        from predictionio_tpu.models import als as als_mod

        rng = np.random.default_rng(0)
        n_users, n_items, k = 16, 12, 3
        users = rng.integers(0, n_users, 80).astype(np.int64)
        items = rng.integers(0, n_items, 80).astype(np.int64)
        ratings = rng.uniform(1, 5, 80).astype(np.float32)
        V0 = rng.normal(size=(n_items, k)).astype(np.float32)
        reg, alpha = 0.1, 2.0

        n_shards = ctx.axis_size("data")
        n_users_pad = als_mod.pad_to_multiple(n_users, n_shards)
        n_items_pad = als_mod.pad_to_multiple(n_items, n_shards)
        perm = als_mod._degree_sort_permutation(users, n_users_pad, n_shards)
        ub = als_mod._make_dense_blocks(
            perm[users], items, ratings, n_users_pad, n_shards
        )
        V_pad = np.zeros((n_items_pad, k), np.float32)
        V_pad[:n_items] = V0
        kernel = partial(
            als_mod._dense_half_step_local, n_buckets=len(ub.widths),
            rank=k, reg=reg, implicit=implicit, alpha=alpha,
        )
        nb = len(ub.widths)
        solve = shard_map(
            kernel, mesh=ctx.mesh,
            in_specs=tuple(P("data") for _ in range(3 * nb)) + (P(), P()),
            out_specs=P("data", None),
        )
        bufs = []
        for i in range(nb):
            bufs += [jnp.asarray(ub.idx[i]), jnp.asarray(ub.rat[i]),
                     jnp.asarray(ub.msk[i])]
        gram = jnp.asarray(V_pad.T @ V_pad) if implicit else jnp.zeros((k, k))
        U_blocked = np.asarray(
            solve(*bufs, jnp.asarray(V_pad), gram.astype(jnp.float32))
        )
        U_dense = U_blocked[perm[:n_users]]  # back to original id order
        U_ref = dense_reference_half_step(
            V0, users, items, ratings, n_users, reg,
            implicit=implicit, alpha=alpha,
        )
        has = np.isin(np.arange(n_users), users)
        np.testing.assert_allclose(
            U_dense[has], U_ref[has], rtol=2e-4, atol=2e-5
        )

    @pytest.mark.parametrize("implicit", [False, True])
    def test_dense_train_matches_segment_train(self, ctx, implicit):
        import dataclasses

        inter = self._zipf_interactions()
        cfg_s = ALSConfig(rank=4, iterations=3, seed=7, implicit=implicit,
                          solver="segment")
        cfg_d = dataclasses.replace(cfg_s, solver="dense")
        ms = train_als(ctx, inter, cfg_s)
        md = train_als(ctx, inter, cfg_d)
        # identical math, different f32 reduction order; agreement is at
        # prediction level (factors drift within conditioning amplification)
        np.testing.assert_allclose(
            ms.user_factors @ ms.item_factors.T,
            md.user_factors @ md.item_factors.T,
            rtol=5e-2, atol=5e-3,
        )

    def test_dense_model_invariant_under_rebalance(self, ctx):
        import dataclasses

        inter = self._zipf_interactions()
        cfg = ALSConfig(rank=4, iterations=3, seed=5, solver="dense")
        m_on = train_als(ctx, inter, dataclasses.replace(cfg, rebalance=True))
        m_off = train_als(ctx, inter, dataclasses.replace(cfg, rebalance=False))
        np.testing.assert_allclose(
            m_on.user_factors, m_off.user_factors, rtol=5e-2, atol=5e-3
        )

    def test_xla_cost_analysis_positive_and_scales_with_ratings(self, ctx):
        from predictionio_tpu.models.als import dense_step_cost_analysis

        small = self._zipf_interactions(nu=300, ni=120, nr=4_000)
        big = self._zipf_interactions(nu=300, ni=120, nr=16_000)
        cfg = ALSConfig(rank=4, solver="dense")
        ca_s = dense_step_cost_analysis(ctx, small, cfg)
        ca_b = dense_step_cost_analysis(ctx, big, cfg)
        assert ca_s["flops_per_iter_per_device"] > 0
        assert ca_s["bytes_per_iter_per_device"] > 0
        # 4x the ratings must cost materially more compiled work
        assert (
            ca_b["flops_per_iter_per_device"]
            > 2 * ca_s["flops_per_iter_per_device"]
        )


class TestBatchedSpdSolve:
    """The plain-ops Cholesky solve that replaced cho_factor/cho_solve:
    XLA:TPU's expansion of those returned wrong factors once the batch was
    spread over more than one chip (CHANGES.md PR 21), which no CPU mesh
    reproduces — so what is pinned here is that the replacement is a
    correct solve whose answer cannot depend on how the batch is split."""

    @pytest.mark.parametrize("k", [1, 4, 10, 32])
    def test_matches_float64_solve(self, k):
        import jax

        rng = np.random.default_rng(k)
        M = rng.standard_normal((257, k, k))
        A = M @ M.transpose(0, 2, 1) + 0.05 * np.eye(k)
        b = rng.standard_normal((257, k))
        x = np.asarray(jax.jit(als_mod._batched_spd_solve)(
            A.astype(np.float32), b.astype(np.float32)))
        ref = np.linalg.solve(A, b[..., None])[..., 0]
        cond = np.linalg.cond(A).max()
        assert x.dtype == np.float32 and x.shape == (257, k)
        assert np.abs(x - ref).max() <= 64 * 2.0**-24 * cond * np.abs(ref).max()

    def test_independent_of_batch_split(self):
        import jax

        rng = np.random.default_rng(0)
        M = rng.standard_normal((96, 10, 10)).astype(np.float32)
        A = M @ M.transpose(0, 2, 1) + np.eye(10, dtype=np.float32)
        b = rng.standard_normal((96, 10)).astype(np.float32)
        solve = jax.jit(als_mod._batched_spd_solve)
        whole = np.asarray(solve(A, b))
        parts = np.concatenate(
            [np.asarray(solve(A[s:s + 24], b[s:s + 24]))
             for s in range(0, 96, 24)]
        )
        np.testing.assert_array_equal(whole, parts)


class TestImplicitALS:
    def test_ranks_observed_items_higher(self, ctx):
        # Two user groups with disjoint item tastes; implicit ALS must rank
        # in-group items above out-group ones for held-in users.
        rng = np.random.default_rng(1)
        rows = []
        for u in range(30):
            group = u % 2
            items = np.arange(0, 10) if group == 0 else np.arange(10, 20)
            for i in rng.choice(items, size=6, replace=False):
                rows.append((u, i, 1.0))
        users, items, ratings = map(np.array, zip(*rows))
        inter = Interactions(
            user=users.astype(np.int32),
            item=items.astype(np.int32),
            rating=ratings.astype(np.float32),
            t=np.zeros(len(rows)),
            user_map=BiMap.string_int(f"u{i}" for i in range(30)),
            item_map=BiMap.string_int(f"i{i}" for i in range(20)),
        )
        model = train_als(
            ctx, inter, ALSConfig(rank=8, iterations=8, reg=0.01, implicit=True, alpha=10.0)
        )
        in_group = model.user_factors[0] @ model.item_factors[:10].T
        out_group = model.user_factors[0] @ model.item_factors[10:].T
        assert in_group.mean() > out_group.mean() + 0.1


class TestALSScorer:
    def test_topk_and_filters(self, ctx):
        inter = synthetic_explicit(n_users=20, n_items=15)
        model = train_als(ctx, inter, ALSConfig(rank=3, iterations=5))
        scorer = ALSScorer(ctx, model)
        idx, scores = scorer.recommend(0, 5)
        assert len(idx) == 5
        assert np.all(np.diff(scores) <= 1e-6)  # descending
        # exclusion removes those items
        idx2, _ = scorer.recommend(0, 5, exclude_items=idx[:2])
        assert not set(idx[:2]) & set(idx2)
        # candidate whitelist restricts the pool
        idx3, _ = scorer.recommend(0, 3, candidate_items=np.array([1, 2, 3]))
        assert set(idx3) <= {1, 2, 3}

    def test_device_path_matches_host_path_with_filters(self, ctx):
        """The on-device scatter-of-indices filter (no dense per-query mask
        upload) must rank identically to the host reference path, across
        filter-bucket sizes including empty and multi-bucket."""
        inter = synthetic_explicit(n_users=12, n_items=40)
        model = train_als(ctx, inter, ALSConfig(rank=4, iterations=4))
        host = ALSScorer(ctx, model, on_device=False)
        dev = ALSScorer(ctx, model, on_device=True)
        rng = np.random.default_rng(0)
        cases = [
            dict(),
            dict(exclude_items=np.array([0])),
            dict(exclude_items=rng.choice(40, 30, replace=False)),
            dict(candidate_items=np.array([5, 6, 7, 8])),
            dict(exclude_items=np.array([5, 6]),
                 candidate_items=np.array([5, 6, 7, 8, 9])),
            dict(candidate_items=np.arange(40)),  # full whitelist = no-op
        ]
        for kw in cases:
            hi, hs = host.recommend(3, 4, **kw)
            di, ds = dev.recommend(3, 4, **kw)
            assert list(hi) == list(di), kw
            np.testing.assert_allclose(hs, ds, rtol=1e-4)

    def test_oversized_filter_set_falls_back_to_host(self, ctx):
        inter = synthetic_explicit(n_users=6, n_items=20)
        model = train_als(ctx, inter, ALSConfig(rank=2, iterations=2))
        scorer = ALSScorer(ctx, model, on_device=True)
        scorer.FILTER_BUCKETS = (0, 4)  # force overflow with 5 exclusions
        idx, _ = scorer.recommend(0, 5, exclude_items=np.arange(5))
        assert not set(idx) & set(range(5))

    def test_num_larger_than_items(self, ctx):
        inter = synthetic_explicit(n_users=5, n_items=4)
        model = train_als(ctx, inter, ALSConfig(rank=2, iterations=2))
        scorer = ALSScorer(ctx, model)
        idx, _ = scorer.recommend(0, 50)
        assert len(idx) == 4  # capped at item count, no padding leaks


class TestSolverConfig:
    def test_env_override_resolved_at_construction(self, monkeypatch):
        """PIO_ALS_SOLVER must take effect for configs constructed AFTER the
        env var changes — an in-process A/B sweep toggles it between runs
        (previously it was read once at import time)."""
        monkeypatch.setenv("PIO_ALS_SOLVER", "segment")
        assert ALSConfig().solver == "segment"
        monkeypatch.setenv("PIO_ALS_SOLVER", "dense")
        assert ALSConfig().solver == "dense"
        monkeypatch.delenv("PIO_ALS_SOLVER")
        assert ALSConfig().solver == "dense"
        # explicit argument always wins over the env var
        monkeypatch.setenv("PIO_ALS_SOLVER", "segment")
        assert ALSConfig(solver="dense").solver == "dense"

    def test_invalid_solver_rejected(self, monkeypatch):
        monkeypatch.setenv("PIO_ALS_SOLVER", "magic")
        with pytest.raises(ValueError, match="solver"):
            ALSConfig()


class TestScorerBatchCompileLock:
    def test_concurrent_recommend_batch_single_compile(self, ctx):
        """Concurrent first calls must share ONE lazily-built _score_batch
        (double-checked lock), not race the setattr and trace twice."""
        import threading

        inter = synthetic_explicit(n_users=8, n_items=12)
        model = train_als(ctx, inter, ALSConfig(rank=2, iterations=2))
        scorer = ALSScorer(ctx, model, on_device=True)
        built = []
        orig_lock = ALSScorer._batch_init_lock

        class SpyLock:
            def __enter__(self):
                orig_lock.acquire()
                built.append(getattr(scorer, "_score_batch", None))
                return self

            def __exit__(self, *a):
                orig_lock.release()

        scorer._batch_init_lock = SpyLock()
        results = []
        threads = [
            threading.Thread(
                target=lambda: results.append(
                    scorer.recommend_batch(np.arange(4), 3)
                )
            )
            for _ in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 4
        # every thread that entered the critical section after the first
        # saw the already-built fn (double check held) — at most one None
        assert sum(b is None for b in built) <= 1
        for idx, _ in results:
            assert idx.shape == (4, 3)
