"""Sharded serving: ShardingPlan + partitioned fastpath (ISSUE 12).

The acceptance bar is BIT-identical answers: for every rung of the bucket
ladder and every factor dtype, the sharded executor (per-shard fused
top-k + leaderboard all-gather + two-key merge) must return exactly the
replicated scorer's indices AND values — cross-shard score ties and
exclusion masks spanning shards included.  Around that sit the plan
builder (LPT balance, budget-derived counts, fingerprints), the sealed
plan.blob publish/load round trip with its degrade-to-replicated failure
matrix, backend resolution semantics, the `pio_shard_*` bridge, and the
`pio shards` CLI.
"""

import argparse
import json
import os
import pickle

import numpy as np
import pytest

from predictionio_tpu.core.persistence import ModelIntegrityError
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.als import ALSScorer, CheckpointedALSModel
from predictionio_tpu.ops.quantize import quantize_factors
from predictionio_tpu.ops.topk import gather_score_topk, merge_topk
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving import sharding
from predictionio_tpu.serving.fastpath import (
    BucketedScorer, resolve_serving_backend,
)

N_USERS, N_ITEMS, RANK = 70, 301, 8


@pytest.fixture(scope="module")
def ctx():
    return MeshContext.create()


@pytest.fixture(scope="module")
def factors():
    rng = np.random.default_rng(17)
    U = rng.normal(size=(N_USERS, RANK)).astype(np.float32)
    V = rng.normal(size=(N_ITEMS, RANK)).astype(np.float32)
    return U, V


@pytest.fixture(scope="module")
def plan(factors):
    _, V = factors
    return sharding.build_plan(
        N_ITEMS, 4, weights=np.linalg.norm(V, axis=1),
        strategy="popularity",
    )


# -- plan builder -------------------------------------------------------------


class TestBuildPlan:
    @pytest.mark.parametrize("strategy", sharding.STRATEGIES)
    def test_every_strategy_builds_a_valid_plan(self, strategy):
        w = np.arange(1, 101, dtype=np.float64)
        p = sharding.build_plan(100, 4, weights=w, strategy=strategy)
        p.validate(100)
        assert p.n_shards == 4
        assert sorted(np.concatenate(
            [p.shard_items(s) for s in range(4)]
        ).tolist()) == list(range(100))
        # the capacity cap keeps byte residency level for every strategy
        assert p.shard_sizes().max() <= int(np.ceil(100 / 4))

    def test_popularity_balances_skewed_weights(self):
        # zipf-ish head: popularity LPT must spread it; contiguous piles
        # the whole head on shard 0
        w = 1.0 / (np.arange(200) + 1.0)
        lpt = sharding.build_plan(200, 4, weights=w, strategy="popularity")
        naive = sharding.build_plan(200, 4, weights=w, strategy="contiguous")
        assert max(lpt.load_share) / min(lpt.load_share) < 1.05
        assert max(naive.load_share) / min(naive.load_share) > 2.0

    def test_shard_items_ascending(self, plan):
        # the on-device order that makes shard-local top-k tie order
        # compose with the global merge
        for s in range(plan.n_shards):
            ids = plan.shard_items(s)
            assert np.all(np.diff(ids) > 0)

    def test_budget_derived_count(self):
        # 300 items × 32 B = 9600 B; a 2500 B per-shard budget needs 4
        assert sharding.shard_count_for_budget(300, 32.0, 2500) == 4
        p = sharding.build_plan(
            300, capacity_budget_bytes=2500, bytes_per_item=32.0
        )
        assert p.n_shards == 4
        assert p.capacity_budget_bytes == 2500
        assert p.shard_sizes().max() * 32.0 <= 2500

    def test_budget_rounding_fills_host_rows_without_overrunning_catalog(self):
        # derived count 4 rounds up to 6 for 3 host rows — still <= items
        p = sharding.build_plan(
            300, capacity_budget_bytes=2500, bytes_per_item=32.0,
            host_groups=3,
        )
        assert p.n_shards == 6 and p.host_groups == 3
        # tiny catalog, many host rows: derived count 7 is servable, but
        # rounding up for 5 rows overruns the 7-item catalog — the error
        # names the pod knob, not the generic shard-count bound
        with pytest.raises(ValueError, match="PIO_POD_HOST_GROUPS"):
            sharding.build_plan(
                7, capacity_budget_bytes=4, bytes_per_item=4.0,
                host_groups=5,
            )

    def test_explicit_count_indivisible_by_host_groups_names_knob(self):
        with pytest.raises(ValueError, match="PIO_POD_HOST_GROUPS"):
            sharding.build_plan(100, 10, host_groups=3)

    def test_fingerprint_stable_and_assignment_sensitive(self):
        w = np.arange(50, dtype=np.float64)
        a = sharding.build_plan(50, 2, weights=w)
        b = sharding.build_plan(50, 2, weights=w)
        c = sharding.build_plan(50, 2, weights=w, strategy="round_robin")
        assert a.fingerprint == b.fingerprint
        assert a.fingerprint != c.fingerprint

    def test_invalid_inputs_rejected(self):
        with pytest.raises(ValueError):
            sharding.build_plan(10, 11)  # more shards than items
        with pytest.raises(ValueError):
            sharding.build_plan(10, 2, weights=np.ones(9))
        with pytest.raises(ValueError):
            sharding.build_plan(10, 2, weights=-np.ones(10))
        with pytest.raises(ValueError):
            sharding.build_plan(10, 2, strategy="hash")
        with pytest.raises(ValueError):
            sharding.build_plan(10)  # neither count nor budget
        bad = sharding.ShardingPlan(
            n_shards=3, assignment=np.zeros(6, np.int32),
            strategy="popularity", load_share=np.ones(3) / 3,
        )
        with pytest.raises(ValueError, match="empty"):
            bad.validate(6)

    def test_plan_from_env(self, monkeypatch):
        monkeypatch.delenv("PIO_SHARD_COUNT", raising=False)
        monkeypatch.delenv("PIO_SHARD_HBM_BUDGET", raising=False)
        assert sharding.plan_from_env(100) is None
        monkeypatch.setenv("PIO_SHARD_COUNT", "3")
        assert sharding.plan_from_env(100).n_shards == 3
        monkeypatch.delenv("PIO_SHARD_COUNT")
        monkeypatch.setenv("PIO_SHARD_HBM_BUDGET", "2500")
        monkeypatch.setenv("PIO_SHARD_STRATEGY", "round_robin")
        p = sharding.plan_from_env(300, bytes_per_item=32.0)
        assert p.n_shards == 4 and p.strategy == "round_robin"


class TestPlanPersistence:
    def test_payload_round_trip(self, plan):
        p2 = sharding.ShardingPlan.from_payload(plan.to_payload())
        assert p2.fingerprint == plan.fingerprint
        np.testing.assert_array_equal(p2.assignment, plan.assignment)
        np.testing.assert_allclose(p2.load_share, plan.load_share)

    def test_sealed_file_round_trip(self, plan, tmp_path):
        path = str(tmp_path / "plan.blob")
        sharding.save_plan(path, plan)
        assert sharding.load_plan(path).fingerprint == plan.fingerprint

    def test_torn_blob_raises_integrity_error(self, plan, tmp_path):
        path = str(tmp_path / "plan.blob")
        sharding.save_plan(path, plan)
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-5] + b"XXXXX")
        with pytest.raises(ModelIntegrityError):
            sharding.load_plan(path)


# -- backend resolution -------------------------------------------------------


class TestResolveServingBackend:
    def test_auto_without_plan_is_replicated(self, ctx, monkeypatch):
        monkeypatch.delenv("PIO_SERVING_SHARDING", raising=False)
        assert resolve_serving_backend(plan=None, ctx=ctx) == "replicated"

    def test_auto_with_plan_and_devices_is_sharded(self, ctx, plan):
        assert ctx.n_devices >= plan.n_shards  # conftest forces 8
        assert resolve_serving_backend(plan=plan, ctx=ctx) == "sharded"

    def test_plan_wider_than_mesh_degrades(self, ctx, factors):
        _, V = factors
        wide = sharding.build_plan(N_ITEMS, ctx.n_devices + 1)
        assert resolve_serving_backend(
            "sharded", plan=wide, ctx=ctx
        ) == "replicated"
        assert resolve_serving_backend(plan=wide, ctx=ctx) == "replicated"

    def test_explicit_sharded_without_plan_raises(self, ctx):
        with pytest.raises(ValueError, match="requires a ShardingPlan"):
            resolve_serving_backend("sharded", plan=None, ctx=ctx)

    def test_explicit_replicated_ignores_plan(self, ctx, plan):
        assert resolve_serving_backend(
            "replicated", plan=plan, ctx=ctx
        ) == "replicated"

    def test_env_knob_respected(self, ctx, plan, monkeypatch):
        monkeypatch.setenv("PIO_SERVING_SHARDING", "replicated")
        assert resolve_serving_backend(plan=plan, ctx=ctx) == "replicated"
        monkeypatch.setenv("PIO_SERVING_SHARDING", "bogus")
        with pytest.raises(ValueError):
            resolve_serving_backend(plan=plan, ctx=ctx)


# -- sharded executor: bit-identical to the replicated reference --------------


def _scorer_pair(ctx, U, V, plan, dtype):
    """(replicated, sharded) BucketedScorer pair for one factor dtype."""
    if dtype == "f32":
        kw: dict = {}
        args = (U, V)
    else:
        Uq, us = quantize_factors(U, dtype)
        Vq, vs = quantize_factors(V, dtype)
        kw = {"factor_dtype": dtype, "user_scale": us, "item_scale": vs}
        args = (Uq, Vq)
    repl = BucketedScorer(ctx, *args, max_k=20, sharding="replicated", **kw)
    shrd = BucketedScorer(
        ctx, *args, max_k=20, plan=plan, sharding="sharded", **kw
    )
    return repl, shrd


class TestShardedBitIdentical:
    @pytest.fixture(scope="class", params=["f32", "bf16", "int8"])
    def pair(self, request, ctx, factors, plan):
        U, V = factors
        return _scorer_pair(ctx, U, V, plan, request.param)

    @pytest.mark.parametrize("batch", [1, 8, 16, 32, 64])
    def test_exact_equality_per_rung(self, pair, batch):
        repl, shrd = pair
        rng = np.random.default_rng(batch)
        users = rng.integers(0, N_USERS, batch).astype(np.int32)
        ri, rv = repl.score_topk(users, 20)
        si, sv = shrd.score_topk(users, 20)
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(sv, rv)

    def test_beyond_top_rung_chunks(self, pair):
        repl, shrd = pair
        users = (np.arange(150, dtype=np.int32) * 3) % N_USERS
        ri, rv = repl.score_topk(users, 7)
        si, sv = shrd.score_topk(users, 7)
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(sv, rv)

    def test_stats_carry_sharding_block(self, pair):
        repl, shrd = pair
        # a dispatch of its own: under `--dist load` this test may be the
        # first of the class its worker runs
        shrd.score_topk(np.arange(8, dtype=np.int32), 5)
        assert repl.stats()["sharding"] is None
        assert repl.stats()["serving_backend"] == "replicated"
        sh = shrd.stats()["sharding"]
        assert shrd.stats()["serving_backend"] == "sharded"
        assert sh["plan"]["n_shards"] == 4
        assert sum(sh["result_wins"]) > 0
        assert sh["merge_bytes"] > 0
        assert len(sh["resident_bytes"]) == 4


def test_popularity_plan_balances_live_result_share(ctx):
    """A catalog past one device's byte budget, served sharded under Zipf
    users: every shard's resident block fits the budget, and a plan built
    from the measured per-item wins keeps the LIVE max/min result share —
    the share the attributed per-shard busy fraction is split by — within
    1.5.  (A count over what the scorer records, not a time.)"""
    from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights

    n_items, rank, k, budget = 1024, 16, 20, 18_000
    rng = np.random.default_rng(12)
    U = rng.normal(size=(128, rank)).astype(np.float32)
    V = rng.normal(size=(n_items, rank)).astype(np.float32)
    users = rng.choice(
        128, size=256, p=zipf_mandelbrot_weights(128, s=1.1)
    ).astype(np.int32)
    repl = BucketedScorer(ctx, U, V, max_k=k, sharding="replicated")
    ref_idx, _ = repl.score_topk(users, k)
    wins = np.bincount(
        np.asarray(ref_idx).reshape(-1), minlength=n_items
    ).astype(np.float64)
    n_shards = sharding.shard_count_for_budget(n_items, rank * 4.0, budget)
    assert V.nbytes > budget and n_shards == 4
    plan = sharding.build_plan(
        n_items, n_shards, weights=wins, strategy="popularity",
        capacity_budget_bytes=budget,
    )
    shrd = BucketedScorer(ctx, U, V, max_k=k, plan=plan, sharding="sharded")
    shrd.score_topk(users, k)
    st = shrd.stats()["sharding"]
    assert max(st["resident_bytes"]) <= budget
    share = st["result_share"]
    assert min(share) > 0 and max(share) / min(share) <= 1.5
    assert all(b is not None for b in st["busy_fraction"])


class TestCrossShardTies:
    def test_duplicate_rows_on_different_shards_tie_break_by_id(
        self, ctx, factors
    ):
        """Identical item rows land on DIFFERENT shards under round-robin;
        lax.top_k breaks exact ties by smallest index, and the merge must
        preserve that across the shard boundary."""
        U, V = factors
        Vt = V.copy()
        # items 0..9 all share one factor row → 10-way exact tie; round
        # robin scatters them over all 4 shards.  A pure first-axis spike
        # makes the tie the undisputed top answer for every user whose
        # first factor component is positive.
        Vt[:10] = 0.0
        Vt[:10, 0] = 100.0
        tie_plan = sharding.build_plan(N_ITEMS, 4, strategy="round_robin")
        repl, shrd = _scorer_pair(ctx, U, Vt, tie_plan, "f32")
        users = np.where(U[:, 0] > 0.5)[0][:32].astype(np.int32)
        assert len(users) >= 8  # enough winners to make the test real
        ri, rv = repl.score_topk(users, 20)
        si, sv = shrd.score_topk(users, 20)
        # the 10 tied duplicates must appear first, in ascending id order
        np.testing.assert_array_equal(
            ri[:, :10], np.tile(np.arange(10), (len(users), 1))
        )
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(sv, rv)

    def test_exclusion_mask_spanning_shards(self, ctx, factors, plan):
        """A per-query exclusion mask gathered into shard layout and
        applied per shard must merge to exactly the reference's masked
        top-k — items excluded on one shard can't resurface via another
        shard's leaderboard."""
        import jax.numpy as jnp

        U, V = factors
        rng = np.random.default_rng(9)
        # exclude ~30% of the catalog, including whole hot stretches so
        # some shards lose many more candidates than others
        mask = rng.random(N_ITEMS) < 0.3
        mask[:40] = True
        k = 20
        users = np.arange(8, dtype=np.int32)

        ref_v, ref_i = gather_score_topk(
            jnp.asarray(U), jnp.asarray(V), jnp.asarray(users), k,
            item_mask=jnp.asarray(mask), backend="reference",
        )

        layout = sharding.build_layout(plan, lambda n: ((n + 7) // 8) * 8)
        local_k = min(k, layout.cap_pad)
        Vs = layout.take_rows(V)  # (S*cap_pad, rank)
        gid = layout.gid
        # exclusion mask in shard layout; padded slots are always masked
        ms = layout.take_rows(mask, fill=True) | layout.pad_mask
        cand_v, cand_g = [], []
        for s in range(plan.n_shards):
            lo, hi = s * layout.cap_pad, (s + 1) * layout.cap_pad
            lv, li = gather_score_topk(
                jnp.asarray(U), jnp.asarray(Vs[lo:hi]),
                jnp.asarray(users), local_k,
                item_mask=jnp.asarray(ms[lo:hi]), backend="reference",
            )
            cand_v.append(np.asarray(lv))
            cand_g.append(gid[lo:hi][np.asarray(li)])
        mv, mi = merge_topk(
            jnp.asarray(np.concatenate(cand_v, axis=1)),
            jnp.asarray(np.concatenate(cand_g, axis=1)), k,
        )
        np.testing.assert_array_equal(np.asarray(mi), np.asarray(ref_i))
        np.testing.assert_array_equal(np.asarray(mv), np.asarray(ref_v))
        # nothing excluded ever wins
        assert not mask[np.asarray(mi).reshape(-1)].any()


# -- publish → deploy round trip ---------------------------------------------


def _model(n_users=40, n_items=60, rank=6, seed=3):
    rng = np.random.default_rng(seed)
    return CheckpointedALSModel(
        rng.standard_normal((n_users, rank)).astype(np.float32),
        rng.standard_normal((n_items, rank)).astype(np.float32),
        BiMap.string_int(f"u{i}" for i in range(n_users)),
        BiMap.string_int(f"i{i}" for i in range(n_items)),
        None,
    )


@pytest.fixture()
def basedir(tmp_path, monkeypatch):
    monkeypatch.setenv("PIO_FS_BASEDIR", str(tmp_path))
    for k in ("PIO_SHARD_COUNT", "PIO_SHARD_HBM_BUDGET",
              "PIO_SHARD_STRATEGY", "PIO_SERVING_SHARDING"):
        monkeypatch.delenv(k, raising=False)
    return tmp_path


def _shard_meta(instance_id):
    with open(
        os.path.join(CheckpointedALSModel._dir(instance_id), "maps.pkl"),
        "rb",
    ) as f:
        return pickle.load(f)["sharding"]


class TestPublishRoundTrip:
    def test_plan_survives_save_load(self, ctx, basedir):
        m = _model()
        m.sharding_plan = sharding.build_plan(60, 3)
        assert m.save("inst-plan", None)
        d = CheckpointedALSModel._dir("inst-plan")
        assert os.path.exists(os.path.join(d, "plan.blob"))
        meta = _shard_meta("inst-plan")
        assert meta["n_shards"] == 3
        assert meta["fingerprint"] == m.sharding_plan.fingerprint
        m2 = CheckpointedALSModel.load("inst-plan", None, ctx)
        assert m2.sharding_plan is not None
        assert m2.sharding_plan.fingerprint == m.sharding_plan.fingerprint
        # the loaded plan drives the sharded fastpath end to end
        fp = ALSScorer(ctx, m2).enable_fastpath()
        assert fp.sharding == "sharded"
        ref = ALSScorer(ctx, m).enable_fastpath()
        ri, rv = ref.score_topk(np.arange(10), 5)
        si, sv = fp.score_topk(np.arange(10), 5)
        np.testing.assert_array_equal(si, ri)
        np.testing.assert_array_equal(sv, rv)

    def test_unsharded_publish_records_zero(self, ctx, basedir):
        m = _model()
        m.save("inst-none", None)
        assert _shard_meta("inst-none") == {"n_shards": 0}
        m2 = CheckpointedALSModel.load("inst-none", None, ctx)
        assert m2.sharding_plan is None
        assert ALSScorer(ctx, m2).enable_fastpath().sharding == "replicated"

    def test_torn_plan_degrades_to_replicated(self, ctx, basedir):
        m = _model()
        m.sharding_plan = sharding.build_plan(60, 3)
        m.save("inst-torn", None)
        blob = os.path.join(
            CheckpointedALSModel._dir("inst-torn"), "plan.blob"
        )
        data = open(blob, "rb").read()
        with open(blob, "wb") as f:
            f.write(data[:-6] + b"YYYYYY")
        m2 = CheckpointedALSModel.load("inst-torn", None, ctx)
        assert m2.sharding_plan is None  # cold start serves replicated
        np.testing.assert_array_equal(m2.user_factors, m.user_factors)
        assert ALSScorer(ctx, m2).enable_fastpath().sharding == "replicated"

    def test_fingerprint_mismatch_degrades(self, ctx, basedir):
        m = _model()
        m.sharding_plan = sharding.build_plan(60, 3)
        m.save("inst-fpmm", None)
        maps_path = os.path.join(
            CheckpointedALSModel._dir("inst-fpmm"), "maps.pkl"
        )
        with open(maps_path, "rb") as f:
            meta = pickle.load(f)
        meta["sharding"]["fingerprint"] = "0" * 16
        with open(maps_path, "wb") as f:
            pickle.dump(meta, f)
        m2 = CheckpointedALSModel.load("inst-fpmm", None, ctx)
        assert m2.sharding_plan is None

    def test_env_declared_plan_at_publish(self, ctx, basedir, monkeypatch):
        from predictionio_tpu.models.als import _declare_sharding_plan

        monkeypatch.setenv("PIO_SHARD_COUNT", "4")
        m = _declare_sharding_plan(_model())
        assert m.sharding_plan is not None
        assert m.sharding_plan.n_shards == 4
        assert m.sharding_plan.strategy == "popularity"


# -- metrics bridge -----------------------------------------------------------


class TestBridge:
    def test_bridge_emits_per_shard_series(self, ctx, factors, plan):
        from predictionio_tpu.obs import bridges, metrics as obs_metrics

        U, V = factors
        _, shrd = _scorer_pair(ctx, U, V, plan, "f32")
        shrd.score_topk(np.arange(16, dtype=np.int32), 10)
        reg = obs_metrics.MetricsRegistry()
        bridges.bridge_sharding(reg, shrd.stats)
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        fp = plan.fingerprint
        assert series[
            ("pio_shard_info",
             (("fingerprint", fp), ("strategy", "popularity")))
        ] == 4.0
        for s in range(4):
            lbl = (("shard", str(s)),)
            assert series[("pio_shard_items", lbl)] > 0
            assert series[("pio_shard_resident_bytes", lbl)] > 0
            assert series[("pio_shard_queries_routed_total", lbl)] == 16.0
        assert sum(
            series[("pio_shard_result_wins_total", (("shard", str(s)),))]
            for s in range(4)
        ) == 160.0
        assert series[("pio_shard_merge_bytes_total", ())] > 0

    def test_bridge_silent_when_replicated(self, ctx, factors):
        from predictionio_tpu.obs import bridges, metrics as obs_metrics

        U, V = factors
        repl = BucketedScorer(ctx, U, V, max_k=5, sharding="replicated")
        reg = obs_metrics.MetricsRegistry()
        bridges.bridge_sharding(reg, repl.stats)
        assert "pio_shard_" not in reg.render_prometheus()


# -- pio shards CLI -----------------------------------------------------------


class TestShardsCLI:
    def test_show_and_rebuild(self, ctx, basedir, capsys):
        from predictionio_tpu.tools.cli import cmd_shards

        m = _model()
        m.sharding_plan = sharding.build_plan(60, 3)
        m.save("inst-cli", None)
        old_fp = m.sharding_plan.fingerprint

        rc = cmd_shards(argparse.Namespace(
            shards_command="show", instance=None
        ))
        assert rc == 0
        rows = json.loads(capsys.readouterr().out)
        assert rows[0]["instance"] == "inst-cli"
        assert rows[0]["fingerprint"] == old_fp

        rc = cmd_shards(argparse.Namespace(
            shards_command="rebuild", instance="inst-cli", shards=5,
            budget=None, strategy="round_robin", weights="uniform",
        ))
        assert rc == 0
        # the reseal is visible to a fresh load AND recorded in the
        # manifest so the fingerprint check passes after reload
        m2 = CheckpointedALSModel.load("inst-cli", None, ctx)
        assert m2.sharding_plan.n_shards == 5
        assert m2.sharding_plan.strategy == "round_robin"
        assert _shard_meta("inst-cli")["fingerprint"] == \
            m2.sharding_plan.fingerprint
        assert m2.sharding_plan.fingerprint != old_fp
