"""IVF approximate retrieval (ISSUE 16): k-means coarse partition,
publish/recall gate, pruned serving scan, and the degrade seams.

The contract under test: with ``nprobe == nlist`` the pruned scan gives
the exact fused path's answers (same kernel, same two-key merge, same tie
order) across batch rungs and factor dtypes — bit-identical on a TPU,
within ``CPU_WIDTH_MAX_ULP`` (tests/conftest.py) on XLA:CPU, whose dot
rounds by block width — approximation enters ONLY through scanning fewer
cluster blocks.  Publish refuses an
index below ``PIO_IVF_MIN_RECALL`` with a metadata receipt; deploy
degrades to exact on a torn/missing/fingerprint-mismatched ``ivf.blob``
and rolls back on ``PIO_RETRIEVAL=exact``.
"""

import os
import pickle

import numpy as np
import pytest

from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.als import ALSScorer, CheckpointedALSModel
from predictionio_tpu.ops import ivf
from predictionio_tpu.ops.quantize import quantize_factors
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving.fastpath import BucketedScorer


@pytest.fixture(scope="module")
def ctx():
    return MeshContext.create()


@pytest.fixture()
def clean_env(monkeypatch):
    for k in ("PIO_RETRIEVAL", "PIO_IVF_NLIST", "PIO_IVF_NPROBE",
              "PIO_IVF_MIN_RECALL", "PIO_IVF_EVAL_USERS",
              "PIO_QUANT_DTYPE", "PIO_QUANT_MIN_OVERLAP"):
        monkeypatch.delenv(k, raising=False)
    return monkeypatch


@pytest.fixture()
def basedir(tmp_path, clean_env):
    clean_env.setenv("PIO_FS_BASEDIR", str(tmp_path))
    return tmp_path


def _clustered(n_items=96, rank=8, nlist=6, seed=7, n_users=64):
    """Well-separated Gaussian mixture: k-means recovers it, recall ≈ 1."""
    rng = np.random.default_rng(seed)
    centers = (rng.normal(size=(nlist, rank)) * 4.0).astype(np.float32)
    V = (
        centers[rng.integers(0, nlist, size=n_items)]
        + rng.normal(size=(n_items, rank)) * 0.25
    ).astype(np.float32)
    U = (
        centers[rng.integers(0, nlist, size=n_users)]
        + rng.normal(size=(n_users, rank)) * 0.25
    ).astype(np.float32)
    return U, V


def _model(n_users=60, n_items=40, rank=8, seed=3):
    rng = np.random.default_rng(seed)
    return CheckpointedALSModel(
        rng.standard_normal((n_users, rank)).astype(np.float32),
        rng.standard_normal((n_items, rank)).astype(np.float32),
        BiMap.string_int(f"u{i}" for i in range(n_users)),
        BiMap.string_int(f"i{i}" for i in range(n_items)),
        None,
    )


def _meta(instance_id, key):
    with open(
        os.path.join(CheckpointedALSModel._dir(instance_id), "maps.pkl"), "rb"
    ) as f:
        return pickle.load(f)[key]


# -- k-means ------------------------------------------------------------------


class TestKMeans:
    def test_deterministic(self):
        _, V = _clustered()
        c1, a1 = ivf.train_kmeans(V, 6, seed=0)
        c2, a2 = ivf.train_kmeans(V, 6, seed=0)
        np.testing.assert_array_equal(c1, c2)
        np.testing.assert_array_equal(a1, a2)

    def test_recovers_separated_clusters_balanced(self):
        _, V = _clustered(n_items=400, nlist=8)
        centroids, assign = ivf.train_kmeans(V, 8)
        sizes = np.bincount(assign, minlength=len(centroids))
        assert sizes.min() >= 1
        # split pass targets 1.25x mean; 2x is the hard capacity cap
        assert sizes.max() <= int(np.ceil(2.0 * 400 / 8))
        assert sizes.max() <= 1.6 * sizes.mean()

    def test_capacity_cap_bounds_runaway_cluster(self):
        # all mass in one tight blob: the cap still levels the partition
        rng = np.random.default_rng(0)
        V = (rng.normal(size=(64, 4)) * 0.01 + 5.0).astype(np.float32)
        _, assign = ivf.train_kmeans(V, 4)
        sizes = np.bincount(assign)
        assert sizes.max() <= int(np.ceil(2.0 * 64 / 4))

    def test_empty_cells_dropped_and_ids_compacted(self):
        # duplicate rows < nlist distinct points: dead cells must vanish
        V = np.repeat(np.eye(3, dtype=np.float32), 5, axis=0)
        centroids, assign = ivf.train_kmeans(V, 8)
        n_live = centroids.shape[0]
        assert n_live <= 8
        assert set(np.unique(assign)) == set(range(n_live))

    def test_nlist_bounds(self):
        _, V = _clustered()
        with pytest.raises(ValueError):
            ivf.train_kmeans(V, 0)
        with pytest.raises(ValueError):
            ivf.train_kmeans(V, len(V) + 1)


# -- index + blob envelope ----------------------------------------------------


class TestIndex:
    def test_build_and_describe(self):
        _, V = _clustered()
        index = ivf.build_index(V, 6)
        index.validate(len(V))
        d = index.describe()
        assert d["nlist"] == index.nlist and d["n_items"] == len(V)
        assert d["nprobe"] == ivf.default_nprobe(index.nlist)
        assert d["items_per_cluster_min"] >= 1

    def test_blob_round_trip(self, tmp_path):
        _, V = _clustered()
        index = ivf.build_index(V, 6, nprobe=2)
        path = str(tmp_path / "ivf.blob")
        ivf.save_index(path, index)
        back = ivf.load_index(path)
        assert back.fingerprint == index.fingerprint
        assert back.nprobe == 2
        np.testing.assert_array_equal(
            back.plan.assignment, index.plan.assignment
        )

    def test_torn_blob_raises_integrity(self, tmp_path):
        from predictionio_tpu.core.persistence import ModelIntegrityError

        _, V = _clustered()
        path = str(tmp_path / "ivf.blob")
        ivf.save_index(path, ivf.build_index(V, 6))
        data = open(path, "rb").read()
        with open(path, "wb") as f:
            f.write(data[:-7] + b"XXXXXXX")
        with pytest.raises(ModelIntegrityError):
            ivf.load_index(path)

    def test_fingerprint_excludes_serving_tunables(self):
        import dataclasses

        _, V = _clustered()
        index = ivf.build_index(V, 6, nprobe=1)
        retuned = dataclasses.replace(
            index, nprobe=6, recall_at_publish=0.99
        )
        # retuning nprobe / stamping the receipt is NOT a new generation
        assert retuned.fingerprint == index.fingerprint

    def test_index_from_env(self, clean_env):
        _, V = _clustered()
        assert ivf.index_from_env(V) is None  # unset → exact-only publish
        clean_env.setenv("PIO_IVF_NLIST", "6")
        clean_env.setenv("PIO_IVF_NPROBE", "3")
        index = ivf.index_from_env(V)
        assert index.nlist == 6 and index.nprobe == 3

    def test_measure_recall_full_probe_is_one(self):
        U, V = _clustered()
        index = ivf.build_index(V, 6)
        assert ivf.measure_recall(
            U, V, index, k=10, nprobe=index.nlist
        ) == 1.0


# -- retrieval seam -----------------------------------------------------------


class TestResolveRetrieval:
    def test_auto_follows_index_presence(self, clean_env):
        _, V = _clustered()
        index = ivf.build_index(V, 6)
        assert ivf.resolve_retrieval(None, index=None) == "exact"
        assert ivf.resolve_retrieval(None, index=index) == "ivf"

    def test_exact_always_wins(self, clean_env):
        _, V = _clustered()
        index = ivf.build_index(V, 6)
        clean_env.setenv("PIO_RETRIEVAL", "exact")
        assert ivf.resolve_retrieval(None, index=index) == "exact"

    def test_explicit_ivf_without_index_is_config_error(self, clean_env):
        with pytest.raises(ValueError, match="PIO_RETRIEVAL=ivf"):
            ivf.resolve_retrieval("ivf", index=None)

    def test_unknown_backend_rejected(self, clean_env):
        clean_env.setenv("PIO_RETRIEVAL", "fuzzy")
        with pytest.raises(ValueError, match="must be one of"):
            ivf.resolve_retrieval(None)


# -- serving: bit-identity + pruning ------------------------------------------


def _scorers(ctx, U, V, dtype, k, nprobe, backend=None):
    index = ivf.build_index(V, 6, nprobe=nprobe)
    kw = {"max_k": k}
    if backend is not None:
        kw["backend"] = backend
    if dtype == "f32":
        args = (U, V)
    else:
        Uq, us = quantize_factors(U, dtype)
        Vq, vs = quantize_factors(V, dtype)
        args = (Uq, Vq)
        kw.update(factor_dtype=dtype, user_scale=us, item_scale=vs)
    exact = BucketedScorer(ctx, *args, **kw)
    pruned = BucketedScorer(
        ctx, *args, ivf_index=index, retrieval="ivf", **kw
    )
    return exact, pruned


class TestBitIdentity:
    @pytest.mark.parametrize("dtype", ["f32", "bf16", "int8"])
    def test_full_probe_identical_across_rungs(
        self, ctx, clean_env, dtype, assert_same_topk
    ):
        # nprobe == nlist: the pruned path scans every block, so answers
        # must equal exact — values and indices, every rung.  The exact
        # scan is one full-width contraction and the pruned one runs per
        # cluster block: identical on a TPU, within the stated ulps on
        # XLA:CPU (tests/conftest.py)
        U, V = _clustered()
        exact, pruned = _scorers(ctx, U, V, dtype, k=10, nprobe=6)
        assert pruned.retrieval == "ivf" and exact.retrieval == "exact"
        for b in (1, 8, 16, 32, 64):
            users = np.arange(b) % U.shape[0]
            ei, ev = exact.score_topk(users, 10)
            pi, pv = pruned.score_topk(users, 10)
            assert_same_topk(ei, ev, pi, pv, f"at rung {b}")

    @pytest.mark.parametrize("dtype", ["f32", "int8"])
    def test_full_probe_identical_fused_interpret(
        self, ctx, clean_env, dtype, assert_same_topk
    ):
        U, V = _clustered(n_users=16)
        exact, pruned = _scorers(
            ctx, U, V, dtype, k=5, nprobe=6, backend="fused"
        )
        for b in (1, 8):
            users = np.arange(b) % U.shape[0]
            ei, ev = exact.score_topk(users, 5)
            pi, pv = pruned.score_topk(users, 5)
            assert_same_topk(ei, ev, pi, pv, f"at rung {b}")


class TestPrunedServing:
    def test_default_nprobe_prunes_and_recalls(self, ctx, clean_env):
        U, V = _clustered(n_items=240, nlist=6, n_users=32)
        from predictionio_tpu.core.evaluation import recall_at_k

        index = ivf.build_index(V, 6, nprobe=1)
        exact = BucketedScorer(ctx, U, V, max_k=10)
        pruned = BucketedScorer(
            ctx, U, V, max_k=10, ivf_index=index, retrieval="ivf"
        )
        ei = []
        pi = []
        for u in range(U.shape[0]):
            ei.append(exact.score_topk(np.array([u]), 10)[0][0])
            pi.append(pruned.score_topk(np.array([u]), 10)[0][0])
        st = pruned.stats()["retrieval"]
        assert st["backend"] == "ivf"
        assert 0 < st["scanned_fraction"] < 1.0
        # clustered queries: one probed cluster holds the whole top-k
        assert recall_at_k(np.stack(ei), np.stack(pi), 10) >= 0.95

    def test_default_nprobe_scans_under_a_fifth_and_recalls(
        self, ctx, clean_env
    ):
        """Both halves of the trade at once, at the computed default
        (``nprobe = nlist // 8``) on a clustered catalog: recall@10 >= 0.95
        against the exact scorer while b=1 dispatches touch <= 0.2 of the
        catalog's padded rows (the scorer's own count)."""
        from predictionio_tpu.core.evaluation import recall_at_k

        U, V = _clustered(
            n_items=4096, rank=16, nlist=64, n_users=32, seed=16
        )
        index = ivf.build_index(V, 64)
        assert index.nprobe == ivf.default_nprobe(64) == 8
        exact = BucketedScorer(ctx, U, V, max_k=10)
        pruned = BucketedScorer(
            ctx, U, V, max_k=10, ivf_index=index, retrieval="ivf"
        )
        ei, pi = [], []
        for u in range(U.shape[0]):
            ei.append(exact.score_topk(np.array([u]), 10)[0][0])
            pi.append(pruned.score_topk(np.array([u]), 10)[0][0])
        assert pruned.stats()["retrieval"]["scanned_fraction"] <= 0.2
        assert recall_at_k(np.stack(ei), np.stack(pi), 10) >= 0.95

    def test_probe_budget_widens_with_rung_and_clamps(self, ctx, clean_env):
        U, V = _clustered()
        index = ivf.build_index(V, 6, nprobe=2)
        sc = BucketedScorer(
            ctx, U, V, max_k=10, ivf_index=index, retrieval="ivf"
        )
        probes = sc.stats()["retrieval"]["probes_per_rung"]
        assert probes["1"] >= 2  # nprobe floor (maybe min_probes above)
        assert probes["64"] == 6  # clamps at nlist
        assert all(
            probes[a] <= probes[b]
            for a, b in zip("1 8 16 32".split(), "8 16 32 64".split())
        )

    def test_min_probes_keeps_padding_out_of_topk(self, ctx, clean_env):
        # many tiny clusters, k bigger than any one cluster: the floor
        # must widen the probe set so ONLY real items fill the top-k
        rng = np.random.default_rng(5)
        centers = (rng.normal(size=(12, 4)) * 4.0).astype(np.float32)
        V = (
            np.repeat(centers, 4, axis=0)
            + rng.normal(size=(48, 4)) * 0.1
        ).astype(np.float32)
        U = centers[:3].copy()
        index = ivf.build_index(V, 12, nprobe=1)
        sc = BucketedScorer(
            ctx, U, V, max_k=10, ivf_index=index, retrieval="ivf"
        )
        st = sc.stats()["retrieval"]
        assert st["min_probes"] >= 3  # 10 slots need >= 3 four-item cells
        idx, vals = sc.score_topk(np.arange(3), 10)
        assert idx.min() >= 0 and idx.max() < 48
        assert np.isfinite(np.asarray(vals)).all()

    def test_deploy_nprobe_override_clamped(self, ctx, clean_env):
        U, V = _clustered()
        index = ivf.build_index(V, 6, nprobe=2)
        clean_env.setenv("PIO_IVF_NPROBE", "999")
        sc = BucketedScorer(
            ctx, U, V, max_k=5, ivf_index=index, retrieval="ivf"
        )
        assert sc.stats()["retrieval"]["nprobe"] == 6  # clamped to nlist

    def test_sharded_plan_takes_precedence(self, ctx, clean_env):
        from predictionio_tpu.serving import sharding as sharding_mod

        U, V = _clustered()
        index = ivf.build_index(V, 6)
        plan = sharding_mod.build_plan(len(V), 2)
        sc = BucketedScorer(
            ctx, U, V, max_k=5, plan=plan, sharding="sharded",
            ivf_index=index, retrieval="auto",
        )
        assert sc.retrieval == "exact" and sc.sharding == "sharded"
        assert sc.stats()["retrieval"] is None


# -- publish → deploy lifecycle -----------------------------------------------


class TestPublishLifecycle:
    def test_declare_seal_load_serve(self, ctx, basedir, clean_env):
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe makes publish-time recall exactly 1.0, so the gate
        # deterministically passes even on unclustered random factors
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        m = _model()
        assert m.save("inst-ivf", None)
        d = CheckpointedALSModel._dir("inst-ivf")
        assert os.path.exists(os.path.join(d, "ivf.blob"))
        rec = _meta("inst-ivf", "ivf")
        assert rec["nlist"] == 8 and rec["fingerprint"]
        assert rec["recall"] >= rec["threshold"]

        m2 = CheckpointedALSModel.load("inst-ivf", None, ctx)
        assert m2.ivf_index is not None
        assert m2.ivf_index.fingerprint == rec["fingerprint"]
        assert m2.ivf_index.recall_at_publish == rec["recall"]
        fp = ALSScorer(ctx, m2).enable_fastpath()
        st = fp.stats()
        assert st["retrieval_backend"] == "ivf"
        assert st["retrieval"]["recall_at_publish"] == rec["recall"]

    def test_corrupt_blob_degrades_to_exact(self, ctx, basedir, clean_env):
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe makes publish-time recall exactly 1.0, so the gate
        # deterministically passes even on unclustered random factors
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        m = _model()
        m.save("inst-torn", None)
        blob = os.path.join(
            CheckpointedALSModel._dir("inst-torn"), "ivf.blob"
        )
        data = open(blob, "rb").read()
        with open(blob, "wb") as f:
            f.write(data[:-7] + b"XXXXXXX")
        m2 = CheckpointedALSModel.load("inst-torn", None, ctx)
        assert m2.ivf_index is None
        fp = ALSScorer(ctx, m2).enable_fastpath()
        assert fp.stats()["retrieval_backend"] == "exact"

    def test_missing_blob_degrades_to_exact(self, ctx, basedir, clean_env):
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe makes publish-time recall exactly 1.0, so the gate
        # deterministically passes even on unclustered random factors
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        m = _model()
        m.save("inst-gone", None)
        os.remove(
            os.path.join(CheckpointedALSModel._dir("inst-gone"), "ivf.blob")
        )
        m2 = CheckpointedALSModel.load("inst-gone", None, ctx)
        assert m2.ivf_index is None

    def test_fingerprint_mismatch_degrades(self, ctx, basedir, clean_env):
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe makes publish-time recall exactly 1.0, so the gate
        # deterministically passes even on unclustered random factors
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        m = _model()
        m.save("inst-fpmm", None)
        maps_path = os.path.join(
            CheckpointedALSModel._dir("inst-fpmm"), "maps.pkl"
        )
        with open(maps_path, "rb") as f:
            maps = pickle.load(f)
        maps["ivf"]["fingerprint"] = "0" * 16  # partial-publish stand-in
        with open(maps_path, "wb") as f:
            pickle.dump(maps, f)
        m2 = CheckpointedALSModel.load("inst-fpmm", None, ctx)
        assert m2.ivf_index is None

    def test_exact_env_is_one_knob_rollback(self, ctx, basedir, clean_env):
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe makes publish-time recall exactly 1.0, so the gate
        # deterministically passes even on unclustered random factors
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        m = _model()
        m.save("inst-roll", None)
        clean_env.setenv("PIO_RETRIEVAL", "exact")
        m2 = CheckpointedALSModel.load("inst-roll", None, ctx)
        # sealed index present and valid, ignored by operator decree
        assert m2.ivf_index is None
        fp = ALSScorer(ctx, m2).enable_fastpath()
        assert fp.stats()["retrieval_backend"] == "exact"


# -- the one parametrized refusal regression ----------------------------------


@pytest.mark.parametrize("gate", ["quant", "ivf"])
def test_below_threshold_publish_refused_with_receipt(
    ctx, basedir, clean_env, gate
):
    """Both accuracy gates share a contract: an unreachable threshold
    refuses the variant, the refusal lands in the instance metadata as a
    receipt, the blob is NOT sealed, and serving stays on the exact/f32
    path — a bad publish can degrade quality of service, never
    correctness."""
    iid = f"inst-refuse-{gate}"
    if gate == "quant":
        clean_env.setenv("PIO_QUANT_DTYPE", "int8")
        clean_env.setenv("PIO_QUANT_MIN_OVERLAP", "1.01")
        blob = "quant.blob"
    else:
        clean_env.setenv("PIO_IVF_NLIST", "8")
        # full probe: recall is exactly 1.0, still below the 1.01 bar —
        # the refusal is purely the threshold's doing, not bad clustering
        clean_env.setenv("PIO_IVF_NPROBE", "8")
        clean_env.setenv("PIO_IVF_MIN_RECALL", "1.01")
        blob = "ivf.blob"
    m = _model()
    m.save(iid, None)
    assert not os.path.exists(
        os.path.join(CheckpointedALSModel._dir(iid), blob)
    )
    if gate == "quant":
        rec = _meta(iid, "quant")
        assert rec["dtype"] == "f32" and rec["refused"] == "int8"
        assert rec["topk_overlap"] < rec["threshold"] == 1.01
    else:
        rec = _meta(iid, "ivf")
        assert rec["nlist"] == 0 and rec["refused"] == 8
        assert rec["recall"] < rec["threshold"] == 1.01
    m2 = CheckpointedALSModel.load(iid, None, ctx)
    fp = ALSScorer(ctx, m2).enable_fastpath()
    st = fp.stats()
    assert st["retrieval_backend"] == "exact"
    assert st["kernel"]["factor_dtype"] == "f32"


# -- observability ------------------------------------------------------------


class TestObservability:
    def test_bridge_emits_only_while_ivf_live(self):
        from predictionio_tpu.obs import bridges, metrics as obs_metrics

        stats = {"retrieval": None}
        reg = obs_metrics.MetricsRegistry()
        bridges.bridge_ivf(reg, lambda: stats)
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        assert not any(n.startswith("pio_ivf_") for (n, _) in series)

        stats["retrieval"] = {
            "backend": "ivf", "nlist": 6, "nprobe": 2, "min_probes": 1,
            "cap_pad": 24, "dispatches": 3, "probed_blocks": 6,
            "scanned_rows": 144, "scanned_fraction": 0.5,
            "recall_at_publish": 0.97, "resident_extra_bytes": 1024,
            "fingerprint": "abc123",
        }
        series = obs_metrics.parse_prometheus(reg.render_prometheus())
        assert series[("pio_ivf_info", (("fingerprint", "abc123"),))] == 6
        assert series[("pio_ivf_nprobe", ())] == 2
        assert series[("pio_ivf_probed_blocks_total", ())] == 6
        assert series[("pio_ivf_scanned_fraction", ())] == 0.5
        assert series[("pio_ivf_recall_at_publish", ())] == 0.97
        assert series[("pio_ivf_resident_extra_bytes", ())] == 1024

    def test_loadtest_summary_retrieval_keys(self):
        from predictionio_tpu.tools.loadtest import summarize_metrics

        base = {
            ("pio_kernel_info",
             (("backend", "reference"), ("dtype", "f32"))): 1.0,
        }
        out = summarize_metrics(dict(base))
        assert out["retrievalBackend"] == "exact"
        assert "ivfNprobe" not in out

        base.update({
            ("pio_ivf_info", (("fingerprint", "abc"),)): 6.0,
            ("pio_ivf_nprobe", ()): 2.0,
            ("pio_ivf_scanned_fraction", ()): 0.25,
        })
        out = summarize_metrics(base)
        assert out["retrievalBackend"] == "ivf"
        assert out["ivfNprobe"] == 2.0
        assert out["ivfScannedFraction"] == 0.25
