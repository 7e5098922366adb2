"""Serving fast path: bucket ladder, AOT compile cache, adaptive batcher.

Covers the ISSUE r06 acceptance points: padding to the next bucket rung,
mask correctness at the padded item tail, cache hits with ZERO recompiles
across repeated sizes, and the adaptive-window micro-batcher under burst
vs. trickle arrival.
"""

import itertools
import threading
import time
import types

import numpy as np
import pytest

from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.serving import batching, fastpath
from predictionio_tpu.serving.batching import MicroBatcher
from predictionio_tpu.serving.fastpath import BUCKETS, BucketedScorer, bucket_for


@pytest.fixture(scope="module")
def ctx():
    return MeshContext.create()


@pytest.fixture(scope="module")
def factors():
    rng = np.random.default_rng(5)
    U = rng.normal(size=(40, 6)).astype(np.float32)
    V = rng.normal(size=(29, 6)).astype(np.float32)  # 29: pads to 32 items
    return U, V


@pytest.fixture(scope="module")
def scorer(ctx, factors):
    U, V = factors
    return BucketedScorer(ctx, U, V, max_k=5)


def _reference_topk(U, V, users, k):
    scores = U[users] @ V.T
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    return idx, np.take_along_axis(scores, idx, axis=1)


class TestBucketLadder:
    def test_bucket_for_picks_smallest_rung(self):
        assert bucket_for(1) == 1
        assert bucket_for(2) == 8
        assert bucket_for(8) == 8
        assert bucket_for(9) == 16
        assert bucket_for(64) == 64

    def test_bucket_for_overflow_is_none(self):
        assert bucket_for(65) is None
        assert bucket_for(3, buckets=(1, 2)) is None

    def test_all_rungs_precompiled(self, scorer):
        assert set(scorer._fns) == set(BUCKETS)
        assert scorer.compile_count == len(BUCKETS)


class TestBucketedScorerCorrectness:
    @pytest.mark.parametrize("batch", [1, 3, 8, 11, 40])
    def test_matches_numpy_reference(self, scorer, factors, batch):
        """Every batch size — on-rung, padded, and beyond the top rung —
        must return exactly the host-numpy top-k (values AND order)."""
        U, V = factors
        rng = np.random.default_rng(batch)
        users = rng.integers(0, U.shape[0], batch)
        idx, vals = scorer.score_topk(users, k=5)
        ref_idx, ref_vals = _reference_topk(U, V, users, 5)
        assert idx.shape == (batch, 5)
        np.testing.assert_allclose(vals, ref_vals, rtol=1e-5)
        # indices may differ only on exact score ties; compare via scores
        np.testing.assert_allclose(
            np.take_along_axis(U[users] @ V.T, idx, axis=1), ref_vals,
            rtol=1e-5,
        )

    def test_padded_item_tail_never_wins(self, scorer):
        """n_items=29 pads to 32; the 3 phantom columns carry garbage and
        must never appear in any result."""
        idx, _ = scorer.score_topk(np.arange(16), k=5)
        assert idx.max() < scorer.n_items

    def test_k_beyond_compiled_width_raises(self, scorer):
        with pytest.raises(ValueError):
            scorer.score_topk(np.array([0]), k=scorer.k + 1)


class TestCompileCache:
    def test_zero_recompiles_across_repeated_sizes(self, scorer, monkeypatch):
        """After warmup, serving any mix of sizes repeatedly must never
        trace or compile again: jax.jit itself is booby-trapped."""
        before = scorer.compile_count

        def boom(*a, **k):
            raise AssertionError("recompile on the serve path")

        monkeypatch.setattr(fastpath.jax, "jit", boom)
        for batch in (1, 8, 3, 8, 16, 1, 40):
            scorer.score_topk(np.zeros(batch, np.int32), k=3)
        assert scorer.compile_count == before

    def test_hit_counters_track_buckets(self, ctx, factors):
        U, V = factors
        s = BucketedScorer(ctx, U, V, max_k=4)
        s.score_topk(np.zeros(3, np.int32), k=4)  # pads 3 → rung 8
        s.score_topk(np.zeros(8, np.int32), k=4)
        stats = s.stats()
        assert stats["bucket_hits"]["8"] == 2
        assert stats["compile_count"] == len(BUCKETS)
        assert stats["queries"] == 11
        assert stats["padded_rows"] == 5
        assert stats["row_occupancy"] == round(11 / 16, 4)


class TestFusedBackend:
    """ISSUE 9: the fused Pallas backend through the full BucketedScorer
    path — every rung warms (compiled AND executed once) at construction,
    so no compile and no first-execution stall can happen under load."""

    @pytest.fixture(scope="class")
    def fused(self, ctx, factors):
        U, V = factors
        return BucketedScorer(ctx, U, V, max_k=5, backend="fused")

    def test_kernel_stats_identify_backend(self, fused):
        kern = fused.stats()["kernel"]
        assert kern["backend"] == "fused"
        assert kern["factor_dtype"] == "f32"
        assert kern["warmup_executions"] == len(BUCKETS)
        assert kern["intensity_flops_per_byte"] > 0

    def test_kernel_stats_report_each_rungs_tile(self, ctx, fused, scorer):
        # what the kernel's own rule gives each compiled rung: 29 items
        # pad to 32, one block; rows in whole sublane tiles
        tiles = fused.stats()["kernel"]["block_items"]
        assert tiles == {
            str(b): {"tile_rows": max(b, 8), "block_items": 32}
            for b in BUCKETS}
        assert scorer.stats()["kernel"]["block_items"] is None
        rng = np.random.default_rng(2)
        wide = BucketedScorer(
            ctx, rng.normal(size=(8, 128)).astype(np.float32),
            rng.normal(size=(9000, 128)).astype(np.float32),
            max_k=5, buckets=(1, 64), backend="fused")
        # 9,000 items pad to 9,216 = 18 x 512 (not to a block multiple)
        assert wide.stats()["kernel"]["block_items"] == {
            "1": {"tile_rows": 8, "block_items": 4096},
            "64": {"tile_rows": 64, "block_items": 2048}}
        assert wide._static_args[1].shape == (9216, 128)
        # placement built the lane row once: the program converts nothing
        assert wide._static_args[2].shape == (1, 9216)
        assert wide._static_args[2].dtype == np.int32
        users = np.arange(5, dtype=np.int32)
        idx, vals = wide.score_topk(users, k=5)
        ri, rv = _reference_topk(
            np.asarray(wide._static_args[0]),
            np.asarray(wide._static_args[1])[:9000], users, 5)
        np.testing.assert_array_equal(idx, ri)
        np.testing.assert_allclose(vals, rv, rtol=1e-5, atol=1e-5)

    def test_zero_compiles_under_load(self, fused, monkeypatch):
        before = fused.compile_count

        def boom(*a, **k):
            raise AssertionError("recompile on the fused serve path")

        monkeypatch.setattr(fastpath.jax, "jit", boom)
        for batch in (1, 8, 3, 16, 40, 8):
            fused.score_topk(np.arange(batch, dtype=np.int32) % 40, k=5)
        assert fused.compile_count == before

    @pytest.mark.parametrize("batch", [1, 8, 16, 32, 64])
    def test_matches_reference_backend(self, fused, scorer, batch):
        users = (np.arange(batch, dtype=np.int32) * 7) % 40
        fi, fv = fused.score_topk(users, k=5)
        ri, rv = scorer.score_topk(users, k=5)
        np.testing.assert_array_equal(fi, ri)
        np.testing.assert_allclose(fv, rv, rtol=1e-5, atol=1e-5)

    def test_merge_counters_ride_the_readback(self, ctx, factors, scorer):
        # the fused program's third output: summed into stats() and onto
        # the batch run's Dispatch record; the reference program has none
        from predictionio_tpu.obs import tracing

        U, V = factors
        fp = BucketedScorer(ctx, U, V, max_k=5, backend="fused")
        assert fp.stats()["merge_passes"] == fp.stats()["merge_blocks"] == 0
        disp = tracing.Dispatch(1, False, 8, 0, 0.0, 0.0, 1.0)
        with tracing.scope((), disp):
            fp.score_topk(np.arange(8, dtype=np.int32), k=5)
        st = fp.stats()
        # 29 items in one block: it merges, and its busiest row places 5
        assert st["merge_blocks"] == 1 and 5 <= st["merge_passes"] <= 29
        assert disp.merge_passes == st["merge_passes"]
        assert disp.to_dict()["mergePasses"] == st["merge_passes"]
        fp.score_topk(np.arange(70, dtype=np.int32) % 40, k=5)  # 64 + 8
        assert fp.stats()["merge_blocks"] == 3
        scorer.score_topk(np.arange(8, dtype=np.int32), k=5)
        assert scorer.stats()["merge_passes"] == 0

    def test_fused_cost_annotation(self, fused):
        kern = fused.stats()["kernel"]
        # fused intensity must beat the reference backend's on the same
        # shapes — the score matrix never round-trips through HBM
        U = np.asarray(fused._static_args[0])
        V = np.asarray(fused._static_args[1])
        ref = BucketedScorer(
            MeshContext.create(), U, V, max_k=5, backend="reference"
        )
        assert kern["intensity_flops_per_byte"] > \
            ref.stats()["kernel"]["intensity_flops_per_byte"]


# one run at each rung, ms: the ALS score program as measured on a v5e
# (CHANGES.md, PR 26), and host code whose time goes with the rows
_ALS_RUN_MS = {1: 9.24, 8: 9.65, 16: 10.57, 32: 11.76, 64: 14.01}
_ROWS_RUN_MS = {b: float(b) for b in BUCKETS}


class TestAdaptiveBatcher:
    def test_burst_coalesces(self):
        """64 concurrent submitters must land in far fewer than 64
        batches (they pile up behind the run in flight), each cut at a
        ladder rung."""
        calls = []
        done = threading.Event()

        def run(batch):
            if not done.is_set():
                time.sleep(0.005)  # hold the worker so a burst can pile up
            calls.append(len(batch))
            return [q * 2 for q in batch]

        mb = MicroBatcher(run, max_batch=64)
        try:
            results = [None] * 64
            threads = [
                threading.Thread(
                    target=lambda i=i: results.__setitem__(i, mb.submit(i))
                )
                for i in range(64)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            done.set()
            assert results == [i * 2 for i in range(64)]
            assert sum(calls) == 64
            assert len(calls) < 64
        finally:
            mb.stop()

    def test_trickle_dispatches_immediately(self):
        """A lone request on a free device runs at once, inline."""
        mb = MicroBatcher(lambda b: list(b), max_batch=64)
        try:
            t0 = time.perf_counter()
            mb.submit("x")
            dt = time.perf_counter() - t0
            assert dt < 0.1
            assert mb.stats()["inline_batches"] == 1
        finally:
            mb.stop()

    @pytest.mark.parametrize("run_ms, waiting, expected", [
        # a rung costs about what the one below does (the ALS score
        # program on a v5e): rows between two rungs run as ONE dispatch,
        # which the scorer pads
        (_ALS_RUN_MS, 2, [1, 2]),
        (_ALS_RUN_MS, 7, [1, 7]),
        (_ALS_RUN_MS, 9, [1, 9]),
        # ... but not at any price: 33 x 14.0 ms against 32 x 11.8 and one
        # row through a second run -- the cut still carries
        (_ALS_RUN_MS, 33, [1, 32, 1]),
        # on a rung there is nothing to decide
        (_ALS_RUN_MS, 8, [1, 8]),
        (_ALS_RUN_MS, 64, [1, 64]),
        # time goes with the rows (host code): 9 queued run as 8 + a
        # carried 1, never 9 -> 16, and 63 walk down the ladder
        (_ROWS_RUN_MS, 9, [1, 8, 1]),
        (_ROWS_RUN_MS, 63, [1, 32, 16, 8, 1, 1, 1, 1, 1, 1, 1]),
    ])
    def test_the_cut_rounds_up_or_carries_by_its_own_run_times(
            self, monkeypatch, run_ms, waiting, expected):
        """A first run holds the batcher while ``waiting`` more rows queue
        up.  Time is the test's: a run takes what ``run_ms`` says for its
        rung, and the batcher has seen one run at every rung before."""
        clock = [1000.0]
        monkeypatch.setattr(batching, "time", types.SimpleNamespace(
            perf_counter=lambda: clock[0], time=time.time))
        calls = []
        in_first = threading.Event()
        release = threading.Event()

        def run(batch):
            if not in_first.is_set():
                in_first.set()
                release.wait(5)  # hold the batcher while the rest enqueue
            calls.append(list(batch))
            clock[0] += run_ms[bucket_for(len(batch))] / 1e3
            return list(batch)

        mb = MicroBatcher(run, max_batch=64)
        for rung, ms in run_ms.items():
            mb._rung_runs[rung].append((0, ms / 1e3))
        try:
            results = [None] * (waiting + 1)
            threads = [
                threading.Thread(
                    target=lambda i=i: results.__setitem__(i, mb.submit(i))
                )
                for i in range(waiting + 1)
            ]
            threads[0].start()
            assert in_first.wait(5)  # the batcher is held inside run([0])
            for n, t in enumerate(threads[1:], start=1):
                t.start()  # one at a time: queue order is submit order
                deadline = time.time() + 5
                while mb.depth() < n and time.time() < deadline:
                    time.sleep(0.0005)
            release.set()
            for t in threads:
                t.join(5)
            assert results == list(range(waiting + 1))
            assert [len(c) for c in calls] == expected
            # FIFO: a carried tail leads the next batch, nothing overtakes
            assert [q for c in calls for q in c] == list(range(waiting + 1))
            s = mb.stats()
            # what every dispatch of the worker but the last left behind
            assert s["carried_rows"] == sum(
                waiting - done
                for done in itertools.accumulate(expected[1:-1]))
            assert s["padded_rows"] == sum(
                bucket_for(n) - n for n in expected)
        finally:
            mb.stop()

    def test_error_propagates_to_every_waiter(self):
        def run(batch):
            raise RuntimeError("boom")

        mb = MicroBatcher(run, max_batch=8)
        try:
            with pytest.raises(RuntimeError, match="boom"):
                mb.submit("q")
        finally:
            mb.stop()

    def test_stats_counters(self):
        mb = MicroBatcher(lambda b: list(b), max_batch=8)
        try:
            for _ in range(3):
                mb.submit("q")
            stats = mb.stats()
            assert stats["queries"] == 3
            assert stats["batches"] >= 1
            assert sum(stats["batch_sizes"].values()) == stats["batches"]
        finally:
            mb.stop()
