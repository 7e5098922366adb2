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

from predictionio_tpu.obs import tracing
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


# -- ISSUE 38: a dispatch's host envelope, the same in both scorers -------------


class _Recorded:
    """Stands in for one output array of a compiled program and writes down,
    in order, what the scorer asks of it."""

    def __init__(self, arr, name, log):
        self._arr, self._name, self._log = arr, name, log

    def copy_to_host_async(self):
        self._log.append(("copy", self._name))
        self._arr.copy_to_host_async()

    def block_until_ready(self):
        self._log.append(("wait", self._name))
        self._arr.block_until_ready()
        return self

    def addressable_data(self, i):
        self._log.append(("shard", self._name, i))
        return _Recorded(self._arr.addressable_data(i), self._name, self._log)

    def __array__(self, *a, **kw):
        self._log.append(("get", self._name))
        return np.asarray(self._arr)


def _bucketed_boundary(ctx, factors):
    import jax
    import jax.numpy as jnp

    U, V = factors
    sc = BucketedScorer(ctx, U, V, max_k=5)
    rng = np.random.default_rng(38)

    def direct(users):
        """The parent's envelope: a placed input, the program, one get."""
        idx, val = [], []
        for s in range(0, len(users), sc.buckets[-1]):
            chunk = users[s:s + sc.buckets[-1]]
            padded = np.zeros(bucket_for(len(chunk), sc.buckets), np.int32)
            padded[:len(chunk)] = chunk
            v, i, *_ = jax.device_get(sc._fns[len(padded)](
                *sc._static_args,
                jax.device_put(jnp.asarray(padded), sc._repl)))
            idx.append(i[:len(chunk), :sc.k])
            val.append(v[:len(chunk), :sc.k])
        return np.concatenate(idx), np.concatenate(val)

    def record(monkeypatch, rung, log, inputs):
        real = sc._fns[rung]

        def fn(*args):
            inputs.append(args[-1])
            outs = real(*args)
            return tuple(_Recorded(a, n, log) for a, n in
                         zip(outs, ("values", "indices", "merge")))

        monkeypatch.setitem(sc._fns, rung, fn)

    return types.SimpleNamespace(
        scorer=sc, rungs=sc.buckets, direct=direct, record=record,
        arg=lambda rung: rng.integers(0, U.shape[0], rung).astype(np.int32),
        over_top=lambda: rng.integers(0, U.shape[0], 70).astype(np.int32),
        # (vals, idx); the merge counters only where the fused kernel runs
        fetched=("values", "indices"))


def _packed_boundary(family):
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    if family == "latent_moe":
        from predictionio_tpu.models import latent_moe as model

        cfg = model.LatentMoEConfig.from_hf(dict(
            vocab_size=300, hidden_size=64, num_hidden_layers=2,
            intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=8, num_experts_per_tok=2, num_attention_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16), max_len=64)
    else:
        from predictionio_tpu.models import gdn_hybrid as model

        cfg = model.GDNHybridConfig.from_hf(dict(
            vocab_size=300, hidden_size=64, intermediate_size=96,
            num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=4,
            layer_types=["linear_attention"] * 3 + ["full_attention"],
            linear_num_key_heads=4, linear_num_value_heads=4,
            linear_key_head_dim=8, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
            rms_norm_eps=1e-6, hidden_act="silu", attention_bias=False,
            tie_word_embeddings=False, rope_parameters={"rope_theta": None},
        ), max_len=64)
    sc = PackedSequenceScorer(cfg, model.init_params(cfg, 38), max_k=5,
                              ladder=(64, 128), max_rows=4)
    rng = np.random.default_rng(38)

    def hists(*lengths):
        return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
                for n in lengths]

    def direct(rows):
        """`forward`: the audits' direct dispatch on a placed input."""
        idx, val = [], []
        for lo, hi in sc._chunks(rows):
            out = sc.forward(rows[lo:hi])
            idx.append(out["indices"][:hi - lo, :sc.k])
            val.append(out["values"][:hi - lo, :sc.k])
        return np.concatenate(idx), np.concatenate(val)

    def record(monkeypatch, rung, log, inputs):
        real = sc._fns[rung]

        def fn(params, flat):
            inputs.append(flat)
            return {n: _Recorded(a, n, log)
                    for n, a in real(params, flat).items()}

        monkeypatch.setitem(sc._fns, rung, fn)

    return types.SimpleNamespace(
        scorer=sc, rungs=sc.ladder, direct=direct, record=record,
        arg=lambda rung: hists(*{64: (20, 30), 128: (60, 50)}[rung]),
        over_top=lambda: hists(5, 20, 17, 3, 60, 64, 20),  # 3 dispatches
        # the leaderboard and the family's own; h_last and the rest stay
        fetched=("values", "indices") + sc._own.fetch)


@pytest.fixture(scope="module", params=["bucketed", "latent_moe",
                                        "gdn_hybrid"])
def boundary(request, ctx, factors):
    if request.param == "bucketed":
        return _bucketed_boundary(ctx, factors)
    return _packed_boundary(request.param)


class TestDispatchEnvelope:
    """The inputs ride the compiled call and the readback is queued behind
    the program at launch; no program, shape or answer changes."""

    def test_answers_are_the_direct_program_calls_bit_for_bit(self, boundary):
        sc = boundary.scorer
        for arg in [boundary.arg(r) for r in boundary.rungs] + [
                boundary.over_top()]:
            idx, val = sc.score_topk(arg, sc.k)
            ref_idx, ref_val = boundary.direct(arg)
            assert idx.dtype == ref_idx.dtype and val.dtype == ref_val.dtype
            np.testing.assert_array_equal(idx, ref_idx)
            np.testing.assert_array_equal(val, ref_val)

    def test_the_host_copy_is_requested_before_the_wait_once_an_array(
            self, boundary, monkeypatch):
        sc = boundary.scorer
        for rung in boundary.rungs:
            log, inputs = [], []
            boundary.record(monkeypatch, rung, log, inputs)
            sc.score_topk(boundary.arg(rung), sc.k)
            names = [n for n in boundary.fetched + ("merge",)
                     if ("copy", n) in log]
            assert set(names) >= set(boundary.fetched)
            n = len(names)
            # every fetched array is asked for its host copy first, once
            # (`device_get` asks again as it starts: those come after), and
            # nothing else is
            assert sorted(log[:n]) == sorted(("copy", x) for x in names)
            # the ONE wait is the get of those arrays: nobody waits for the
            # program apart, so no second wake-up follows the first
            assert not [e for e in log if e[0] == "wait"]
            assert sorted(e for e in log if e[0] == "get") == sorted(
                ("get", x) for x in names)
            assert min(i for i, e in enumerate(log) if e[0] == "get") >= n
            # the input rode the call: a host array, no placement of its own
            assert [type(x) for x in inputs] == [np.ndarray]

    def test_every_call_counts_a_queued_readback(self, boundary):
        sc = boundary.scorer
        before = sc.stats()
        sc.score_topk(boundary.over_top(), sc.k)
        after = sc.stats()
        assert after["calls"] - before["calls"] >= 2
        assert after["readbacks_queued"] == after["calls"]

    def test_two_threads_at_once_get_what_each_gets_alone_counted_exactly(
            self, boundary):
        """ISSUE 40: the batcher launches a run while the one before it is
        still in flight, so ``score_topk`` is entered by two threads at
        once.  Each gets what it gets alone, bit for bit, and the counters
        (under the scorer's own lock) lose no update."""
        import sys

        sc = boundary.scorer
        args = [boundary.arg(r) for r in boundary.rungs] + [
            boundary.over_top()]
        alone = [sc.score_topk(a, sc.k) for a in args]
        counted = ("calls", "queries", "readbacks_queued", "bucket_hits",
                   "tokens", "padded_tokens", "padded_rows")
        c0 = sc.stats()
        for a in args:
            sc.score_topk(a, sc.k)
        c1 = sc.stats()
        once = {k: ({r: c1[k][r] - c0[k][r] for r in c1[k]}
                    if isinstance(c1[k], dict) else c1[k] - c0[k])
                for k in counted if k in c1}
        assert once["calls"] >= len(args) + 1  # over_top is several
        rounds, got, errors = 12, {0: [], 1: []}, []
        start = threading.Barrier(2)

        def caller(t):
            try:
                start.wait(10)
                for _ in range(rounds):
                    # the two threads walk the rungs in opposite order, so
                    # programs of different rungs are in flight together
                    for i in (range(len(args)) if t == 0
                              else reversed(range(len(args)))):
                        got[t].append((i, sc.score_topk(args[i], sc.k)))
            except BaseException as e:  # shown by the assert below
                errors.append(e)

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=caller, args=(t,), daemon=True)
                       for t in (0, 1)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(120)
                assert not th.is_alive()
        finally:
            sys.setswitchinterval(old)
        assert errors == []
        for t in (0, 1):
            assert len(got[t]) == rounds * len(args)
            for i, (idx, val) in got[t]:
                np.testing.assert_array_equal(idx, alone[i][0])
                np.testing.assert_array_equal(val, alone[i][1])
        c2 = sc.stats()
        for k, per_pass in once.items():
            if isinstance(per_pass, dict):
                assert {r: c2[k][r] - c1[k][r] for r in c2[k]} == {
                    r: 2 * rounds * n for r, n in per_pass.items()}, k
            else:
                assert c2[k] - c1[k] == 2 * rounds * per_pass, k
        assert c2["readbacks_queued"] == c2["calls"]
        assert c2["held_launches"] == 0  # a CPU names no memory limit
        assert c2["compile_count"] == c0["compile_count"]

    def test_only_the_last_launch_of_a_run_tells_the_batcher(self, boundary):
        """ISSUE 40: the batcher times the run in flight from the launch
        the scorer stamps on its record.  Rows past the top rung are several
        launches in one run: only the last may say when the run ends."""
        sc = boundary.scorer
        woken = []
        rec = tracing.Dispatch(3, False, 1, 0, t_run=time.perf_counter(),
                               collect_s=0.0, slow_after_s=2.0)
        rec.on_launch = lambda: woken.append(
            (rec.rung, rec.more, rec.t_launch, rec.t_enqueued))
        calls = sc.stats()["calls"]
        with tracing.scope((), dispatch=rec):
            sc.score_topk(boundary.over_top(), sc.k)
        assert sc.stats()["calls"] - calls >= 2
        ((rung, more, t_launch, t_enqueued),) = woken
        assert rung == rec.rung and more is False
        # stamped around the LAST jitted call, inside its device_compute
        assert rec.dc_start < t_launch <= t_enqueued < rec.dc_end
        assert (rec.t_launch, rec.t_enqueued) == (t_launch, t_enqueued)

    def test_a_record_keeps_its_three_stages_in_order(self, boundary,
                                                      monkeypatch):
        """What crosses the boundary when has moved, the stages have not: a
        record still holds `h2d`, `device_compute`, `d2h`, one after the
        other, the launch still lies inside `pio.device_compute`, and the
        turnaround counter's two instants are still that stage's ends."""
        sc, rung = boundary.scorer, boundary.rungs[-1]
        seen, spans, real = [], [], tracing.annotation
        monkeypatch.setattr(
            tracing, "annotation",
            lambda name, **kv: seen.append(name) or real(name, **kv))

        class Rec(tracing.Dispatch):
            def add_stage(self, name, t0, t1):
                spans.append((name, t0, t1))
                super().add_stage(name, t0, t1)

        rec = Rec(9, False, 1, 0, t_run=time.perf_counter(), collect_s=0.0,
                  slow_after_s=2.0)
        with tracing.scope((), dispatch=rec):
            sc.score_topk(boundary.arg(rung), 3)
        assert seen[-4:] == ["pio.h2d", "pio.device_compute", "pio.launch",
                             "pio.d2h"]
        h2d, dc, d2h = spans[-3:]
        assert [s[0] for s in (h2d, dc, d2h)] == ["h2d", "device_compute",
                                                  "d2h"]
        assert h2d[1] <= h2d[2] <= dc[1] < dc[2] <= d2h[1] <= d2h[2]
        assert (rec.dc_start, rec.dc_end) == (dc[1], dc[2])
        assert rec.rung == rung
        assert set(rec.stages) == set(tracing.Dispatch.STAGES)

    def test_across_processes_the_placement_and_the_shard_stay(
            self, ctx, factors, monkeypatch):
        """A pod that spans processes cannot feed remote shards from one
        host's array, nor read a global one: the flag the scorer already has
        keeps `place` on the way in and `addressable_data(0)` on the way out."""
        b = _bucketed_boundary(ctx, factors)
        sc = b.scorer
        placed = []

        def place(x, *spec):
            placed.append(x)
            return ctx.replicate(x)

        sc._shard_ctx = types.SimpleNamespace(place=place)
        sc._pod_spans = True
        log, inputs = [], []
        b.record(monkeypatch, 8, log, inputs)
        users = b.arg(8)
        idx, val = sc.score_topk(users, sc.k)
        assert len(placed) == 1 and type(placed[0]) is np.ndarray
        assert type(inputs[0]) is not np.ndarray  # the placed array went in
        assert log[:4] == [("shard", "values", 0), ("shard", "indices", 0),
                           ("copy", "values"), ("copy", "indices")]
        assert {e[0] for e in log[4:]} == {"copy", "get"}  # device_get's own
        sc._pod_spans = False
        ref_idx, ref_val = b.direct(users)
        np.testing.assert_array_equal(idx, ref_idx)
        np.testing.assert_array_equal(val, ref_val)
        assert sc.stats()["readbacks_queued"] == sc.stats()["calls"] == 1
