"""Equivalence suite: fused Pallas score kernel vs the XLA reference.

The fused kernel (gather→dot→masked running top-k in one ``pallas_call``)
must produce bit-identical *rankings* to the reference backend — indices
exactly equal, including ``lax.top_k``'s ascending-index order among tied
scores — with values allclose (the two backends may accumulate the dot
product in different orders).  On the CPU test mesh the identical kernel
runs in interpret mode via an explicit ``backend="fused"`` opt-in; the
``auto`` selector must never pick the TPU kernel on CPU by itself.

Property grid: batch rungs {1, 8, 16, 32, 64} × factor dtypes
{f32, bf16, int8} × ragged item tails, plus duplicate-score ties,
exclusion masks, and multi-block grids (items > block_items).

The merge loop ends at the first pass that places nothing (ISSUE 26):
``TestEarlyExit`` holds the item orders that decide how soon that is, on
integer-valued factors whose scores are exact in any accumulation order,
so values as well as indices must equal the reference's bit for bit, and
pins the kernel's own count of passes to a numpy model of the rule.

The sweep's tile is sized from the shapes it runs (ISSUE 30):
``TestTileGeometry`` holds the rule itself and the two things it brought —
rows filled up to whole sublane tiles by repeating the last one, and a
last block that hangs over the table's end.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import score_kernel
from predictionio_tpu.ops.quantize import quantize_factors
from predictionio_tpu.ops.topk import (
    BACKENDS, gather_score_topk, resolve_backend,
)

RUNGS = (1, 8, 16, 32, 64)
DTYPES = ("f32", "bf16", "int8")


def _factors(n_users=50, n_items=40, rank=8, seed=0):
    rng = np.random.default_rng(seed)
    U = rng.standard_normal((n_users, rank)).astype(np.float32)
    V = rng.standard_normal((n_items, rank)).astype(np.float32)
    return U, V


def _both(U, V, u_idx, k, dtype="f32", item_mask=None, seed_scale=None):
    """(fused result, reference result) on identical quantized inputs."""
    Uq, us = quantize_factors(U, dtype)
    Vq, vs = quantize_factors(V, dtype)
    kw = dict(item_mask=item_mask, u_scale=us, v_scale=vs)
    fused = gather_score_topk(Uq, Vq, u_idx, k, backend="fused", **kw)
    ref = gather_score_topk(Uq, Vq, u_idx, k, backend="reference", **kw)
    return fused, ref


def _assert_ranking_equal(fused, ref, dtype):
    fv, fi = np.asarray(fused[0]), np.asarray(fused[1])
    rv, ri = np.asarray(ref[0]), np.asarray(ref[1])
    np.testing.assert_array_equal(
        fi, ri, err_msg=f"[{dtype}] fused ranking differs from reference"
    )
    # values: same math, possibly different accumulation order — allclose,
    # not bit-equal (documented tolerance; the *ranking* is the contract)
    np.testing.assert_allclose(fv, rv, rtol=1e-5, atol=1e-5)


class TestEquivalence:
    @pytest.mark.parametrize("batch", RUNGS)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rungs_match_reference(self, batch, dtype):
        U, V = _factors(seed=batch)
        rng = np.random.default_rng(batch + 1)
        u_idx = rng.integers(0, U.shape[0], batch).astype(np.int32)
        fused, ref = _both(U, V, u_idx, 10, dtype=dtype)
        _assert_ranking_equal(fused, ref, dtype)

    @pytest.mark.parametrize("n_items", (1, 7, 29, 37))
    def test_ragged_item_tail(self, n_items):
        # non-multiple-of-8 catalogs: the kernel pads internally and the
        # padded tail must never appear in the top-k
        U, V = _factors(n_items=n_items, seed=n_items)
        k = min(5, n_items)
        u_idx = np.arange(min(8, U.shape[0]), dtype=np.int32)
        fused, ref = _both(U, V, u_idx, k)
        _assert_ranking_equal(fused, ref, "f32")
        assert np.asarray(fused[1]).max() < n_items

    def test_duplicate_score_ties_exact(self):
        # identical item rows ⇒ exactly tied scores; both backends must
        # break ties by ascending item index (lax.top_k semantics)
        U, _ = _factors(seed=3)
        rng = np.random.default_rng(4)
        base = rng.standard_normal((5, 8)).astype(np.float32)
        V = np.repeat(base, 6, axis=0)  # 30 items in 5 groups of 6 clones
        u_idx = np.arange(8, dtype=np.int32)
        fused, ref = _both(U, V, u_idx, 12)
        _assert_ranking_equal(fused, ref, "f32-ties")

    def test_exclusion_mask_never_wins(self):
        U, V = _factors()
        mask = np.zeros(V.shape[0], dtype=bool)
        mask[::2] = True  # exclude every even item
        u_idx = np.arange(16, dtype=np.int32)
        fused, ref = _both(U, V, u_idx, 8, item_mask=mask)
        _assert_ranking_equal(fused, ref, "f32-mask")
        assert not np.any(np.asarray(fused[1]) % 2 == 0)

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_multi_block_grid(self, dtype):
        # items > block_items forces multiple grid steps: the running
        # top-k accumulator must merge across blocks, including a
        # cross-block tie (item 3 cloned into the last block)
        U, V = _factors(n_items=64, seed=9)
        V[60] = V[3]
        Uq, us = quantize_factors(U, dtype)
        Vq, vs = quantize_factors(V, dtype)
        u_idx = np.arange(8, dtype=np.int32)
        fused = score_kernel.fused_gather_score_topk(
            Uq, Vq, u_idx, 10, u_scale=us, v_scale=vs, block_items=16
        )
        ref = gather_score_topk(
            Uq, Vq, u_idx, 10, backend="reference", u_scale=us, v_scale=vs
        )
        _assert_ranking_equal(fused, ref, dtype)

    def test_k_equals_items(self):
        U, V = _factors(n_items=12)
        u_idx = np.arange(4, dtype=np.int32)
        fused, ref = _both(U, V, u_idx, 12)
        _assert_ranking_equal(fused, ref, "f32-fullk")


def _int_factors(n_users, n_items, rank=4, seed=0, hi=4):
    """Small-integer factors: every score is an integer well inside
    f32's (and bf16's, int8's) exact range, so the two backends cannot
    differ by an accumulation order, and ties are everywhere."""
    rng = np.random.default_rng(seed)
    U = rng.integers(-hi, hi + 1, (n_users, rank)).astype(np.float32)
    V = rng.integers(-hi, hi + 1, (n_items, rank)).astype(np.float32)
    return U, V


def _ranked(scores_by_item, n_users=8):
    """(U, V) whose score for user b and item i is (b + 1) * scores[i]:
    every row ranks the items the same way."""
    V = np.zeros((len(scores_by_item), 4), np.float32)
    V[:, 0] = scores_by_item
    U = np.zeros((n_users, 4), np.float32)
    U[:, 0] = np.arange(1, n_users + 1)
    return U, V


def _merge_model(S, k, block):
    """The merge's cost by its own rule, from a (B, n_pad) score matrix
    (excluded slots at NEG_INF): per block, every row inserts its
    candidates above its k-th value largest first, one a pass, so a block
    costs as many inserting passes as its busiest row has inserts.
    Returns (passes, blocks that merged anything)."""
    b = S.shape[0]
    tops = [[score_kernel.NEG_INF] * k for _ in range(b)]  # sorted desc
    passes = blocks = 0
    for lo in range(0, S.shape[1], block):
        busiest = 0
        for r in range(b):
            inserts = 0
            for v in sorted(S[r, lo:lo + block], reverse=True):
                if not v > tops[r][-1]:
                    break
                tops[r] = sorted(tops[r] + [v], reverse=True)[:k]
                inserts += 1
            busiest = max(busiest, inserts)
        passes += busiest
        blocks += busiest > 0
    return passes, blocks


def _fused_with_stats(U, V, u_idx, k, block, dtype="f32", item_mask=None):
    Uq, us = quantize_factors(U, dtype)
    Vq, vs = quantize_factors(V, dtype)
    fused = score_kernel.fused_gather_score_topk(
        Uq, Vq, u_idx, k, item_mask, u_scale=us, v_scale=vs,
        block_items=block, with_stats=True,
    )
    ref = gather_score_topk(
        Uq, Vq, u_idx, k, item_mask=item_mask, backend="reference",
        u_scale=us, v_scale=vs,
    )
    return fused, ref


def _assert_identical(fused, ref):
    np.testing.assert_array_equal(np.asarray(fused[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(fused[0]), np.asarray(ref[0]))


class TestEarlyExit:
    BLOCK = 16
    N_BLOCKS = 6
    N = BLOCK * N_BLOCKS

    @pytest.mark.parametrize("k", (5, 16, 20))
    def test_ascending_every_block_places(self, k):
        # the worst input: each block's scores all beat what came before,
        # so every block places min(k, block) entries in every row — the
        # fixed loop's cost, never more
        U, V = _ranked(np.arange(1, self.N + 1))
        u_idx = np.arange(8, dtype=np.int32)
        fused, ref = _fused_with_stats(U, V, u_idx, k, self.BLOCK)
        _assert_identical(fused, ref)
        assert list(np.asarray(fused[2])) == [
            self.N_BLOCKS * min(k, self.BLOCK), self.N_BLOCKS]

    @pytest.mark.parametrize("k", (5, 16, 20))
    def test_descending_only_the_first_blocks_place(self, k):
        # best first: the leaderboard is full after ceil(k / block) blocks
        # and every later block costs its one check
        U, V = _ranked(np.arange(self.N, 0, -1))
        u_idx = np.arange(8, dtype=np.int32)
        fused, ref = _fused_with_stats(U, V, u_idx, k, self.BLOCK)
        _assert_identical(fused, ref)
        assert list(np.asarray(fused[2])) == [k, -(-k // self.BLOCK)]

    def test_block_wholly_masked_places_nothing(self):
        # the best items sit in a block that is excluded whole
        scores = np.arange(1, self.N + 1)
        U, V = _ranked(scores)
        mask = np.zeros(self.N, bool)
        mask[-self.BLOCK:] = True
        u_idx = np.arange(8, dtype=np.int32)
        fused, ref = _fused_with_stats(
            U, V, u_idx, 5, self.BLOCK, item_mask=mask)
        _assert_identical(fused, ref)
        assert np.asarray(fused[1]).max() < self.N - self.BLOCK
        assert list(np.asarray(fused[2])) == [
            (self.N_BLOCKS - 1) * 5, self.N_BLOCKS - 1]

    def test_everything_masked_is_one_check_a_block(self):
        # (no winner exists, so there is no ranking to hold the reference
        # to: an excluded slot's value is all the two agree on)
        U, V = _ranked(np.arange(1, self.N + 1))
        fused, ref = _fused_with_stats(
            U, V, np.arange(8, dtype=np.int32), 5, self.BLOCK,
            item_mask=np.ones(self.N, bool))
        np.testing.assert_array_equal(
            np.asarray(fused[0]), np.asarray(ref[0]))
        assert list(np.asarray(fused[2])) == [0, 0]

    def test_ties_with_the_kth_value_across_a_block_edge(self):
        # k = 4 and the 4th value is 3: its equals at the end of block 0
        # and the start of block 1 must not displace it (strict >), while a
        # 5 in block 1 enters behind the earlier 5s (ties by index)
        scores = np.ones(self.N)
        scores[[0, 1]] = 5
        scores[[2, 3, self.BLOCK - 1, self.BLOCK]] = 3
        scores[self.BLOCK + 1] = 5
        U, V = _ranked(scores)
        fused, ref = _fused_with_stats(
            U, V, np.arange(8, dtype=np.int32), 4, self.BLOCK)
        _assert_identical(fused, ref)
        assert list(np.asarray(fused[1])[0]) == [0, 1, self.BLOCK + 1, 2]
        assert list(np.asarray(fused[2])) == [4 + 1, 2]

    @pytest.mark.parametrize("batch", (1, 8, 64))
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rungs_identical_on_exact_scores(self, batch, dtype):
        U, V = _int_factors(50, self.N, seed=batch)
        rng = np.random.default_rng(batch + 7)
        u_idx = rng.integers(0, 50, batch).astype(np.int32)
        mask = rng.random(self.N) < 0.2
        fused, ref = _fused_with_stats(
            U, V, u_idx, 10, self.BLOCK, dtype=dtype, item_mask=mask)
        if dtype == "int8":  # per-row scales: the scores are not integers
            _assert_ranking_equal(fused, ref, dtype)
        else:
            _assert_identical(fused, ref)

    @pytest.mark.parametrize("batch,k,seed", [
        (1, 10, 0), (8, 10, 1), (8, 20, 2), (64, 5, 3)])
    def test_pass_count_equals_the_rule(self, batch, k, seed):
        U, V = _int_factors(50, self.N, seed=seed)
        rng = np.random.default_rng(seed + 11)
        u_idx = rng.integers(0, 50, batch).astype(np.int32)
        mask = rng.random(self.N) < 0.1
        fused, ref = _fused_with_stats(
            U, V, u_idx, k, self.BLOCK, item_mask=mask)
        _assert_identical(fused, ref)
        S = np.where(mask[None, :], score_kernel.NEG_INF, U[u_idx] @ V.T)
        passes, blocks = _merge_model(S, k, self.BLOCK)
        assert list(np.asarray(fused[2])) == [passes, blocks]
        # the loop's trips: the passes and at most one ending check a
        # block — under the k a block that the fixed loop ran
        assert 0 < blocks <= self.N_BLOCKS
        assert passes + self.N_BLOCKS < k * self.N_BLOCKS

    def test_stats_are_off_by_default_and_fused_only(self):
        U, V = _int_factors(50, self.N)
        u_idx = np.arange(8, dtype=np.int32)
        assert len(gather_score_topk(U, V, u_idx, 5, backend="fused")) == 2
        out = gather_score_topk(
            U, V, u_idx, 5, backend="fused", with_stats=True)
        assert np.asarray(out[2]).shape == (2,)
        with pytest.raises(ValueError, match="with_stats"):
            gather_score_topk(
                U, V, u_idx, 5, backend="reference", with_stats=True)


class TestTileGeometry:
    N = 96  # one block of its own size; 16 divides it; 40 leaves a tail
    BLOCKS = (96, 16, 40)

    @pytest.mark.parametrize("batch,rank,dtype,n_pad,want", [
        (1, 128, "float32", 5_700_096, (8, 4096)),  # als-wgde-d128
        (8, 128, "float32", 5_700_096, (8, 4096)),
        (16, 128, "float32", 5_700_096, (16, 4096)),
        (64, 128, "float32", 5_700_096, (64, 2048)),  # rows narrow it
        (64, 2048, "bfloat16", 129_536, (64, 512)),  # the sequence head
        (1, 10, "float32", 59_392, (8, 4096)),  # a row is whole lane tiles
        (8, 64, "float32", 304, (8, 304)),  # a 300-item IVF cluster
        (3, 128, "float32", 1024, (8, 1024)),  # at most the table
    ])
    def test_rule(self, batch, rank, dtype, n_pad, want):
        assert score_kernel.tile_geometry(batch, rank, dtype, n_pad) == want

    @pytest.mark.parametrize("dtype", ("float32", "bfloat16", "int8"))
    @pytest.mark.parametrize("rank", (8, 100, 128, 256, 2048, 7168))
    def test_rule_keeps_whole_tiles_under_the_budget(self, rank, dtype):
        itemsize = jnp.dtype(dtype).itemsize
        lanes = -(-rank // 128) * 128
        for batch in (1, 2, 7, 8, 9, 16, 33, 64, 256):
            for n_items in (5, 300, 512, 513, 5000, 129_280, 5_700_000):
                n_pad = score_kernel.pad_block_items(n_items)
                rows, block = score_kernel.tile_geometry(
                    batch, rank, dtype, n_pad)
                assert rows >= batch and rows % 8 == 0 and rows - batch < 8
                # whole lane tiles, or the whole (small) table
                assert block == n_pad or block % score_kernel.BLOCK_I == 0
                assert 0 < block <= n_pad
                assert block == min(score_kernel.BLOCK_I, n_pad) or (
                    score_kernel._live_tile_bytes(rows, block, lanes, itemsize)
                    <= score_kernel.VMEM_TILE_BUDGET)
                assert block * lanes * itemsize <= max(
                    score_kernel.BLOCK_BYTES,
                    score_kernel.BLOCK_I * lanes * itemsize)

    @pytest.mark.parametrize("n_items,want", [
        (300, 304), (512, 512), (513, 1024), (129_280, 129_536),
        (5_700_000, 5_700_096)])
    def test_padding_does_not_follow_the_block(self, n_items, want):
        # tables, clusters and shards pad to BLOCK_I whatever block the
        # sweep takes: seeded weights and byte counts hang on these shapes
        assert score_kernel.pad_block_items(n_items) == want

    @pytest.mark.parametrize("batch", (1, 3, 8, 9))
    @pytest.mark.parametrize("block", BLOCKS)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_rows_and_blocks_identical_on_exact_scores(
            self, batch, block, dtype, assert_same_topk):
        U, V = _int_factors(50, self.N, seed=batch + block)
        rng = np.random.default_rng(batch * 100 + block)
        u_idx = rng.integers(0, 50, batch).astype(np.int32)
        mask = rng.random(self.N) < 0.2
        fused, ref = _fused_with_stats(
            U, V, u_idx, 10, block, dtype=dtype, item_mask=mask)
        assert np.asarray(fused[0]).shape == (batch, 10)
        if dtype == "int8":
            # per-row scales: integer scores that tie exactly come out an
            # ulp apart, and on XLA:CPU the two backends round them apart
            assert_same_topk(fused[1], fused[0], ref[1], ref[0])
        else:
            _assert_identical(fused, ref)

    @pytest.mark.parametrize("batch", (1, 3, 8, 9))
    @pytest.mark.parametrize("block", BLOCKS)
    def test_repeated_rows_add_no_merge_pass(self, batch, block):
        # the tile is filled to 8 or 16 rows by repeating the last real
        # row: the counters must be those of the real rows alone
        U, V = _int_factors(50, self.N, seed=block)
        rng = np.random.default_rng(batch + block)
        u_idx = rng.integers(0, 50, batch).astype(np.int32)
        mask = rng.random(self.N) < 0.1
        fused, ref = _fused_with_stats(U, V, u_idx, 10, block, item_mask=mask)
        _assert_identical(fused, ref)
        S = np.where(mask[None, :], score_kernel.NEG_INF, U[u_idx] @ V.T)
        assert list(np.asarray(fused[2])) == list(_merge_model(S, 10, block))

    @pytest.mark.parametrize("batch", (1, 3, 8))
    def test_tie_with_the_kth_value_across_a_ragged_block_edge(self, batch):
        # blocks of 40 over 96 items: edges at 40 and 80, the last block
        # hangs 24 lanes over the end.  k = 4 and the 4th value is 3: its
        # equals on both sides of each edge must not displace it, and a 5
        # just past the first edge enters behind the earlier 5s
        scores = np.ones(self.N)
        scores[[0, 1]] = 5
        scores[[2, 3, 39, 40, 79, 80, 95]] = 3
        scores[41] = 5
        U, V = _ranked(scores)
        fused, ref = _fused_with_stats(
            U, V, np.arange(batch, dtype=np.int32), 4, 40)
        _assert_identical(fused, ref)
        assert list(np.asarray(fused[1])[0]) == [0, 1, 41, 2]
        assert list(np.asarray(fused[2])) == [4 + 1, 2]

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_masked_item_in_the_ragged_tail(self, dtype):
        # ascending scores put the winners in the block that hangs over
        # the end; the best three are excluded, and what the overhang read
        # (of V, the mask row, an int8 scale row) must never win
        U, V = _ranked(np.arange(1, self.N + 1))
        mask = np.zeros(self.N, bool)
        mask[-3:] = True
        fused, ref = _fused_with_stats(
            U, V, np.arange(3, dtype=np.int32), 5, 40, dtype=dtype,
            item_mask=mask)
        _assert_ranking_equal(fused, ref, dtype)
        assert list(np.asarray(fused[1])[0]) == list(
            range(self.N - 4, self.N - 9, -1))

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_unpadded_table_with_a_ragged_block(self, dtype, assert_same_topk):
        # 1,000 items pad to 1,024 here; blocks of 384 leave a tail of 256
        U, V = _int_factors(20, 1000, seed=5)
        mask = np.random.default_rng(6).random(1000) < 0.3
        fused, ref = _fused_with_stats(
            U, V, np.arange(3, dtype=np.int32), 10, 384, dtype=dtype,
            item_mask=mask)
        assert_same_topk(fused[1], fused[0], ref[1], ref[0])
        assert np.asarray(fused[1]).max() < 1000

    @pytest.mark.parametrize("backend", ("fused", "reference"))
    def test_mask_as_the_lane_row_placement_builds(self, backend):
        U, V = _int_factors(50, self.N)
        u_idx = np.arange(3, dtype=np.int32)
        mask = np.random.default_rng(1).random(self.N) < 0.3
        row = score_kernel.item_mask_row(mask)
        assert row.shape == (1, self.N) and row.dtype == np.int32
        a = gather_score_topk(U, V, u_idx, 7, item_mask=mask, backend=backend)
        b = gather_score_topk(U, V, u_idx, 7, item_mask=row, backend=backend)
        _assert_identical(a, b)

    def test_mask_of_another_form_is_refused(self):
        U, V = _int_factors(50, self.N)
        u_idx = np.arange(3, dtype=np.int32)
        with pytest.raises(ValueError, match="item_mask"):
            gather_score_topk(
                U, V, u_idx, 5, backend="fused",
                item_mask=np.zeros((1, self.N), bool))


class TestBackendResolution:
    def test_auto_never_fused_on_cpu(self):
        # the CPU test mesh: auto must fall back to the reference path,
        # not silently run the TPU kernel through the interpreter
        import jax

        if jax.default_backend() != "tpu":
            assert resolve_backend("auto") == "reference"
            assert resolve_backend(None) == "reference"

    def test_pio_native_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PIO_NATIVE", "0")
        assert resolve_backend("fused") == "reference"

    def test_invalid_backend_raises(self):
        with pytest.raises(ValueError, match="backend="):
            resolve_backend("vectorized")
        assert set(BACKENDS) == {"fused", "reference", "auto"}


class TestQuantize:
    def test_int8_round_trip_error_bounded(self):
        U, _ = _factors()
        q, scale = quantize_factors(U, "int8")
        assert q.dtype == np.int8 and scale.dtype == np.float32
        back = q.astype(np.float32) * scale
        # per-row max error ≤ half a quantization step
        step = np.abs(U).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(back - U) <= step / 2 + 1e-7)

    def test_zero_row_is_stable(self):
        Z = np.zeros((3, 8), dtype=np.float32)
        q, scale = quantize_factors(Z, "int8")
        assert np.all(q == 0) and np.all(np.isfinite(scale))

    def test_f32_passthrough(self):
        U, _ = _factors()
        q, scale = quantize_factors(U, "f32")
        assert q is U and scale is None
