"""Equivalence suite: fused Pallas training kernel vs the XLA reference.

The fused gather-contract kernel (``ops/train_kernel.py``) replaces the
per-bucket ``V[idx]`` gather + batched einsum of the dense ALS half-step
with one ``pallas_call`` whose opposite-factor block sits VMEM-resident.
Its contraction is the reference einsum's exact ``dot_general`` — same
operand order, same cast points, f32 accumulation — so the suite holds
the two backends to BIT-identical normal equations and solved factors
for f32 and int8 compute dtypes (int8 dequantizes to f32 before any
inexact multiply).  The one documented tolerance: the bf16 implicit
``A`` term multiplies two inexact bf16 operands, and XLA may keep that
product in f32 across a fusion boundary when the comparison runs
eagerly — bf16 implicit is held allclose at bf16-epsilon order instead
(end-to-end under jit it comes out bit-equal too, which
``test_train_als_fused_matches_reference`` exercises).

On the CPU test mesh the identical kernel body runs via ``interpret=``;
the ``auto`` selector must never pick the fused path on CPU by itself.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from predictionio_tpu.ops import train_kernel
from predictionio_tpu.ops.quantize import quantize_factors_jax

DTYPES = ("f32", "bf16", "int8")


def _bucket(n_b, D, n_opp, k, seed=0, mask_p=0.7):
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, n_opp, (n_b, D)).astype(np.int32)
    rat = rng.uniform(1, 5, (n_b, D)).astype(np.float32)
    msk = (rng.uniform(size=(n_b, D)) < mask_p).astype(np.float32)
    V = rng.normal(size=(n_opp, k)).astype(np.float32)
    return jnp.asarray(idx), jnp.asarray(rat), jnp.asarray(msk), \
        jnp.asarray(V)


def _reference_normal_eq(idx, rat, msk, opp, implicit, alpha):
    """The dense half-step's per-bucket math, verbatim from
    ``models/als.py:_dense_half_step_local`` (cast order and all)."""
    f32 = jnp.float32
    Vg = opp[idx]
    w = msk.astype(Vg.dtype)
    if implicit:
        cw = (alpha * rat).astype(Vg.dtype) * w
        A = jnp.einsum(
            "edk,edl->ekl", Vg * cw[:, :, None], Vg,
            preferred_element_type=f32,
        )
        b = jnp.einsum(
            "edk,ed->ek", Vg, (1.0 + alpha * rat).astype(Vg.dtype) * w,
            preferred_element_type=f32,
        )
        cnt = jnp.zeros(idx.shape[0], f32)
    else:
        W = Vg * w[:, :, None]
        A = jnp.einsum("edk,edl->ekl", W, W, preferred_element_type=f32)
        b = jnp.einsum(
            "edk,ed->ek", W, rat.astype(Vg.dtype),
            preferred_element_type=f32,
        )
        cnt = msk.sum(-1)
    return A, b, cnt


def _both(idx, rat, msk, V, dtype, implicit, alpha=2.0, **kw):
    q, scale = quantize_factors_jax(V, dtype)
    opp = q if scale is None else q.astype(jnp.float32) * scale
    ref = _reference_normal_eq(idx, rat, msk, opp, implicit, alpha)
    fused = train_kernel.fused_train_normal_eq(
        idx, rat, msk, q, scale, implicit=implicit, alpha=alpha, **kw
    )
    return fused, ref


def _assert_equal(fused, ref, dtype, implicit):
    for name, f, r in zip("A b cnt".split(), fused, ref):
        f, r = np.asarray(f), np.asarray(r)
        if dtype == "bf16" and implicit and name == "A":
            # documented tolerance: the kernel materializes the bf16
            # weight product; an eager reference may keep it f32 across
            # the fusion into the dot (see module docstring).  The atol
            # absorbs near-cancelling sums over the D axis whose bf16
            # per-term rounding (~0.4% of term magnitude) doesn't shrink.
            np.testing.assert_allclose(f, r, rtol=2e-2, atol=0.5)
        else:
            np.testing.assert_array_equal(
                f, r, err_msg=f"[{dtype}/{implicit}] {name} differs"
            )


class TestNormalEqEquivalence:
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("implicit", (False, True))
    def test_matches_reference(self, dtype, implicit):
        args = _bucket(13, 24, 37, 5, seed=1)
        fused, ref = _both(*args, dtype, implicit)
        _assert_equal(fused, ref, dtype, implicit)

    @pytest.mark.parametrize(
        "n_b,D", [(1, 4), (5, 8), (8, 16), (17, 33), (32, 7)]
    )
    def test_ragged_shapes(self, n_b, D):
        """Entity counts off the block grid (padding rows solve to zero
        contributions) and odd bucket widths."""
        args = _bucket(n_b, D, 29, 6, seed=n_b * 31 + D)
        fused, ref = _both(*args, "f32", False)
        _assert_equal(fused, ref, "f32", False)

    def test_masked_slots_contribute_exactly_zero(self):
        """A masked slot's idx must be irrelevant: pointing dead slots at
        a different row cannot change any output bit."""
        idx, rat, msk, V = _bucket(9, 12, 21, 4, seed=3, mask_p=0.5)
        scrambled = jnp.where(msk.astype(bool), idx, (idx + 7) % 21)
        a1 = train_kernel.fused_train_normal_eq(idx, rat, msk, V)
        a2 = train_kernel.fused_train_normal_eq(scrambled, rat, msk, V)
        for x, y in zip(a1, a2):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_fully_masked_bucket_is_all_zero(self):
        idx, rat, _, V = _bucket(6, 10, 15, 4, seed=4)
        zero = jnp.zeros_like(rat)
        A, b, cnt = train_kernel.fused_train_normal_eq(idx, rat, zero, V)
        assert not np.any(np.asarray(A))
        assert not np.any(np.asarray(b))
        assert not np.any(np.asarray(cnt))

    def test_multi_block_d_grid(self):
        """Explicit block_d < D sweeps the inner grid dim; accumulation
        over d steps must still match the reference allclose (the
        documented trade: chunked f32 accumulation order)."""
        args = _bucket(8, 32, 25, 4, seed=5)
        fused, ref = _both(*args, "f32", False, block_d=8)
        for f, r in zip(fused, ref):
            np.testing.assert_allclose(
                np.asarray(f), np.asarray(r), rtol=1e-5, atol=1e-5
            )


class TestGatherRows:
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_matches_xla_gather_bitwise(self, dtype):
        rng = np.random.default_rng(7)
        V = jnp.asarray(rng.normal(size=(33, 6)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 33, (77,)).astype(np.int32))
        q, scale = quantize_factors_jax(V, dtype)
        opp = q if scale is None else q.astype(jnp.float32) * scale
        want = opp[idx].astype(jnp.float32)
        got = train_kernel.fused_gather_rows(q, idx, scale)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_unaligned_length_pads_and_slices(self):
        rng = np.random.default_rng(8)
        V = jnp.asarray(rng.normal(size=(10, 4)).astype(np.float32))
        idx = jnp.asarray(rng.integers(0, 10, (13,)).astype(np.int32))
        got = train_kernel.fused_gather_rows(V, idx, block_n=8)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(V)[idx])


class TestBackendResolution:
    def test_auto_never_fused_on_cpu(self, monkeypatch):
        monkeypatch.delenv("PIO_TRAIN_KERNEL", raising=False)
        assert jax.default_backend() != "tpu"
        assert train_kernel.resolve_backend() == "reference"
        assert train_kernel.resolve_backend("auto") == "reference"

    def test_env_selector(self, monkeypatch):
        monkeypatch.setenv("PIO_TRAIN_KERNEL", "fused")
        assert train_kernel.resolve_backend() == "fused"
        monkeypatch.setenv("PIO_TRAIN_KERNEL", "reference")
        assert train_kernel.resolve_backend() == "reference"

    def test_explicit_argument_wins_over_env(self, monkeypatch):
        monkeypatch.setenv("PIO_TRAIN_KERNEL", "reference")
        assert train_kernel.resolve_backend("fused") == "fused"

    def test_pio_native_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PIO_NATIVE", "0")
        assert train_kernel.resolve_backend("fused") == "reference"

    def test_invalid_backend_raises(self):
        with pytest.raises(ValueError, match="PIO_TRAIN_KERNEL"):
            train_kernel.resolve_backend("mosaic")

    def test_alsconfig_validates_knobs(self, monkeypatch):
        from predictionio_tpu.models.als import ALSConfig

        monkeypatch.delenv("PIO_TRAIN_KERNEL", raising=False)
        monkeypatch.delenv("PIO_ALS_COMPUTE_DTYPE", raising=False)
        cfg = ALSConfig()
        assert cfg.train_kernel == "auto"
        assert cfg.compute_dtype == "f32"
        monkeypatch.setenv("PIO_ALS_COMPUTE_DTYPE", "int8")
        assert ALSConfig().compute_dtype == "int8"
        with pytest.raises(ValueError):
            ALSConfig(train_kernel="nope")
        with pytest.raises(ValueError):
            ALSConfig(compute_dtype="fp8")

    def test_vmem_budget_counts_padded_tiles(self):
        # a rank-10 f32 row is 40 B of data but one 128-lane row of an
        # (8, 128) tile in VMEM: 512 B
        assert train_kernel.resident_bytes(8, 10, "f32") == 8 * 128 * 4
        assert train_kernel.resident_bytes(59_000, 10, "f32") == \
            59_000 * 512
        # rows pad to the dtype's sublane count (bf16: 16, int8: 32), and
        # int8 carries its f32 scale column, itself a lane-padded tile
        assert train_kernel.resident_bytes(10, 8, "bf16") == 16 * 128 * 2
        assert train_kernel.resident_bytes(100, 8, "int8") == \
            128 * 128 * 1 + 104 * 128 * 4
        # the budget holds the block twice (Pallas double-buffers it)
        edge = train_kernel.VMEM_RESIDENT_BUDGET // (2 * 512)
        assert train_kernel.fits_vmem(edge, 10, "f32")
        assert not train_kernel.fits_vmem(edge + 8, 10, "f32")
        # MovieLens-25M width fits on neither side
        assert not train_kernel.fits_vmem(59_000, 10, "f32")
        assert not train_kernel.fits_vmem(162_000, 10, "f32")

    def test_explicit_fused_that_does_not_fit_raises(self, monkeypatch):
        from predictionio_tpu.models import als as als_mod

        monkeypatch.setenv("PIO_TRAIN_KERNEL", "fused")
        cfg = als_mod.ALSConfig(rank=10)
        assert als_mod._resolve_side_backend(cfg, 8_000) == "fused"
        with pytest.raises(ValueError, match="padded tiles"):
            als_mod._resolve_side_backend(cfg, 59_000)
        # the kill switch is still an explicit operator override
        monkeypatch.setenv("PIO_NATIVE", "0")
        assert als_mod._resolve_side_backend(cfg, 59_000) == "reference"

    def test_auto_resolves_by_platform_and_budget(self, monkeypatch):
        monkeypatch.delenv("PIO_TRAIN_KERNEL", raising=False)
        kw = dict(rank=10, compute_dtype="f32")
        # off-TPU auto is the reference whatever the size
        assert train_kernel.resolve_backend("auto", n_opp=8_000, **kw) == \
            "reference"
        monkeypatch.setattr(train_kernel, "use_fused_default", lambda: True)
        assert train_kernel.resolve_backend("auto", n_opp=8_000, **kw) == \
            "fused"
        assert train_kernel.resolve_backend("auto", n_opp=59_000, **kw) == \
            "reference"
        # a compiled kernel cannot gather one packed (bf16/int8) row: on a
        # TPU auto gives way and an explicit request raises with the reason
        for dt in ("bf16", "int8"):
            assert train_kernel.resolve_backend(
                "auto", n_opp=8_000, rank=10, compute_dtype=dt
            ) == "reference"
            with pytest.raises(ValueError, match="packs rows"):
                train_kernel.resolve_backend(
                    "fused", n_opp=8_000, rank=10, compute_dtype=dt
                )

    def test_per_side_decision_lands_in_train_stats(self, monkeypatch):
        from predictionio_tpu.models import als as als_mod

        cfg = als_mod.ALSConfig(rank=10, train_kernel="auto")
        train_kernel.reset_stats()
        try:
            als_mod._record_train_kernel_stats(
                cfg, "fused", "reference", 162_000, 8_000
            )
            st = train_kernel.stats()
            assert st["backend_u_solve"] == "fused"
            assert st["backend_v_solve"] == "reference"
            assert st["backend"] == "mixed"
        finally:
            train_kernel.reset_stats()


class TestInt8RoundTrip:
    def test_error_bounded_by_half_scale(self):
        rng = np.random.default_rng(9)
        V = jnp.asarray(rng.normal(size=(64, 10)).astype(np.float32))
        q, scale = quantize_factors_jax(V, "int8")
        deq = np.asarray(q).astype(np.float32) * np.asarray(scale)
        err = np.abs(deq - np.asarray(V))
        bound = np.asarray(scale) * 0.5 + 1e-7
        assert np.all(err <= bound)

    def test_zero_row_is_stable(self):
        V = jnp.zeros((4, 6), jnp.float32)
        q, scale = quantize_factors_jax(V, "int8")
        assert not np.any(np.asarray(q))
        assert np.all(np.asarray(scale) == 1.0)


class TestEndToEnd:
    """Solved factors, fused vs reference, through the real solvers on
    the CPU mesh (interpret-mode kernel under jit/shard_map)."""

    @pytest.fixture(scope="class")
    def ctx(self):
        from predictionio_tpu.parallel.mesh import MeshContext

        return MeshContext.create()

    @pytest.fixture(scope="class")
    def inter(self):
        from predictionio_tpu.data.batch import Interactions
        from predictionio_tpu.data.bimap import BiMap

        rng = np.random.default_rng(11)
        n_u, n_i, n_r = 48, 36, 500
        return Interactions(
            user=rng.integers(0, n_u, n_r).astype(np.int32),
            item=rng.integers(0, n_i, n_r).astype(np.int32),
            rating=rng.uniform(1, 5, n_r).astype(np.float32),
            t=np.zeros(n_r),
            user_map=BiMap.string_int(f"u{i}" for i in range(n_u)),
            item_map=BiMap.string_int(f"i{i}" for i in range(n_i)),
        )

    @pytest.mark.parametrize("solver,dtype,implicit", [
        ("dense", "f32", False),
        ("dense", "bf16", True),
        ("dense", "int8", False),
        ("segment", "f32", True),
        ("segment", "bf16", False),
        ("segment", "int8", True),
    ])
    def test_train_als_fused_matches_reference(
        self, ctx, inter, solver, dtype, implicit
    ):
        from predictionio_tpu.models.als import ALSConfig, train_als

        def run(backend):
            m = train_als(ctx, inter, ALSConfig(
                rank=4, iterations=2, seed=3, solver=solver,
                implicit=implicit, compute_dtype=dtype,
                train_kernel=backend,
            ))
            return np.asarray(m.user_factors), np.asarray(m.item_factors)

        Ur, Ir = run("reference")
        Uf, If = run("fused")
        # under jit both backends fuse identically — observed bit-equal
        # for every dtype; bf16 keeps a tolerance in case a future XLA
        # moves the rounding point at a fusion boundary
        if dtype == "bf16":
            np.testing.assert_allclose(Uf, Ur, rtol=1e-3, atol=1e-3)
            np.testing.assert_allclose(If, Ir, rtol=1e-3, atol=1e-3)
        else:
            np.testing.assert_array_equal(Uf, Ur)
            np.testing.assert_array_equal(If, Ir)

    def test_reference_env_is_one_env_rollback(
        self, ctx, inter, monkeypatch
    ):
        from predictionio_tpu.models.als import ALSConfig, train_als

        monkeypatch.setenv("PIO_TRAIN_KERNEL", "reference")
        cfg = ALSConfig(rank=3, iterations=1)
        assert cfg.train_kernel == "reference"
        m = train_als(ctx, inter, cfg)
        assert m.user_factors.shape[1] == 3
        assert train_kernel.stats().get("backend") == "reference"


class TestStatsBridge:
    def test_record_and_bridge(self):
        from predictionio_tpu.obs import bridges, metrics as obs_metrics

        train_kernel.reset_stats()
        try:
            train_kernel.record_stats(
                backend="fused", compute_dtype="int8",
                resident_bytes=84_000.0,
                intensity_flop_per_byte=39.5,
            )
            reg = obs_metrics.MetricsRegistry()
            bridges.bridge_train_kernel(reg, train_kernel.stats)
            text = reg.render_prometheus()
            assert 'pio_train_kernel_info{backend="fused"' in text
            assert 'compute_dtype="int8"' in text
            assert "pio_train_kernel_resident_bytes 84000" in text
            assert "pio_train_kernel_intensity_flop_per_byte 39.5" in text
        finally:
            train_kernel.reset_stats()

    def test_bridge_silent_before_first_train(self):
        from predictionio_tpu.obs import bridges, metrics as obs_metrics

        train_kernel.reset_stats()
        reg = obs_metrics.MetricsRegistry()
        bridges.bridge_train_kernel(reg, train_kernel.stats)
        assert "pio_train_kernel" not in reg.render_prometheus()
