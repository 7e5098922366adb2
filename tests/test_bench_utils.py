"""bench.py helper sanity: the artifact math the driver records per round."""

import numpy as np

import bench


class TestUtilizationModel:
    def test_scales_and_reports_against_known_peaks(self):
        base = bench._utilization(
            n_ratings=1_000_000, n_users=50_000, n_items=10_000, rank=10,
            iterations=3, dtype="f32", dt=10.0, n_chips=1,
            device_kind="TPU v5 lite",
        )
        assert base["model_flops_per_sec_per_chip"] > 0
        assert base["model_hbm_gbps_per_chip"] > 0
        assert 0 < base["mfu"] < 1 and 0 < base["hbm_util"] < 1
        # double the ratings at fixed wall time → ~double the throughput
        double = bench._utilization(
            n_ratings=2_000_000, n_users=50_000, n_items=10_000, rank=10,
            iterations=3, dtype="f32", dt=10.0, n_chips=1,
            device_kind="TPU v5 lite",
        )
        ratio = (
            double["model_flops_per_sec_per_chip"]
            / base["model_flops_per_sec_per_chip"]
        )
        assert 1.9 < ratio < 2.0  # entity terms keep it just under 2x
        # a device that is not in the table — a CPU, another TPU generation
        # — must NOT report utilization against v5e's peaks
        for kind in ("cpu", "TPU v4", "rocm"):
            unk = bench._utilization(
                n_ratings=1_000_000, n_users=50_000, n_items=10_000,
                rank=10, iterations=3, dtype="f32", dt=10.0, n_chips=1,
                device_kind=kind,
            )
            assert unk["mfu"] is None and unk["hbm_util"] is None

    def test_bf16_halves_gather_traffic(self):
        f32 = bench._utilization(
            1_000_000, 50_000, 10_000, 10, 3, "f32", 10.0, 1, "TPU v5 lite"
        )
        bf16 = bench._utilization(
            1_000_000, 50_000, 10_000, 10, 3, "bf16", 10.0, 1, "TPU v5 lite"
        )
        assert bf16["model_hbm_gbps_per_chip"] < f32["model_hbm_gbps_per_chip"]


class TestSampleIds:
    def test_distributions_cover_range(self):
        rng = np.random.default_rng(0)
        for dist in ("uniform", "zipf"):
            ids = bench._sample_ids(rng, 1000, 50_000, dist, s=1.1)
            assert ids.min() >= 0 and ids.max() < 1000
        # zipf concentrates mass on low ids far beyond uniform
        rng = np.random.default_rng(0)
        z = bench._sample_ids(rng, 1000, 100_000, "zipf", s=1.1)
        u = bench._sample_ids(rng, 1000, 100_000, "uniform", s=1.1)
        assert (z < 50).mean() > 2 * (u < 50).mean()


class TestMeasuredUtilization:
    def test_xla_cost_analysis_positive_and_scales_with_ratings(self):
        from predictionio_tpu.models.als import (
            ALSConfig,
            dense_step_cost_analysis,
        )
        from predictionio_tpu.parallel.mesh import MeshContext

        ctx = MeshContext.create()
        small = bench._make_interactions("uniform", 300, 120, 4_000)
        big = bench._make_interactions("uniform", 300, 120, 16_000)
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

    def test_device_busy_parses_device_planes_only(self, tmp_path):
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        space = xplane_pb2.XSpace()
        dev = space.planes.add()
        dev.name = "/device:TPU:0"
        line = dev.lines.add()
        for dur in (3_000_000, 2_000_000):  # ps
            ev = line.events.add()
            ev.duration_ps = dur
        host = space.planes.add()
        host.name = "/host:CPU"
        hline = host.lines.add()
        hline.events.add().duration_ps = 999_000_000_000
        d = tmp_path / "plugins" / "profile" / "x"
        d.mkdir(parents=True)
        (d / "vm.xplane.pb").write_bytes(space.SerializeToString())
        busy, n = bench._device_busy_seconds(str(tmp_path))
        assert n == 1
        assert abs(busy - 5e-6) < 1e-12  # host plane excluded

    def test_device_busy_none_without_device_plane(self, tmp_path):
        from tensorflow.tsl.profiler.protobuf import xplane_pb2

        space = xplane_pb2.XSpace()
        host = space.planes.add()
        host.name = "/host:CPU"
        d = tmp_path / "p"
        d.mkdir()
        (d / "vm.xplane.pb").write_bytes(space.SerializeToString())
        busy, n = bench._device_busy_seconds(str(tmp_path))
        assert busy is None and n == 0


class TestBenchMatrix:
    def _load(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(bench.__file__),
                            "tools", "bench_matrix.py")
        spec = importlib.util.spec_from_file_location("bench_matrix", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_any_fallback_cell_never_touches_tpu_artifact(self, tmp_path,
                                                          monkeypatch):
        """Cells stage in a side file; the TPU artifact is replaced only
        when EVERY cell is genuine — a run that lost its chip (tpu cells
        then cpu cells) must leave prior TPU evidence intact."""
        bm = self._load()
        out = tmp_path / "BENCH_TPU_MANUAL.json"
        out.write_text('{"platform": "tpu", "value": 3208643.4}')
        monkeypatch.setattr(bm, "OUT", str(out))
        results = iter(
            [{"platform": "tpu", "fallback": False, "value": 9e6}]
            + [{"platform": "cpu", "fallback": True, "value": 1.0}] * 10
        )
        monkeypatch.setattr(bm, "run_cell", lambda name, o: next(results))
        rc = bm.main()
        assert rc == 1  # not all on tpu
        import json as jsonlib

        # prior TPU evidence untouched; everything staged aside
        assert jsonlib.loads(out.read_text())["value"] == 3208643.4
        staging = tmp_path / "BENCH_TPU_MANUAL.staging.json"
        assert len(jsonlib.loads(staging.read_text())["cells"]) == \
            len(bm.CELLS)

    def test_all_tpu_run_promotes_to_primary_artifact(self, tmp_path,
                                                      monkeypatch):
        bm = self._load()
        out = tmp_path / "BENCH_TPU_MANUAL.json"
        monkeypatch.setattr(bm, "OUT", str(out))
        monkeypatch.setattr(
            bm, "run_cell",
            lambda name, o: {"platform": "tpu", "fallback": False,
                             "value": 5e6},
        )
        assert bm.main() == 0
        import json as jsonlib

        assert len(jsonlib.loads(out.read_text())["cells"]) == len(bm.CELLS)
        # staging was promoted (renamed), not duplicated
        assert not (tmp_path / "BENCH_TPU_MANUAL.staging.json").exists()

    def test_cells_pin_every_matrix_axis(self):
        """An ambient BENCH_REBALANCE/BENCH_DTYPE from a prior manual run
        must never change what a labeled cell measures."""
        bm = self._load()
        for name, overrides in bm.CELLS:
            assert "BENCH_REBALANCE" in overrides, name
            assert "BENCH_DTYPE" in overrides, name
            assert "BENCH_DIST" in overrides, name
