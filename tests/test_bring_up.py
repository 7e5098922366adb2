"""Bring-up contracts (PR 21): nothing on the chip path may hide the device.

* the persistent compile cache is placed from outside or at ONE fixed,
  git-ignored path in the checkout — never a temp, pid or time path;
* under ``batching=True`` a cold-start warm-up failure raises, while a
  reload failure keeps the previous (warm) generation serving;
* ``pio deploy --fleet`` / ``pio launch`` refuse more than one local
  process on an accelerator host, without the parent touching JAX;
* ``chip_smoke.py``'s tiny preset runs on the CPU, says ``platform: cpu``
  and is never a pass; the default preset refuses to run without a TPU;
  a fast path that cannot warm fails the run, which still ends in its
  ``summary:`` line and the two-key result line the chip check reads.

(The train kernel's static dispatch rule is covered next to the kernel, in
tests/test_train_kernel.py::TestBackendResolution.)
"""

import json
import os
import subprocess
import sys
import tempfile

import jax
import pytest

from predictionio_tpu.parallel import mesh as mesh_mod
from predictionio_tpu.serving.query_server import QueryServer
from predictionio_tpu.templates.recommendation import ALSAlgorithm
from predictionio_tpu.tools import cli, launcher
from tests.test_query_server import call, trained  # noqa: F401 (fixture)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _restore(self):
        before = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", before)

    def test_env_set_code_names_no_directory(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        assert mesh_mod.configure_compile_cache() is None
        # the code set nothing: whatever was configured is untouched
        assert jax.config.jax_compilation_cache_dir == "sentinel"

    def test_env_unset_fixed_path_in_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = mesh_mod.configure_compile_cache()
        assert got == os.path.join(REPO, ".jax_compile_cache")
        assert jax.config.jax_compilation_cache_dir == got
        # the path is part of the cache key: same answer every time, and
        # nothing in it that changes between runs
        assert mesh_mod.configure_compile_cache() == got
        assert not got.startswith(tempfile.gettempdir() + os.sep)
        assert str(os.getpid()) not in os.path.basename(got)

    def test_mesh_context_create_places_it(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", None)
        mesh_mod.MeshContext.create()
        assert jax.config.jax_compilation_cache_dir == \
            mesh_mod.COMPILE_CACHE_DIR

    def test_git_ignores_it(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            ignored = {ln.strip() for ln in f}
        assert ".jax_compile_cache/" in ignored
        assert {".chip_smoke/", "chiprun_out/"} <= ignored


class TestWarmupFailure:
    def _boom(self, monkeypatch):
        def warmup(self, model):
            raise RuntimeError("Mosaic failed to compile TPU kernel (test)")

        monkeypatch.setattr(ALSAlgorithm, "warmup", warmup)

    def test_cold_start_failure_raises(self, trained, monkeypatch):  # noqa: F811
        self._boom(monkeypatch)
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            QueryServer(
                trained["engine"], storage=trained["storage"],
                ctx=trained["ctx"], batching=True,
            )

    def test_cold_start_failure_fails_pio_deploy(self, trained, monkeypatch):  # noqa: F811
        self._boom(monkeypatch)
        monkeypatch.setattr(cli, "_storage", lambda: trained["storage"])
        monkeypatch.setattr(
            cli, "load_variant", lambda args: {
                "engineFactory": "f", "datasource": {}, "algorithms": []})
        monkeypatch.setattr(
            cli, "resolve_engine_from_variant", lambda v: trained["engine"])
        monkeypatch.setattr(cli, "make_ctx", lambda v: trained["ctx"])
        rc = cli.main(["deploy", "--port", "0", "--batching"])
        assert rc != 0

    def test_plain_deploy_never_warms(self, trained, monkeypatch):  # noqa: F811
        self._boom(monkeypatch)
        qs = QueryServer(
            trained["engine"], storage=trained["storage"], ctx=trained["ctx"]
        )
        qs.stop()

    def test_reload_failure_keeps_previous_generation(
        self, trained, monkeypatch  # noqa: F811
    ):
        from predictionio_tpu.core.workflow import run_train

        qs = QueryServer(
            trained["engine"], storage=trained["storage"],
            ctx=trained["ctx"], batching=True,
        )
        port = qs.start("127.0.0.1", 0)
        base = f"http://127.0.0.1:{port}"
        try:
            first = qs._deployed.instance_id
            run_train(trained["engine"], trained["ep"], "f",
                      storage=trained["storage"], ctx=trained["ctx"])
            self._boom(monkeypatch)
            assert qs.reload() == first  # the new generation was refused
            _, ready = call("GET", base + "/readyz")
            assert ready["engineInstanceId"] == first
            assert ready["reloadDegraded"] is True
            assert ready["fastpathWarm"] is True  # the OLD one is warm
            snap = qs.counters.snapshot()
            assert snap["warmup_errors"] == 1 and snap["reload_failed"] == 1
            status, res = call(
                "POST", base + "/queries.json", {"user": "u1", "num": 3})
            assert status == 200 and len(res["itemScores"]) == 3
            assert "degraded" not in res
        finally:
            qs.stop()


class TestOneProcessPerChip:
    def test_cpu_platform_needs_no_probe(self, monkeypatch):
        def no_probe():
            raise AssertionError("probed under JAX_PLATFORMS=cpu")

        monkeypatch.setattr(launcher, "local_accelerator", no_probe)
        assert launcher.local_processes_refusal(
            4, env={"JAX_PLATFORMS": "cpu"}) is None
        assert launcher.local_processes_refusal(1, env={}) is None

    def test_accelerator_host_refuses_second_process(self, monkeypatch):
        monkeypatch.setattr(launcher, "local_accelerator", lambda: ("tpu", 1))
        msg = launcher.local_processes_refusal(2, env={})
        assert "one process at a time" in msg and "1 tpu chip" in msg
        # more chips do not help: nothing assigns chips to processes yet
        monkeypatch.setattr(launcher, "local_accelerator", lambda: ("tpu", 4))
        assert launcher.local_processes_refusal(2, env={}) is not None
        # a CPU-only host found by the probe is fine
        monkeypatch.setattr(launcher, "local_accelerator", lambda: ("cpu", 1))
        assert launcher.local_processes_refusal(2, env={}) is None

    def test_probe_is_a_child_process(self):
        # the parent's own backend state is irrelevant: the answer comes
        # from a fresh interpreter (here it inherits JAX_PLATFORMS=cpu)
        assert launcher.local_accelerator()[0] == "cpu"

    def test_verbs_refuse_before_spawning(self, monkeypatch, capsys):
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        monkeypatch.setattr(launcher, "local_accelerator", lambda: ("tpu", 1))

        def no_spawn(*a, **k):
            raise AssertionError("spawned a process")

        monkeypatch.setattr(subprocess, "Popen", no_spawn)
        assert cli.main(["launch", "-n", "2", "--", "train"]) == 2
        assert cli.main(
            ["deploy", "--port", "0", "--fleet", "2", "--batching"]) == 2
        err = capsys.readouterr().err
        assert err.count("one process at a time") == 2


def _smoke_output(stdout):
    """(summary, result) of one smoke run: the ``summary:`` line, and the
    last line, which must be the result with exactly the contract's keys."""
    lines = stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert sorted(result) == ["device", "ok"]
    assert sorted(result["device"]) == ["count", "kind", "platform"]
    assert isinstance(result["ok"], bool)
    assert isinstance(result["device"]["count"], int)
    assert lines[-2].startswith("summary: ")
    summary = json.loads(lines[-2][len("summary: "):])
    assert summary["ok"] == result["ok"]
    assert summary["device"] == result["device"]
    return summary, result


class TestChipSmoke:
    def _run(self, *argv, cwd=REPO, script=None, **extra_env):
        env = dict(os.environ, JAX_PLATFORMS="cpu", **extra_env)
        env.pop("XLA_FLAGS", None)  # one CPU device, like the sandbox
        return subprocess.run(
            [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
             *argv],
            cwd=cwd, env=env, capture_output=True, text=True, timeout=600,
        )

    def test_tiny_preset_runs_on_cpu_and_is_never_a_pass(self, tmp_path):
        r = self._run("--preset", "tiny", "--workdir", str(tmp_path / "wd"))
        assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
        assert r.stdout.startswith("platform: cpu")
        summary, result = _smoke_output(r.stdout)
        assert result == {
            "ok": False,
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        }
        assert summary["phases_ok"] is True and summary["failed"] == []
        assert summary["ok"] is False  # never a pass for the chip
        assert summary["claim"] is None
        rb = summary["facts"]["readback"]
        assert rb["fastpathWarm"] is True and rb["warmup_errors"] == 0
        assert rb["degraded"] == 0 and rb["device_dispatches"] > 0
        assert rb["compile_count_after"] == rb["compile_count_before"]
        # on the CPU every kernel ran interpreted, and the run says so
        assert all(t["mosaic"] == 0 for t in summary["pallas_traces"].values())

    def test_failed_warmup_fails_the_run_and_still_ends_in_a_summary(
        self, tmp_path
    ):
        """The fast path cannot warm (a factor placement that does not exist):
        cold-start ``batching=True`` raises, the deploy phase fails, its
        dependents are skipped — exit 1, and the output still ends in the
        summary, naming all three, and the result line."""
        wd = tmp_path / "wd"
        r = self._run("--preset", "tiny", "--workdir", str(wd),
                      PIO_SERVING_SHARDING="bogus")
        assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
        summary, _ = _smoke_output(r.stdout)
        assert summary["ok"] is False and summary["phases_ok"] is False
        assert summary["failed"] == ["deploy", "queries", "readback"]
        assert "PIO_SERVING_SHARDING" in summary["phases"]["deploy"]["error"]
        assert summary["phases"]["queries"]["skipped"] == "needs ['deploy']"
        assert summary["phases"]["readback"]["skipped"] == "needs ['queries']"
        assert summary["phases"]["train"]["ok"] is True
        assert not wd.exists()  # cleaned up on the failure path too

    def test_kernel_that_does_not_compile_fails_the_run(self, tmp_path):
        """A kernel on the path raising at compile time (planted here: the
        CPU has no Mosaic to refuse anything) fails the kernels phase and
        the run; the phases that do not need it still run and report."""
        wd = tmp_path / "wd"
        code = (
            "import sys\n"
            f"sys.argv = ['chip_smoke.py', '--preset', 'tiny', "
            f"'--workdir', {str(wd)!r}]\n"
            "import chip_smoke\n"
            "from predictionio_tpu.ops import train_kernel\n"
            "def refuse(*a, **k):\n"
            "    raise RuntimeError('Mosaic failed to compile TPU kernel')\n"
            "train_kernel.fused_train_normal_eq = refuse\n"
            "sys.exit(chip_smoke.main())\n"
        )
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert r.returncode == 1, r.stdout[-2000:] + r.stderr[-2000:]
        summary, _ = _smoke_output(r.stdout)
        assert summary["failed"] == ["kernels"] and summary["ok"] is False
        assert "Mosaic failed" in summary["phases"]["kernels"]["error"]
        assert summary["phases"]["readback"]["ok"] is True

    def test_default_preset_fails_without_a_tpu(self):
        r = self._run()
        assert r.returncode == 3
        # says what it found, on standard error; prints no result
        assert "no TPU (platform: cpu  device_kind: cpu  count: 1)" in r.stderr
        assert r.stdout == ""

    def test_alone_in_a_directory_it_fails(self, tmp_path):
        import shutil

        alone = tmp_path / "chip_smoke.py"
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
        r = self._run(cwd=str(tmp_path), script=str(alone))
        assert r.returncode != 0
        assert r.stdout == ""  # no result
