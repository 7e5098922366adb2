"""Pod-scale serving: 2-process pod mesh bit-identity + shard-aware router.

Two proofs the pod tentpole rests on:

* **Bit-identical two-tier merge across processes** — a 2-process
  ``jax.distributed`` CPU mesh (2 virtual devices per process, Gloo
  collectives) serves a 4-shard / 2-host-group plan through the real
  ``BucketedScorer``; its global top-k must be BIT-identical to the
  single-process flat sharded merge, and equal to the single-process
  replicated reference computed by the parent (bit-identical on a TPU,
  within ``CPU_WIDTH_MAX_ULP`` on XLA:CPU, whose dot rounds by matrix
  width), for every bucket rung × factor dtype — and the measured
  cross-host merge traffic
  must equal the ``H·B·k·8`` derivation in docs/perf_roofline.md exactly
  (the flat ``S·B·local_k`` collective never crosses hosts).
* **Shard-aware router fan-out** — replicas advertising a pod host group
  on /readyz get exactly their own group's queries (stable user-key
  hash), the ``client:pod:merge`` chaos site fires on the group hop, and
  a kill -9 of one host group's process degrades that group to
  fleet-wide fallback with ZERO client-visible failures until it heals.
"""

import json
import os
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
import zlib

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_USERS, N_ITEMS, RANK, K = 40, 320, 8, 10
SEED = 11
DTYPES = ("f32", "bf16", "int8")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def wait_until(pred, timeout=20.0, interval=0.05, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


# -- part 1: 2-process pod mesh vs single-process replicated reference --------

# same preamble contract as tests/test_distributed.py: 2 virtual CPU
# devices per process, platform pinned at the config level
POD_WORKER = f"""
import os, sys
sys.path.insert(0, {REPO!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")
import json
import numpy as np
from predictionio_tpu.parallel import distributed

assert distributed.initialize()
from predictionio_tpu.parallel.mesh import MeshContext
from predictionio_tpu.ops.quantize import quantize_factors
from predictionio_tpu.serving import sharding as _sharding
from predictionio_tpu.serving.fastpath import BucketedScorer

N_USERS, N_ITEMS, RANK, K = {N_USERS}, {N_ITEMS}, {RANK}, {K}
ctx = MeshContext.create()
assert ctx.n_devices == 4, ctx.n_devices
rng = np.random.default_rng({SEED})
U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
V = rng.standard_normal((N_ITEMS, RANK)).astype(np.float32)
batches = [rng.integers(0, N_USERS, n).astype(np.int32) for n in (1, 13)]
plan = _sharding.build_plan(N_ITEMS, 4, host_groups=2)
assert plan.host_groups == 2 and plan.shards_per_group == 2
out = {{}}
for dtype in {DTYPES!r}:
    Uq, us = quantize_factors(U, dtype)
    Vq, vs = quantize_factors(V, dtype)
    sc = BucketedScorer(
        ctx, Uq, Vq, max_k=K, buckets=(1, 8), factor_dtype=dtype,
        user_scale=us, item_scale=vs, sharding="sharded", plan=plan,
    )
    assert sc._pod and sc._pod_spans
    cells = []
    for users in batches:
        idx, vals = sc.score_topk(users, K)
        cells.append({{
            "idx": np.asarray(idx).tolist(),
            "vals": np.asarray(vals, np.float64).tolist(),
        }})
    pod = sc.stats()["pod"]
    # the (H, B, k) tier-2 gather is the ONLY cross-host traffic:
    # H*b*k*8 bytes per dispatch over rungs b=1 once and b=8 twice
    expect = 2 * 1 * K * 8 + 2 * (2 * 8 * K * 8)
    assert pod["cross_host_merge_bytes"] == expect, (pod, expect)
    assert pod["dispatches"] == 3, pod
    assert pod["host_groups"] == 2 and pod["process_count"] == 2
    out[dtype] = {{"cells": cells,
                  "pod_bytes": pod["cross_host_merge_bytes"]}}
print("POD_RESULT " + json.dumps(out))
print("POD_OK", distributed.process_index())
"""


def _launch_worker(script_path, pid: int, port: int) -> subprocess.Popen:
    env = dict(os.environ)
    env.update(
        PIO_COORDINATOR=f"127.0.0.1:{port}",
        PIO_NUM_PROCESSES="2",
        PIO_PROCESS_ID=str(pid),
    )
    return subprocess.Popen(
        [sys.executable, str(script_path)], env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )


def _run_worker_pair(script_path, timeout=180) -> list[str]:
    port = free_port()
    procs = [
        _launch_worker(script_path, 0, port),
        _launch_worker(script_path, 1, port),
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
            assert p.returncode == 0, out
    finally:
        for p in procs:  # never leak workers stuck in the rendezvous
            if p.poll() is None:
                p.kill()
    return outs


def _single_process_reference(sharding: str) -> dict:
    """Single-process answers for the worker's exact inputs: ``replicated``
    (one full-width scan), or ``sharded`` — the FLAT single-tier merge over
    the same 4 item blocks the pod workers score."""
    from predictionio_tpu.ops.quantize import quantize_factors
    from predictionio_tpu.parallel.mesh import MeshContext
    from predictionio_tpu.serving import sharding as _sharding
    from predictionio_tpu.serving.fastpath import BucketedScorer

    rng = np.random.default_rng(SEED)
    U = rng.standard_normal((N_USERS, RANK)).astype(np.float32)
    V = rng.standard_normal((N_ITEMS, RANK)).astype(np.float32)
    batches = [rng.integers(0, N_USERS, n).astype(np.int32) for n in (1, 13)]
    ctx = MeshContext.create()
    plan = _sharding.build_plan(N_ITEMS, 4) if sharding == "sharded" else None
    ref = {}
    for dtype in DTYPES:
        Uq, us = quantize_factors(U, dtype)
        Vq, vs = quantize_factors(V, dtype)
        sc = BucketedScorer(
            ctx, Uq, Vq, max_k=K, buckets=(1, 8), factor_dtype=dtype,
            user_scale=us, item_scale=vs, sharding=sharding, plan=plan,
        )
        assert not sc._pod
        ref[dtype] = [sc.score_topk(users, K) for users in batches]
    return ref


@pytest.fixture(scope="module")
def pod_results(tmp_path_factory) -> list[dict]:
    """One 2-process pod run shared by the identity tests: each worker's
    parsed ``POD_RESULT`` (per dtype: the cells and the tier-2 bytes)."""
    script = tmp_path_factory.mktemp("pod") / "pod_worker.py"
    script.write_text(POD_WORKER)
    results = []
    for out in _run_worker_pair(script):
        assert "POD_OK" in out, out
        line = next(
            ln for ln in out.splitlines() if ln.startswith("POD_RESULT ")
        )
        results.append(json.loads(line[len("POD_RESULT "):]))
    return results


def _exactly_equal(idx_a, val_a, idx_b, val_b, what):
    np.testing.assert_array_equal(
        idx_a, idx_b, err_msg=f"indices diverge from {what}"
    )
    np.testing.assert_array_equal(
        np.asarray(val_a, np.float64), np.asarray(val_b, np.float64),
        err_msg=f"values diverge from {what}",
    )


def _assert_pod_equals(
    pod_results, ref: dict, what: str, same=_exactly_equal
) -> None:
    """Every worker's every cell == ``ref``, indices and values, under
    ``same`` (by default EXACTLY)."""
    for got in pod_results:
        for dtype in DTYPES:
            for cell, (ref_idx, ref_vals) in zip(
                got[dtype]["cells"], ref[dtype]
            ):
                same(
                    np.asarray(cell["idx"], np.int32), cell["vals"],
                    ref_idx, ref_vals, f"{what} for {dtype}",
                )


def test_pod_mesh_bit_identical_to_flat_merge(pod_results):
    """2-process two-tier pod merge == single-process FLAT sharded merge,
    bit for bit, across bucket rungs × factor dtypes: both score the same
    four item blocks, so the second tier may change nothing — and the
    measured cross-host merge moved (H, B, k) entries, not
    (S, B, local_k)."""
    for got in pod_results:
        for dtype in DTYPES:
            # tier-2 bytes: S/H × local_k/k smaller than the flat gather
            flat = 4 * (1 + 8 + 8) * K * 8.0
            assert got[dtype]["pod_bytes"] * 2 == flat
    _assert_pod_equals(
        pod_results, _single_process_reference("sharded"), "the flat merge"
    )


def test_pod_mesh_bit_identical_to_replicated_reference(
    pod_results, assert_same_topk
):
    """2-process pod serving == single-process replicated across bucket
    rungs × factor dtypes — the guarantee docs/operations.md gives for the
    ``PIO_SERVING_SHARDING=replicated`` rollback.

    The replicated scan contracts against the 320-wide matrix and the pod
    against 80-wide shard blocks: identical on a TPU, within
    ``CPU_WIDTH_MAX_ULP`` on XLA:CPU (tests/conftest.py says why, and what
    was measured).  The flat merge above scores the pod's own four blocks,
    so that comparison stays bit-exact.
    """
    _assert_pod_equals(
        pod_results, _single_process_reference("replicated"),
        "the replicated reference", same=assert_same_topk,
    )


# -- part 2: shard-aware router + chaos ---------------------------------------

POD_STUB = """
import os
from predictionio_tpu.common.http import HttpService, json_response

svc = HttpService("podstub")
GROUP = int(os.environ["POD_STUB_GROUP"])
GROUPS = int(os.environ["POD_STUB_GROUPS"])
SPANS = os.environ.get("POD_STUB_SPANS") == "1"

@svc.route("GET", r"/readyz")
def readyz(req):
    return json_response(200, {
        "status": "ready", "generation": 1, "fastpathWarm": True,
        "draining": False,
        "pod": {"group": GROUP, "groups": GROUPS, "fingerprint": "fp-pod",
                "processIndex": GROUP, "processCount": GROUPS,
                "spansProcesses": SPANS},
    })

@svc.route("POST", r"/queries\\.json")
def queries(req):
    return json_response(200, {"group": GROUP})

svc.start("127.0.0.1", int(os.environ["POD_STUB_PORT"]))
svc.serve_forever()
"""


def _spawn_stub(
    port: int, group: int, groups: int = 2, spans: bool = False
) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.update(
        POD_STUB_PORT=str(port),
        POD_STUB_GROUP=str(group),
        POD_STUB_GROUPS=str(groups),
        POD_STUB_SPANS="1" if spans else "0",
    )
    return subprocess.Popen([sys.executable, "-c", POD_STUB], env=env)


def _post_query(base: str, user: str):
    req = urllib.request.Request(
        base + "/queries.json",
        data=json.dumps({"user": user, "num": 3}).encode(),
        method="POST", headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        return r.status, json.loads(r.read().decode())


def _users_for_group(group: int, groups: int = 2, n: int = 5) -> list[str]:
    out = []
    i = 0
    while len(out) < n:
        u = f"u{i}"
        if zlib.crc32(u.encode()) % groups == group:
            out.append(u)
        i += 1
    return out


@pytest.fixture()
def pod_fleet():
    """Two stub replica subprocesses (one per host group) + a router."""
    from predictionio_tpu.serving.router import Router

    ports = [free_port(), free_port()]
    procs = {g: _spawn_stub(ports[g], g) for g in (0, 1)}
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    router = Router(urls, telemetry=False)
    router.health_interval_ms = 50.0
    router.probe_timeout_ms = 500.0
    router.eject_after = 2
    router.readmit_after = 2
    router.slow_start_s = 0.2
    port = router.start("127.0.0.1", 0)
    base = f"http://127.0.0.1:{port}"
    try:
        yield router, base, procs, ports
    finally:
        router.stop()
        for p in procs.values():
            if p.poll() is None:
                p.kill()


def _pod_ready(router, groups=2):
    st = router.stats()
    pod = st.get("pod")
    return (
        st["available"] == 2 and pod is not None
        and pod.get("groups") == groups
    )


def test_router_fans_each_query_to_owning_group(pod_fleet):
    router, base, _procs, _ports = pod_fleet
    wait_until(lambda: _pod_ready(router), msg="pod map on both replicas")
    for group in (0, 1):
        for user in _users_for_group(group):
            status, body = _post_query(base, user)
            assert status == 200
            # exactly ONE host group saw the query — and it is the owner
            assert body["group"] == group, (user, body)
    pod = router.stats()["pod"]
    assert pod["queriesRouted"] == {"0": 5, "1": 5}
    assert pod["fallbackBroadcasts"] == 0
    # no user key → no owner group → plain fleet-wide pick: neither the
    # per-group counters nor the fallback counter move
    req = urllib.request.Request(
        base + "/queries.json", data=b'{"num": 3}', method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=10) as r:
        assert r.status == 200
    pod = router.stats()["pod"]
    assert pod["queriesRouted"] == {"0": 5, "1": 5}
    assert pod["fallbackBroadcasts"] == 0


def test_pod_merge_fault_site_fires_and_retries_absorb(pod_fleet):
    from predictionio_tpu.common import faults

    router, base, _procs, _ports = pod_fleet
    wait_until(lambda: _pod_ready(router), msg="pod map on both replicas")
    plan = faults.FaultPlan(
        faults.parse_spec("site=client:pod:merge,kind=drop,times=1"),
        seed=7,
    )
    faults.install(plan)
    try:
        for user in _users_for_group(0, n=3):
            status, body = _post_query(base, user)
            assert status == 200  # free transport retries absorb the tear
        fired = plan.stats()["rules"][0]["fired"]
        assert fired == 1, plan.stats()
    finally:
        faults.clear()


def test_host_group_loss_degrades_without_client_failures(pod_fleet):
    """kill -9 of host group 1's process: its queries fall back
    fleet-wide with zero client-visible failures; once the process heals
    the router returns to group-affine routing."""
    router, base, procs, ports = pod_fleet
    wait_until(lambda: _pod_ready(router), msg="pod map on both replicas")
    g1_users = _users_for_group(1, n=8)
    status, body = _post_query(base, g1_users[0])
    assert status == 200 and body["group"] == 1

    procs[1].kill()  # SIGKILL: the kill -9 contract, no drain
    procs[1].wait(10)
    # mid-outage load: every query must still answer 200 — refused
    # connects retry free onto group 0 (the documented degrade)
    for user in g1_users:
        status, body = _post_query(base, user)
        assert status == 200, (user, status)
        assert body["group"] == 0  # absorbed by the surviving group
    # retries keep the primary pick's group affinity: every mid-outage
    # query lands off-owner at least once (either its retry pick after
    # the dead owner, or — once the breaker opens — its primary pick),
    # and each such attempt is charged to the fallback counter
    assert (
        router.stats()["pod"]["fallbackBroadcasts"] >= len(g1_users)
    ), router.stats()["pod"]
    wait_until(
        lambda: router.stats()["available"] == 1,
        msg="dead replica ejected",
    )
    baseline_fb = router.stats()["pod"]["fallbackBroadcasts"]
    for user in g1_users[:3]:
        status, body = _post_query(base, user)
        assert status == 200 and body["group"] == 0
    # ejected owner → picks degrade fleet-wide and are counted
    assert router.stats()["pod"]["fallbackBroadcasts"] >= baseline_fb + 3

    # heal: same port, same group identity; readmission via the health
    # gate, then group-affine routing resumes
    procs[1] = _spawn_stub(ports[1], 1)

    def _healed():
        try:
            status, body = _post_query(base, g1_users[0])
        except (urllib.error.URLError, OSError):
            return False
        return status == 200 and body["group"] == 1

    wait_until(_healed, timeout=30.0, msg="group 1 back in rotation")


def test_router_ignores_process_spanning_pod_adverts():
    """A replica whose pod mesh spans ``jax.distributed`` processes can
    only score in SPMD lockstep — routing any single query to one of its
    processes would deadlock the cross-host collective.  The router must
    drop such pod adverts and serve the fleet as plain replicas."""
    from predictionio_tpu.serving.router import Router

    ports = [free_port(), free_port()]
    procs = {g: _spawn_stub(ports[g], g, spans=True) for g in (0, 1)}
    router = Router(
        [f"http://127.0.0.1:{p}" for p in ports], telemetry=False
    )
    router.health_interval_ms = 50.0
    router.probe_timeout_ms = 500.0
    port = router.start("127.0.0.1", 0)
    base = f"http://127.0.0.1:{port}"
    try:
        # `available` alone races startup (replicas begin admitted);
        # `generation` starts None and is only ever set from a
        # successful probe round-trip against a live stub
        wait_until(
            lambda: router.stats()["available"] == 2
            and all(
                r["generation"] is not None
                for r in router.stats()["replicas"]
            ),
            msg="both replicas probed",
        )
        assert router.stats()["pod"] is None
        # queries still answer — as a plain fleet, never group-affine
        for user in _users_for_group(0) + _users_for_group(1):
            status, _body = _post_query(base, user)
            assert status == 200
        assert router.stats()["pod"] is None
        assert all(
            r["podGroup"] is None
            for r in router.stats()["replicas"]
        )
    finally:
        router.stop()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
