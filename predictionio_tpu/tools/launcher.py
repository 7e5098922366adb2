"""Multi-host launch orchestration — the ``Runner.runOnSpark`` role.

The reference CLI never runs workloads in-process: it builds a
``spark-submit`` argv and lets Spark place executors across the cluster
(``tools/src/main/scala/org/apache/predictionio/tools/Runner.scala:185-334``).
The TPU-native equivalent has no cluster manager in the middle — one
process per host runs the SAME program under the ``jax.distributed``
SPMD contract (``parallel/distributed.py``):

    PIO_COORDINATOR=host0:port PIO_NUM_PROCESSES=N PIO_PROCESS_ID=i pio <verb>

``pio launch`` materializes that contract two ways:

* **local mode** (default): spawn all N processes on this machine —
  exercising real cross-process collectives (the Spark ``local[N]`` role).
  CPU platform only: an accelerator host runs ONE process that drives
  every local chip, and :func:`local_processes_refusal` says so up front.
* **--hosts h0,h1,...**: print the per-host command lines (host 0 is the
  coordinator) for the operator's parallel-ssh tooling; this image has no
  ssh, and the reference similarly delegates placement (to Spark).

Every line of a worker's output is prefixed ``[p<i>] `` so interleaved
logs stay attributable; exit status is 0 only if every worker exited 0
(signal-killed workers report negative codes and still fail the launch).
"""

from __future__ import annotations

import os
import shlex
import subprocess
import sys
import threading
import uuid
from typing import Optional, Sequence

WORKER_PREFIX = "[p{index}] "

# first backend start-up on an accelerator host takes ~15 s; a probe that
# is still silent after this long is reported, not waited on
PROBE_TIMEOUT_S = 120


def local_accelerator() -> tuple[str, int]:
    """``(platform, local device count)`` as JAX reports them on this host.

    Asked of a short-lived CHILD: a process that initialises the backend
    holds the chip until it exits, and the callers of this function are
    parents about to spawn the processes that need it.
    """
    try:
        r = subprocess.run(
            [
                sys.executable, "-c",
                "import jax; d = jax.local_devices(); "
                "print('PROBE', d[0].platform, len(d))",
            ],
            capture_output=True, text=True, timeout=PROBE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"accelerator probe did not answer within {PROBE_TIMEOUT_S} s "
            "(is another process holding the chip?)"
        ) from None
    for line in r.stdout.splitlines():
        if line.startswith("PROBE "):
            _, platform, n = line.split()
            return platform, int(n)
    raise RuntimeError(
        f"accelerator probe failed (exit {r.returncode}): "
        f"{r.stderr.strip()[-500:]}"
    )


def local_processes_refusal(
    num_processes: int, env: Optional[dict] = None
) -> Optional[str]:
    """Why ``num_processes`` local JAX processes cannot run here, or None.

    An accelerator chip belongs to one process at a time and every process
    opens all of its host's chips; nothing assigns chips to processes yet.
    So on an accelerator host a second local process fails or hangs at
    backend start-up — and under a crash-restarting supervisor does so
    forever.  ``pio deploy --fleet`` and ``pio launch`` ask here first and
    refuse in seconds instead.  ``JAX_PLATFORMS=cpu`` (tests, rehearsals)
    needs no probe: CPU processes share nothing.
    """
    if num_processes <= 1:
        return None
    env = os.environ if env is None else env
    if env.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return None
    platform, n = local_accelerator()
    if platform == "cpu":
        return None
    return (
        f"{num_processes} local processes were asked for, but this host's "
        f"{n} {platform} chip(s) can be opened by one process at a time and "
        "nothing assigns chips to processes yet: every process after the "
        "first would fail or hang at backend start-up. Run ONE process (it "
        "drives every local chip), or set JAX_PLATFORMS=cpu for a CPU "
        "rehearsal."
    )


def worker_env(
    base_env: dict,
    coordinator: str,
    num_processes: int,
    process_id: int,
    run_id: Optional[str] = None,
) -> dict:
    env = dict(base_env)
    env.update(
        {
            "PIO_COORDINATOR": coordinator,
            "PIO_NUM_PROCESSES": str(num_processes),
            "PIO_PROCESS_ID": str(process_id),
        }
    )
    if run_id is not None:
        # launch-scoped id shared by every worker: scopes cross-host
        # rendezvous artifacts (sharded-ingest map exchange) per run
        env["PIO_RUN_ID"] = run_id
    return env


def _pump(proc: subprocess.Popen, index: int, out) -> None:
    prefix = WORKER_PREFIX.format(index=index)
    for line in proc.stdout:
        out.write(prefix + line)
        out.flush()


def launch_local(
    pio_args: Sequence[str],
    num_processes: int,
    coordinator_port: int,
    env: Optional[dict] = None,
    out=None,
) -> int:
    """Run ``pio <pio_args>`` as N coordinated local processes.

    Returns 0 iff every worker exited 0. Signal-killed workers report
    negative codes on POSIX (SIGKILL=-9, SIGSEGV=-11), so ``max()`` alone
    would mask a dead worker whenever any sibling exited 0; instead any
    nonzero code — positive or negative — fails the launch, and the
    failing process indices are logged with their raw codes. A worker
    that dies takes the rendezvous with it, so the rest exit too rather
    than hanging forever — jax.distributed's barrier sees the drop.
    """
    out = out or sys.stdout
    base = dict(env if env is not None else os.environ)
    coordinator = f"127.0.0.1:{coordinator_port}"
    run = uuid.uuid4().hex[:12]
    procs: list[subprocess.Popen] = []
    pumps: list[threading.Thread] = []
    for i in range(num_processes):
        p = subprocess.Popen(
            [sys.executable, "-m", "predictionio_tpu.tools.cli", *pio_args],
            env=worker_env(base, coordinator, num_processes, i, run_id=run),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        procs.append(p)
        t = threading.Thread(target=_pump, args=(p, i, out), daemon=True)
        t.start()
        pumps.append(t)
    rcs = [p.wait() for p in procs]
    for t in pumps:
        t.join(timeout=5)
    return aggregate_exit_codes(rcs, out)


def aggregate_exit_codes(rcs: Sequence[int], out=None) -> int:
    """Collapse per-worker exit codes into the launch exit code.

    0 only when EVERY worker exited 0 — ``max()`` would hide signal-killed
    workers (negative POSIX codes: SIGKILL=-9, SIGSEGV=-11) behind any
    sibling's 0. Negative codes map to 1 (shells can't carry them).
    """
    out = out or sys.stdout
    failed = [(i, rc) for i, rc in enumerate(rcs) if rc != 0]
    if not failed:
        return 0
    for i, rc in failed:
        out.write(f"ERROR: process {i} exited with code {rc}\n")
    out.flush()
    first = failed[0][1]
    return first if first > 0 else 1


def render_host_commands(
    pio_args: Sequence[str],
    hosts: Sequence[str],
    coordinator_port: int,
) -> list[str]:
    """Per-host command lines; hosts[0] is the coordinator."""
    coordinator = f"{hosts[0]}:{coordinator_port}"
    quoted = " ".join(shlex.quote(a) for a in pio_args)
    run = uuid.uuid4().hex[:12]
    lines = [
        "# PIO_RUN_ID scopes the run's cross-host rendezvous state; it must "
        "be IDENTICAL on every host\n"
        "# and FRESH per launch attempt — re-render (or substitute a new "
        "shared id) before re-running."
    ]
    for i, host in enumerate(hosts):
        lines.append(
            f"# on {host}:\n"
            f"PIO_COORDINATOR={coordinator} "
            f"PIO_NUM_PROCESSES={len(hosts)} "
            f"PIO_PROCESS_ID={i} "
            f"PIO_RUN_ID={run} pio {quoted}"
        )
    return lines
