#!/usr/bin/env python3
"""A builder's tool (PR 25): one run of a cell, exactly as `run.py` makes it,
that also keeps what the batcher recorded about its own dispatches.

    python3 benchmark/findings/keep_dispatches.py <out.json> [--hunt <seconds>] --workload ... (run.py's arguments)

Before the deployment is stopped (after the window and the audit) it reads
`GET /trace/dispatches.json`, the batcher's counters from `GET /` and the
newest request traces, and writes them to <out.json>; a program without that
route (the parent of PR 25) leaves the file out.  Nothing of this is part of
a run's result.

`--hunt <seconds>` is for catching a short stall, never for a measured run:
it lowers the batcher's slow-dispatch threshold to <seconds> in this process
(the program's own is `max(2 s, 8 x ewma_run)`, a constant), so the server's
log gets every thread's stack for any run that long, and it polls the route
ten times a second, keeping under `inFlightSeen` the first sight of each run
held that long: the stages it had finished and the live stack of its thread
(faulthandler's dump lists only the newest 100 threads).
"""
import json
import os
import sys
import threading
import urllib.error

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]  # run.py, and the program

import run as bench_run  # noqa: E402
from pio_bench.engines import als_recommendation as family  # noqa: E402

out = sys.argv.pop(1)
hunt_s = None
if sys.argv[1:2] == ["--hunt"]:
    hunt_s = float(sys.argv[2])
    del sys.argv[1:3]
stop = family.Deployment.stop
seen = {}


def hunt(dep):
    """Poll for a run that has held the batcher for `hunt_s`; keep the
    first sight of each."""
    while not dep.hunt_over.wait(0.1):
        try:
            cur = family._get(
                dep.base + "/trace/dispatches.json?limit=1")["inFlight"]
        except (OSError, ValueError):
            continue
        if cur and cur["heldMs"] > hunt_s * 1e3:
            seen.setdefault(cur["seq"], cur)


if hunt_s is not None:
    from predictionio_tpu.serving.batching import MicroBatcher

    MicroBatcher.SLOW_FLOOR_S, MicroBatcher.SLOW_MULT = hunt_s, 0.0
    init = family.Deployment.__init__

    def init_then_hunt(self, *a, **kw):
        init(self, *a, **kw)
        self.hunt_over = threading.Event()
        threading.Thread(target=hunt, args=(self,), daemon=True).start()

    family.Deployment.__init__ = init_then_hunt


def keep_then_stop(self):
    if hunt_s is not None:
        self.hunt_over.set()
    try:
        doc = family._get(self.base + "/trace/dispatches.json")
        doc["batching"] = self.root()["batching"]
        # the newest request traces (a traced run samples every request):
        # each names the dispatch that ran it
        doc["recentTraces"] = [
            {k: t.get(k) for k in ("requestId", "status", "wallMs", "meta")}
            for t in self.traces()["traces"]]
        doc["inFlightSeen"] = list(seen.values())
        with open(out, "w") as f:
            json.dump(doc, f)
    except urllib.error.URLError as e:
        print(f"keep_dispatches: {e}", file=sys.stderr)
    stop(self)


family.Deployment.stop = keep_then_stop
sys.exit(bench_run.main())
