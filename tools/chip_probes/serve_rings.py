"""One run of a benchmark cell, exactly as `benchmark/run.py` makes it, that
also keeps what the server says about itself before it is stopped: the whole
of `GET /` (the fast path's `merge_passes`, `merge_blocks`, `calls` and
`bucket_hits`, the batcher's counters) and the batcher's dispatch ring
(`GET /trace/dispatches.json`: each run's rung, `mergePasses` and stage
walls).  `benchmark/findings/keep_dispatches.py` with the `fastpath` block
added, for either family; run from the root of the checkout to be measured,
so the parent's unpacked archive can be driven by this copy:

    cd <checkout> && python3 <repo>/tools/chip_probes/serve_rings.py <out.json> \
        --workload wgde-d128.serve-steady --seed N --seconds 40 --trace 0

Nothing of this is part of a run's result; the result line is still the last
line of stdout.
"""
import json
import os
import sys
import urllib.error
import urllib.request

BENCH = os.path.join(os.getcwd(), "benchmark")
sys.path[:0] = [BENCH, os.getcwd()]  # run.py, and the program

import importlib  # noqa: E402

import run as bench_run  # noqa: E402

out = sys.argv.pop(1)
# the family of the cell's configuration, as run.py will find it
cell = sys.argv[sys.argv.index("--workload") + 1]
config = next(w["config"] for w in
              bench_run.load_json(os.getcwd(), "BENCHMARK.json")["workloads"]
              if w["name"] == cell)
family = importlib.import_module("pio_bench.engines." + bench_run.load_json(
    BENCH, "configs", config + ".json")["engine"])
stop = family.Deployment.stop


def _get(url):  # not every family's engine module has one of its own
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read().decode())


def keep_then_stop(self):
    try:
        doc = _get(self.base + "/trace/dispatches.json")
        doc["root"] = self.root()
        with open(out, "w") as f:
            json.dump(doc, f)
    except urllib.error.URLError as e:
        print(f"serve_rings: {e}", file=sys.stderr)
    stop(self)


family.Deployment.stop = keep_then_stop
sys.exit(bench_run.main())
