"""ISSUE 49's second worry: does a LOADED rung's program cost the host more
a dispatch than the same rung compiled in this process?  One deployment of a
benchmark configuration as a cell's run makes it (no load), built twice so
that the second scorer's rungs come from the program store; then, per rung,
the store's executable and one compiled here from a fresh lowering take the
same dispatch in turn — the compiled call on the warm-up's form of input
(the host array rides the call), the copies requested, one `device_get` —
and the medians of the call's return and of the whole dispatch are kept.

    chiprun -- python3 tools/chip_probes/loaded_call_cost.py <config name> [--n 400] [--rungs 1,8]

(from the root of the checkout to measure; `--shrink 500` for the ALS
configuration off the chip, where the numbers are a check of the script).
Writes chiprun_out/pr49.loaded_call_cost.<config>.json; exit 1 if an output
of the two programs differs on one input.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.getcwd()
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("config")
    ap.add_argument("--n", type=int, default=400)
    ap.add_argument("--rungs", default="")
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--seed", type=int, default=4900000801)
    args = ap.parse_args()

    import jax
    import numpy as np

    from predictionio_tpu.parallel import mesh as mesh_mod

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    if "users" in cfg and args.shrink != 1:
        cfg["users"] //= args.shrink
        cfg["items"] //= args.shrink
    engine = importlib.import_module("pio_bench.engines." + cfg["engine"])
    ctx = mesh_mod.MeshContext.create()
    dep = engine.Deployment(
        cfg, args.seed, tempfile.mkdtemp(prefix="pio_probe_"), ctx)
    first = dep.scorer()
    # the same ladder again, as a reload builds it: its rungs are loaded
    rp = type(first._rungs)(
        jax.devices()[0], first._rungs.ladder, first._lower,
        warm_args=_warm_args(first), fetch=first._rungs._fetch,
        describe=first._describe)
    ladder = list(rp.ladder)
    rungs = ([int(r) for r in args.rungs.split(",")] if args.rungs
             else ladder[:2])
    doc = {"config": args.config, "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind, "n": args.n,
           "first_build_loaded": first._rungs.programs_loaded,
           "second_build_loaded": rp.programs_loaded, "rungs": {}}
    print(json.dumps(doc), flush=True)
    warm = _warm_args(first)
    bad = 0
    for r in rungs:
        programs = {"loaded": rp.fns[r],
                    "compiled": first._lower(r).compile()}
        call_args = warm(r)
        outs = {k: jax.device_get(p(*call_args)) for k, p in programs.items()}
        same = all(np.array_equal(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(outs["loaded"]),
            jax.tree_util.tree_leaves(outs["compiled"])))
        bad += not same
        walls = {k: ([], []) for k in programs}
        for i in range(args.n):
            # in turn, the order alternating: a drift meets both alike
            for k in (("loaded", "compiled") if i % 2 else
                      ("compiled", "loaded")):
                t0 = time.perf_counter()
                out = programs[k](*call_args)
                t1 = time.perf_counter()
                back = rp._request(out)
                jax.device_get(back)
                t2 = time.perf_counter()
                walls[k][0].append(t1 - t0)
                walls[k][1].append(t2 - t0)
        row = {"outputs_equal": same}
        for k, (call, whole) in walls.items():
            row[k] = {"call_us": round(1e6 * float(np.median(call)), 1),
                      "dispatch_ms": round(1e3 * float(np.median(whole)), 4),
                      "dispatch_p25_p75_ms": [
                          round(1e3 * float(np.percentile(whole, q)), 4)
                          for q in (25, 75)]}
        doc["rungs"][str(r)] = row
        print(r, json.dumps(row), flush=True)
    out = os.path.join(ROOT, "chiprun_out",
                       f"pr49.loaded_call_cost.{args.config}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1)
    dep.stop()
    return 1 if bad else 0


def _warm_args(sc):
    """A rung's arguments in the form the warm-up and a dispatch pass them."""
    import numpy as np

    if hasattr(sc, "buckets"):
        return lambda b: sc._call_args(np.zeros(b, np.int32))
    return lambda t: sc._call_args([np.zeros(1, np.int32)], t)


if __name__ == "__main__":
    sys.exit(main())
