"""Is every executable the program store serves still the program this tree
lowers to?  The store's key (`predictionio_tpu/serving/program_store.py`)
holds what determines a rung's program WITHOUT tracing it — the package's
source text, versions, the configuration, the arguments' abstract values,
flags — so it cannot see what none of those names: a dependency patched in
place, code patched at run time, a variable read somewhere new.  This tool
sees it: for each rung of the given benchmark configurations' ladders it
builds the scorer as a cell's run deploys it (which LOADS from the store
where the store holds the rung), lowers the rung's program afresh and
compares the sha256 of its text with the one the entry has kept since it was
compiled.

    python3 tools/verify_program_store.py <config name> [<config name> ...] [--seed N] [--out FILE]

from the root of the checkout whose store is to be checked, on the machine
that wrote it (`benchmark/configs/<config name>.json`; on a chip the
published widths, off it the configuration's `rehearsal` widths).  One line
a rung: `same`, `DIFFERENT`, or `absent` (no entry under this rung's key:
nothing is served, nothing to check).  Exit 1 on any `DIFFERENT`, 2 where
the store does not engage in this process (JAX's persistent cache disabled
or without a directory), 0 otherwise.  A `DIFFERENT` entry is served until
it is removed: delete the store's directory (`docs/operations.md`).

The digest (`program_store.text_digest`) takes each Pallas kernel's
serialized body WITHOUT its debug information: the body holds the call stack
of the trace that made it, and this tool reaches `_lower` by another caller
than a deploy does (PR 49's first chip run of it read every entry DIFFERENT
for that alone).  On a chip give ONE configuration a process: a deployment's
tables or weights fill the device, and the engines keep them for the
process's life.
"""

import argparse
import importlib
import json
import os
import sys
import tempfile

ROOT = os.getcwd()
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("configs", nargs="+")
    ap.add_argument("--seed", type=int, default=4900000501)
    ap.add_argument("--shrink", type=int, default=1,
                    help="off the chip: the ALS configuration's users and "
                         "items divided by this")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    import jax

    from predictionio_tpu.parallel import mesh as mesh_mod
    from predictionio_tpu.serving import program_store

    ctx = mesh_mod.MeshContext.create()  # places the compile cache
    store = program_store.open_store()
    if store is None:
        print("verify_program_store: the store does not engage here (JAX's "
              "persistent compile cache is disabled or has no directory)",
              file=sys.stderr)
        return 2
    doc = {"store": store.root, "checkout": ROOT,
           "platform": jax.devices()[0].platform,
           "device_kind": jax.devices()[0].device_kind, "rungs": []}
    for name in args.configs:
        with open(os.path.join(ROOT, "benchmark", "configs",
                               name + ".json")) as f:
            cfg = json.load(f)
        if "users" in cfg and args.shrink != 1:
            cfg["users"] //= args.shrink
            cfg["items"] //= args.shrink
        engine = importlib.import_module("pio_bench.engines." + cfg["engine"])
        dep = engine.Deployment(
            cfg, args.seed, tempfile.mkdtemp(prefix="pio_verify_"), ctx)
        try:
            sc = dep.scorer()
            loaded = sc._rungs.programs_loaded
            for rung in sc._rungs.ladder:
                row = {"config": name, "rung": rung, **_check(
                    program_store, store, sc, rung)}
                doc["rungs"].append(row)
                print(json.dumps(row), flush=True)
            print(f"{name}: {loaded} of {len(sc._rungs.ladder)} rungs were "
                  "loaded from the store by this deploy", flush=True)
        finally:
            dep.stop()
    doc["entries"] = len(store.entries())
    doc["bytes"] = store.total_bytes()
    doc["different"] = sum(r["verdict"] == "DIFFERENT" for r in doc["rungs"])
    doc["absent"] = sum(r["verdict"] == "absent" for r in doc["rungs"])
    print(json.dumps({k: v for k, v in doc.items() if k != "rungs"}))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
    return 1 if doc["different"] else 0


def _check(program_store, store, sc, rung) -> dict:
    statics, lower_args = sc._describe(rung)
    on = program_store.lowered_on(lower_args)
    if on is None:
        return {"verdict": "absent", "why": "lowered over several devices"}
    pre = program_store.preimage(statics, lower_args, on)
    path = store.path(pre)
    try:
        header, _ = program_store.read_header(path)
    except (OSError, ValueError) as e:
        return {"verdict": "absent", "why": f"{type(e).__name__}: {e}"}
    fresh = program_store.text_digest(sc._lower(rung))
    same = fresh == header["lowered_sha256"]
    return {"verdict": "same" if same else "DIFFERENT",
            "entry": os.path.basename(path), "bytes": os.path.getsize(path),
            "stored": header["lowered_sha256"][:16], "fresh": fresh[:16],
            "written_from": header.get("written_from")}


if __name__ == "__main__":
    sys.exit(main())
