"""What does each crossing of the host-device boundary cost a dispatch, and
which form of ISSUE 38's envelope is the cheapest on the chip?

Deploys one of the benchmark's configurations as a run does (no load), takes
the scorer's compiled programs and times ONE dispatch per variant, the variants
taken in turn so that a drift of the machine meets them all alike:

* `parent`        `device_put(jnp.asarray(x))`, call, `block_until_ready`,
                  `device_get` (PR 37's envelope; the sequence scorer's
                  `device_put(x, device)` has no `jnp.asarray` hop)
* `put1`          one `device_put(x, sharding)`, the rest as the parent
* `ride`          the host array handed to the compiled call
* `ride_queue`    the same, `copy_to_host_async()` on the fetched outputs
                  right after the call, then the wait, then `device_get`
* `ride_queue_get`  the same without the `block_until_ready`: the
                  `device_get` is the wait
* `put1_queue`    `put1` with the copy queued

per stage (`h2d`, `call`, `queue`, `wait`, `d2h`) the median over `--n`
dispatches in ms, and their sum.  Then, rung by rung, `score_topk` of THIS
tree against the direct program call on a `device_put` input: equal bit for
bit, or the count of rungs that differ.

    chiprun --timeout 900 -- python tools/chip_probes/boundary_split.py \
        --config als-wgde-d128 --rungs 1,8

Writes chiprun_out/boundary_split.<config>.json; under JAX_PLATFORMS=cpu pass
`--shrink 500 --n 5` (ALS) or `--shrink 64 --n 3` (the sequence ones, which
run at the configuration's `rehearsal` widths off the chip).
"""

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

VARIANTS = ("parent", "put1", "ride", "ride_queue", "ride_queue_get",
            "put1_queue")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="als-wgde-d128")
    ap.add_argument("--n", type=int, default=300)
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--seed", type=int, default=3800000001)
    ap.add_argument("--rungs", default="",
                    help="rungs timed (default: the two lowest); the "
                         "identity check takes every rung")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.parallel import mesh as mesh_mod

    with open(os.path.join(ROOT, "benchmark", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    cfg["users"] //= args.shrink
    cfg["items"] //= args.shrink
    family = importlib.import_module("pio_bench.engines." + cfg["engine"])
    ctx = mesh_mod.MeshContext.create()
    dep = family.Deployment(cfg, args.seed, tempfile.mkdtemp(prefix="pio_probe_"), ctx)
    sc = dep.scorer()
    rng = np.random.default_rng(args.seed)
    als = hasattr(sc, "buckets")
    ladder = list(sc.buckets if als else sc.ladder)
    timed = ([int(r) for r in args.rungs.split(",")] if args.rungs
             else ladder[:2])

    if als:
        k = sc.k

        def host_input(rung):
            return rng.integers(0, cfg["users"], rung).astype(np.int32)

        def parent_put(x):
            return jax.device_put(jnp.asarray(x), sc._repl)

        def put1(x):
            return jax.device_put(x, sc._repl)

        def call(rung, x):
            return sc._fns[rung](*sc._static_args, x)

        def fetched(outs):
            return outs

        def served(rung, x):
            idx, val = sc.score_topk(x, k)
            return {"indices": idx, "values": val}

        def direct(rung, x):
            val, idx, *_ = jax.device_get(call(rung, parent_put(x)))
            return {"indices": idx[:, :k], "values": val[:, :k]}
    else:
        k = sc.k
        fam = sc._family

        def rows_for(rung):
            """Histories whose tokens land in this rung and no lower one
            (a rung above `max_len` takes several)."""
            n_rows = -(-rung // sc.config.max_len)
            return [rng.integers(
                0, sc.config.vocab_size,
                rung // n_rows - int(rng.integers(0, 8))).astype(np.int32)
                for _ in range(n_rows)]

        rows_of = {}

        def host_input(rung):
            rows_of[rung] = rows_for(rung)
            return fam.flatten(fam.pack(rows_of[rung], rung, sc.max_rows))

        def parent_put(x):
            return jax.device_put(x, sc._device)

        put1 = parent_put

        def call(rung, x):
            return sc._fns[rung](sc._params, x)

        def fetched(out):
            return {name: out[name] for name in
                    ("values", "indices", "merge") + sc._own.fetch
                    if name in out}

        def served(rung, x):
            idx, val = sc.score_topk(rows_of[rung], k)
            return {"indices": idx, "values": val}

        def direct(rung, x):
            got = jax.device_get(fetched(call(rung, parent_put(x))))
            n = len(rows_of[rung])
            return {"indices": got["indices"][:n, :k],
                    "values": got["values"][:n, :k]}

    def one(variant, rung, x):
        t = [time.perf_counter()]
        if variant == "parent":
            x = parent_put(x)
        elif variant.startswith("put1"):
            x = put1(x)
        t.append(time.perf_counter())
        outs = fetched(call(rung, x))
        t.append(time.perf_counter())
        if "queue" in variant:
            for a in jax.tree_util.tree_leaves(outs):
                a.copy_to_host_async()
        t.append(time.perf_counter())
        if not variant.endswith("_get"):
            jax.block_until_ready(outs)
        t.append(time.perf_counter())
        jax.device_get(outs)
        t.append(time.perf_counter())
        return [(b - a) * 1e3 for a, b in zip(t, t[1:])]

    out = {"config": args.config, "n": args.n,
           "device": jax.devices()[0].device_kind, "rungs": {}}
    for rung in timed:
        rows = {v: [] for v in VARIANTS}
        for i in range(args.n + 5):
            x = host_input(rung)
            # rotate who goes first
            for j in range(len(VARIANTS)):
                v = VARIANTS[(i + j) % len(VARIANTS)]
                ms = one(v, rung, x)
                if i >= 5:
                    rows[v].append(ms)
        table = {}
        for v, r in rows.items():
            a = np.asarray(r)
            med = np.median(a, axis=0)
            table[v] = dict(zip(("h2d", "call", "queue", "wait", "d2h"),
                                (round(float(m), 4) for m in med)))
            table[v]["sum_of_medians"] = round(float(med.sum()), 4)
            table[v]["median_of_sums"] = round(
                float(np.median(a.sum(axis=1))), 4)
        out["rungs"][str(rung)] = table
        print(rung, json.dumps(table), flush=True)

    differ = []
    for rung in ladder:
        x = host_input(rung)
        before = sc.stats()["bucket_hits"][str(rung)]
        a, b = served(rung, x), direct(rung, x)
        assert sc.stats()["bucket_hits"][str(rung)] == before + 1, rung
        if not all(np.array_equal(a[n], b[n]) and a[n].dtype == b[n].dtype
                   for n in a):
            differ.append(rung)
    out["identity"] = {"rungs": ladder, "differ": differ}
    st = sc.stats()
    out["stats"] = {n: st[n] for n in ("calls", "readbacks_queued")
                    if n in st}
    print("identity", out["identity"], out["stats"], flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"boundary_split.{args.config}.json"), "w") as f:
        json.dump(out, f, indent=1)
    dep.stop()
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
