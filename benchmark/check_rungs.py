#!/usr/bin/env python3
"""Direct all-rung check, and the lower-precision control, on the chip.

    chiprun -- python3 benchmark/check_rungs.py --config als-wgde-d128 \
        --seeds 12 --first-seed 3000000001

For each seed: draw the configuration's factors, build the program's
``BucketedScorer`` over them (every rung AOT-compiled), call ``score_topk``
with exactly 1, 8, 16, 32 and 64 seeded users at k = max_k, and judge every
row against float64 (``pio_bench/reference.py``).  Then the control: the
same users scored by plain ``jnp`` at ``Precision.HIGH`` (three bf16
passes) and at ``Precision.DEFAULT`` (one), judged by the same rules —
it has to come out as NOT correct.  Writes one JSON line per seed to
``chiprun_out/check_rungs.<config>.jsonl``; exits 1 if a sound row fails
or a control passes.  The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at a shrunken size; never a finding")
    ap.add_argument("--shrink", type=int, default=1)
    args = ap.parse_args()

    import numpy as np

    from pio_bench import reference, seeded

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    n_u, n_i = cfg["users"] // args.shrink, cfg["items"] // args.shrink
    rank, k = cfg["rank"], cfg["max_k"]
    tol = cfg["guarantees"]["score_tolerance"]

    import jax
    import jax.numpy as jnp

    dev = jax.devices()
    if dev[0].platform != "tpu" and not args.allow_cpu:
        print("check_rungs: no TPU", file=sys.stderr)
        return 3
    from predictionio_tpu.parallel import mesh as mesh_mod
    from predictionio_tpu.serving.fastpath import BucketedScorer

    ctx = mesh_mod.MeshContext.create()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"check_rungs.{args.config}.jsonl")
    bad = 0
    with open(out_path, "w") as out:
        for s in range(args.seeds):
            seed = args.first_seed + s
            t0 = time.perf_counter()
            U = seeded.make_factors(seed, seeded.STREAM_USER_FACTORS, n_u, rank)
            V = seeded.make_factors(seed, seeded.STREAM_ITEM_FACTORS, n_i, rank)
            t_gen = time.perf_counter() - t0
            t0 = time.perf_counter()
            fp = BucketedScorer(ctx, U, V, max_k=k, buckets=cfg["rungs"])
            t_build = time.perf_counter() - t0
            gen = seeded.rng(seed, seeded.STREAM_RUNGS)
            users, idx, vals, walls = [], [], [], {}
            for b in cfg["rungs"]:
                u = gen.choice(n_u, b, replace=False)
                best = float("inf")
                for _ in range(3):
                    t0 = time.perf_counter()
                    i_b, v_b = fp.score_topk(u, k)
                    best = min(best, time.perf_counter() - t0)
                walls[str(b)] = best
                users += u.tolist()
                idx += [r for r in np.asarray(i_b)]
                vals += [r for r in np.asarray(v_b)]
            t0 = time.perf_counter()
            vmax = reference.max_row_norm(V)
            sound = reference.check_topk(
                U, V, users, idx, vals, [k] * len(users), tol, vmax=vmax)
            t_check = time.perf_counter() - t0
            # the control: plain jnp at a lower precision, same users
            controls = {}
            # (over the scorer's resident copies of the benchmark's own
            # factors: a second copy of 5.8 GB would not fit beside them)
            Ud, Vd = fp._U, fp._V
            rows = jnp.asarray(np.asarray(users, np.int32))
            for name, prec in (("high_3pass", jax.lax.Precision.HIGH),
                               ("bf16_1pass", jax.lax.Precision.DEFAULT)):
                sc = jnp.matmul(Ud[rows], Vd[:n_i].T, precision=prec)
                cv, ci = jax.lax.top_k(sc, k)
                c = reference.check_topk(
                    U, V, users, np.asarray(ci), np.asarray(cv),
                    [k] * len(users), tol, vmax=vmax)
                controls[name] = {x: c[x] for x in (
                    "ok", "score_over_tol", "beat_over_tol", "order_over_tol")}
                del sc, cv, ci
            ms = dev[0].memory_stats() or {}
            rec = {
                "seed": seed, "config": args.config, "shrink": args.shrink,
                "device": {"platform": dev[0].platform,
                           "kind": dev[0].device_kind, "count": len(dev)},
                "sound": sound, "controls": controls,
                "rung_wall_s": walls, "compile_count": fp.compile_count,
                "seconds": {"factors": t_gen, "scorer": t_build,
                            "float64": t_check},
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
            }
            out.write(json.dumps(rec) + "\n")
            out.flush()
            print(json.dumps(rec), flush=True)
            if not sound["ok"] or any(c["ok"] for c in controls.values()):
                bad += 1
            del fp, Ud, Vd, U, V
    print(f"check_rungs: {args.seeds} seeds, {bad} with a sound row refused or "
          f"a control passed; written to {out_path}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
