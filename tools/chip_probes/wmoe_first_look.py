"""The window/global sparse-expert family's first contact with the chip: do
the streamed grouped-query kernel, the held experts' products (column
tiles, the pass loop) and the head at rank 3,072 run as the off-chip compile
said, and what does ONE dispatch cost per token rung, by op?  Seeded weights
at the published widths, `PackedSequenceScorer`, per rung two bare
dispatches — one history that fills the rung, and rows of ~200 events that
fill it — and since PR 42 a third, one history of HALF the rung and one
event (`half_plus_one`: what a rung's padded tail costs), each profiled on
its own.  `--lengths one_row,half_plus_one` picks among the three, `--rungs
1024,2048` among the rungs, `--ops N` keeps the N largest other ops a row;
`--out FILE` (default `chiprun_out/wmoe_first_look.json` of the checkout
this file lies in) says where the result goes, so that another checkout's
copy of this file can write into the one `chiprun` brings back."""
import json, os, shutil, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, numpy as np
from predictionio_tpu.parallel import mesh as mesh_mod
mesh_mod.MeshContext.create()
from predictionio_tpu.models import window_moe as wm
from predictionio_tpu.serving.seqpath import PackedSequenceScorer
from pio_bench.engines import window_moe_sequence as family
from pio_bench import xplane_named

cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", "trinity-large-l5-ep8.json")))
hf = family.model_config(cfgj)
serving = cfgj["serving"]
cfg = wm.WindowMoEConfig.from_hf(hf, max_len=serving["max_len"])
out = {}
t0 = time.perf_counter(); P = wm.init_params(cfg, 3900000001); jax.block_until_ready(P)
out["init_s"] = time.perf_counter() - t0
t0 = time.perf_counter()
sc = PackedSequenceScorer(cfg, P, max_k=cfgj["max_k"], ladder=serving["token_ladder"], max_rows=serving["max_rows"])
out["compile_warm_s"] = time.perf_counter() - t0
out["resident_bytes"] = sc.resident_bytes
print(out, flush=True)
rng = np.random.default_rng(0)
hist = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
LENGTHS = (sys.argv[sys.argv.index("--lengths") + 1].split(",") if "--lengths" in sys.argv
           else ["one_row", "rows_of_200", "half_plus_one"])
RESULT = (sys.argv[sys.argv.index("--out") + 1] if "--out" in sys.argv
          else os.path.join(OUT, "wmoe_first_look.json"))
ONLY = ([int(t) for t in sys.argv[sys.argv.index("--rungs") + 1].split(",")] if "--rungs" in sys.argv else None)
N_OPS = int(sys.argv[sys.argv.index("--ops") + 1]) if "--ops" in sys.argv else 8
NAMES = ("window_attention", "global_attention", "moe_experts", "score_topk")
os.makedirs(OUT, exist_ok=True)
rungs = {}
for t in sc.ladder:
    if ONLY and t not in ONLY:
        continue
    for label, hs in (("one_row", [hist(t)]), ("rows_of_200", [hist(200) for _ in range(min(64, max(1, t // 200)))]),
                      ("half_plus_one", [hist(t // 2 + 1)])):
        if label not in LENGTHS:
            continue
        sc.score_topk(hs, 20)
        walls = []
        for _ in range(3):
            t0 = time.perf_counter(); sc.score_topk(hs, 20); walls.append((time.perf_counter() - t0) * 1e3)
        tdir = os.path.join(OUT, f"wmoe_trace.{t}.{label}")
        jax.profiler.start_trace(tdir)
        for _ in range(2):
            sc.score_topk(hs, 20)
        jax.profiler.stop_trace()
        named = xplane_named.load_named(tdir)
        mods = [d for n, d in named["modules"] if "pio_seq_forward" in n]
        row = {"host_wall_ms": sorted(walls)[1], "device_ms": 1e3 * sum(mods) / max(1, len(mods)), "runs": len(mods)}
        for needle in NAMES:
            hits = [d for n, d in named["ops"] if needle in n]
            row[needle + "_ms"] = 1e3 * sum(hits) / max(1, len(mods))
            row[needle + "_ops"] = len(hits) // max(1, len(mods))
        other = sorted(((n, d) for n, d in named["ops"] if not any(x in n for x in NAMES)), key=lambda x: -x[1])
        agg = {}
        for n, d in other:
            agg[n] = agg.get(n, 0.0) + d
        row["other_top"] = [[n[:60], round(1e3 * d / max(1, len(mods)), 3)] for n, d in sorted(agg.items(), key=lambda x: -x[1])[:N_OPS]]
        shutil.rmtree(tdir, ignore_errors=True)
        rungs[f"{t}.{label}"] = row
        print(t, label, {k: (round(v, 3) if isinstance(v, float) else v) for k, v in row.items() if k != "other_top"}, flush=True)
out["rungs"] = rungs
out["mem"] = jax.devices()[0].memory_stats()
out["stats"] = sc.stats()
os.makedirs(os.path.dirname(os.path.abspath(RESULT)), exist_ok=True)
json.dump(out, open(RESULT, "w"), indent=1, default=str)
print("peak", out["mem"].get("peak_bytes_in_use"), "stats", {k: out["stats"][k] for k in ("experts_touched", "expert_assignments", "routed_assignments", "tokens_without_held_expert", "local_row_overflows", "window_kv_blocks", "window_kv_blocks_unskipped", "load_max_over_mean_sum", "sparse_layer_dispatches")})
