"""The state-space / attention family with routed experts behind every
layer, at its first contact with the chip: do the scan at 128 heads of 64 in
ONE group (chunks of 256), the grouped-query kernel at 32 / 8 heads with no
rotary and the scale 1/128, the grouped products over a run's ONE table of
n x 36 groups and the tied head at rank 4,096 run as the off-chip compile
said, and what does ONE dispatch cost per token rung, by op?  Seeded weights
at the published widths, `PackedSequenceScorer`, per rung three bare
dispatches — one history that fills the rung, rows of ~200 events that fill
it, one history of HALF the rung and one event — each profiled on its own.
`--lengths one_row,half_plus_one` picks among the three, `--rungs 1024,2048`
among the rungs, `--ops N` keeps the N largest other ops a row; `--out FILE`
(default `chiprun_out/smoe_first_look.json`) says where the result goes."""
import json, os, shutil, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, numpy as np
from predictionio_tpu.parallel import mesh as mesh_mod
mesh_mod.MeshContext.create()
from predictionio_tpu.models import ssm_moe as sm
from predictionio_tpu.serving.seqpath import PackedSequenceScorer
from pio_bench.engines import ssm_moe_sequence as family
from pio_bench import xplane_named

cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", "granite-4.0-h-small-l10-ep2.json")))
hf = family.model_config(cfgj)
serving = cfgj["serving"]
cfg = sm.SSMMoEConfig.from_hf(hf, max_len=serving["max_len"])
out = {}
t0 = time.perf_counter(); P = sm.init_params(cfg, 4600000001); jax.block_until_ready(P)
out["init_s"] = time.perf_counter() - t0
t0 = time.perf_counter()
sc = PackedSequenceScorer(cfg, P, max_k=cfgj["max_k"], ladder=serving["token_ladder"], max_rows=serving["max_rows"])
out["compile_warm_s"] = time.perf_counter() - t0
out["resident_bytes"] = sc.resident_bytes
out["program_bytes"] = {str(r): n for r, n in sc._rungs.gate._need.items()} if hasattr(sc._rungs.gate, "_need") else None
print(out, flush=True)
rng = np.random.default_rng(0)
hist = lambda n: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
arg = lambda name, default: sys.argv[sys.argv.index(name) + 1] if name in sys.argv else default
LENGTHS = arg("--lengths", "one_row,rows_of_200,half_plus_one").split(",")
RESULT = arg("--out", os.path.join(OUT, "smoe_first_look.json"))
ONLY = [int(t) for t in arg("--rungs", "").split(",") if t]
N_OPS = int(arg("--ops", "8"))
NAMES = ("ssd_scan", "global_attention", "moe_experts", "score_topk")
os.makedirs(OUT, exist_ok=True)
rungs = {}
for t in sc.ladder:
    if ONLY and t not in ONLY:
        continue
    for label, hs in (("one_row", [hist(t)]), ("rows_of_200", [hist(200) for _ in range(min(64, max(1, t // 200)))]),
                      ("half_plus_one", [hist(t // 2 + 1)])):
        if label not in LENGTHS:
            continue
        before = sc.stats()
        sc.score_topk(hs, 20)
        after = sc.stats()
        walls = []
        for _ in range(3):
            t0 = time.perf_counter(); sc.score_topk(hs, 20); walls.append((time.perf_counter() - t0) * 1e3)
        tdir = os.path.join(OUT, f"smoe_trace.{t}.{label}")
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
        for key in ("expert_assignments", "experts_touched", "expert_row_tiles", "routed_assignments",
                    "tokens_without_held_expert", "local_row_overflows", "scan_chunks"):
            row[key] = after[key] - before[key]
        other = {}
        for n, d in named["ops"]:
            if not any(x in n for x in NAMES):
                other[n] = other.get(n, 0.0) + d
        row["other_top"] = [[n[:60], round(1e3 * d / max(1, len(mods)), 3)] for n, d in sorted(other.items(), key=lambda x: -x[1])[:N_OPS]]
        shutil.rmtree(tdir, ignore_errors=True)
        rungs[f"{t}.{label}"] = row
        print(t, label, {k: (round(v, 3) if isinstance(v, float) else v) for k, v in row.items() if k != "other_top"}, flush=True)
        print("   other:", row["other_top"], flush=True)
out["rungs"] = rungs
out["mem"] = jax.devices()[0].memory_stats()
out["stats"] = sc.stats()
os.makedirs(os.path.dirname(os.path.abspath(RESULT)), exist_ok=True)
json.dump(out, open(RESULT, "w"), indent=1, default=str)
print("peak", out["mem"].get("peak_bytes_in_use"), "stats", {k: out["stats"][k] for k in ("block_items", "resident_bytes", "held_launches", "launch_lag_ms", "scan_tokens", "scan_rows", "scan_chunks", "scan_chunk", "causal_pairs", "expert_row_tiles", "expert_assignments")})
