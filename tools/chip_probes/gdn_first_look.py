"""First chip look at the gated-delta-rule hybrid: set-up times, device time
per token rung, the scan and attention kernels alone (chunk 64 against 128),
op names in a trace, peak memory.  Measures the checkout this file lies in
and writes `chiprun_out/gdn_first_look.json`.  `--rungs` (PR 42): per
token rung the bare program on rows of at most 2,048 events that FILL it
(`n = R`) and on HALF the rung and one event (`n = R/2 + 1`: what a rung's
padded tail costs), then stop; `--out FILE` says where that goes."""
import glob, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, jax.numpy as jnp, numpy as np
from predictionio_tpu.parallel import mesh as mesh_mod
mesh_mod.MeshContext.create()
from predictionio_tpu.models import gdn_hybrid as gh
from predictionio_tpu.ops import gated_delta as gd
from predictionio_tpu.ops.flash_attention import packed_causal_attention
from predictionio_tpu.serving.seqpath import PackedSequenceScorer
from pio_bench.engines import gdn_hybrid_sequence as family
out = {}
cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", "olmo-hybrid-7b-l16.json")))
cfg = gh.Config.from_hf(family.model_config(cfgj), max_len=2048)
t0 = time.perf_counter(); P = gh.init_params(cfg, 3000000001); jax.block_until_ready(P)
out["init_s"] = time.perf_counter() - t0
t0 = time.perf_counter(); hs = family.make_histories(3000000001, cfgj["users"], cfgj["items"], cfgj["history"]); out["histories_s"] = time.perf_counter() - t0
lens = np.diff(hs.indptr); out["history_len"] = {"mean": float(lens.mean()), "p50": float(np.median(lens)), "over_1000": float((lens > 1000).mean())}
t0 = time.perf_counter(); sc = PackedSequenceScorer(cfg, P); out["compile_warm_s"] = time.perf_counter() - t0
print(out, flush=True)
rng = np.random.default_rng(0)
def hist(n): return rng.integers(0, cfg.vocab_size, n).astype(np.int32)
def timed(fn, n=7):
    jax.block_until_ready(fn()); ts = []
    for _ in range(n):
        t0 = time.perf_counter(); jax.block_until_ready(fn()); ts.append((time.perf_counter() - t0) * 1e3)
    return sorted(ts)[n // 2]
def rows(n):  # n tokens as rows of at most max_len events
    return [hist(min(2048, n - at)) for at in range(0, n, 2048)]
if "--rungs" in sys.argv:
    per_rung = {}
    for t in sc.ladder:
        for label, n in (("full", t), ("half_plus_one", t // 2 + 1)):
            dev = sc._put(gh.pack(rows(n), t, sc.max_rows))
            per_rung[f"{t}.{label}"] = {"tokens": n, "device_ms": timed(lambda: sc._fns[t](sc._params, dev)["values"])}
            print(t, label, per_rung[f"{t}.{label}"], flush=True)
    out["per_rung"] = per_rung
    out["memory"] = {k_: v_ for k_, v_ in (jax.devices()[0].memory_stats() or {}).items() if "bytes" in k_}
    out["stats"] = sc.stats()
    result = (sys.argv[sys.argv.index("--out") + 1] if "--out" in sys.argv
              else os.path.join(OUT, "gdn_first_look.rungs.json"))
    os.makedirs(os.path.dirname(os.path.abspath(result)), exist_ok=True)
    json.dump(out, open(result, "w"), indent=1, default=str)
    sys.exit(0)
times = {}
for label, hh in [("1x130", [hist(130)]), ("1x250", [hist(250)]), ("2x250", [hist(250)] * 2), ("1x1000", [hist(1000)]), ("1x2048", [hist(2048)]),
                  ("8x250", [hist(250)] * 8), ("16x250", [hist(250)] * 16), ("32x250", [hist(250)] * 32), ("4x2048", [hist(2048)] * 4)]:
    n_tok = sum(len(h) for h in hh); t = sc.rung_for(n_tok)
    dev = sc._put(gh.pack(hh, t, sc.max_rows))
    times[label] = {"rung": t, "device_ms": timed(lambda: sc._fns[t](sc._params, dev)["values"]),
                    "score_topk_ms": timed(lambda: sc.score_topk(hh, 20)[0])}
    print(label, times[label], flush=True)
out["per_batch"] = times
if "--quick" in sys.argv:  # set-up and per-rung times only
    out["memory"] = {k_: v_ for k_, v_ in (jax.devices()[0].memory_stats() or {}).items() if "bytes" in k_}
    os.makedirs(OUT, exist_ok=True)
    json.dump(out, open(os.path.join(OUT, "gdn_first_look.quick.json"), "w"), indent=1, default=str)
    sys.exit(0)
# the kernels alone at the top rung: one layer's worth
H, T = 30, 8192
k1, k2, k3, k4, k5 = jax.random.split(jax.random.key(0), 5)
q = jax.random.normal(k1, (H, T, 96), jnp.bfloat16) * 0.1; k = jax.random.normal(k2, (H, T, 96), jnp.bfloat16) * 0.1
v = jax.random.normal(k3, (H, T, 192), jnp.bfloat16); g = -jnp.exp(jax.random.uniform(k4, (H, T), jnp.float32, -6, 0))
beta = jax.random.uniform(k5, (H, T), jnp.float32, 0, 2)
segs = {"one_row_2048s": (np.arange(T) // 2048 * 2048).astype(np.int32), "rows_of_200": (np.arange(T) // 200 * 200).astype(np.int32),
        "all_padding": np.arange(T, dtype=np.int32)}
alone = {}
for name, seg in segs.items():
    s = jnp.asarray(seg)
    for chunk in (64, 128):
        f = jax.jit(lambda q, k, v, g, b, s, c=chunk: gd.gdn_scan(q, k, v, g, b, s, chunk=c))
        alone[f"scan.{name}.chunk{chunk}"] = timed(lambda: f(q, k, v, g, beta, s))
    qa = jax.random.normal(k1, (H, T, 128), jnp.bfloat16)
    fa = jax.jit(lambda a, s: packed_causal_attention(a, a, a, s))
    alone[f"attn.{name}"] = timed(lambda: fa(qa, s))
out["kernels_alone_ms_T8192_one_layer"] = alone
print(alone, flush=True)
os.makedirs(OUT, exist_ok=True)
tdir = os.path.join(OUT, "gdn_probe_trace")
jax.profiler.start_trace(tdir)
for hh in ([hist(130)], [hist(250)] * 2, [hist(2048)], [hist(250)] * 32):
    for _ in range(3): sc.score_topk(hh, 20)
jax.profiler.stop_trace()
path = sorted(glob.glob(tdir + "/plugins/profile/*/*.xplane.pb"))[-1]
data = jax.profiler.ProfileData.from_file(path)
ops = {}
for plane in data.planes:
    if not plane.name.startswith("/device:TPU:0"):
        continue
    for line in plane.lines:
        if line.name == "XLA Ops":
            for e in line.events:
                d = ops.setdefault(e.name.split(" = ")[0], [0.0, 0]); d[0] += e.duration_ns / 1e6; d[1] += 1
        if line.name == "XLA Modules":
            for e in line.events:
                d = ops.setdefault("MODULE " + e.name[:60], [0.0, 0]); d[0] += e.duration_ns / 1e6; d[1] += 1
out["trace_top_ops_ms_count"] = sorted(([n, round(d[0], 3), d[1]] for n, d in ops.items()), key=lambda x: -x[1])[:40]
import shutil; shutil.rmtree(tdir, ignore_errors=True)
out["memory"] = {k_: v_ for k_, v_ in (jax.devices()[0].memory_stats() or {}).items() if "bytes" in k_}
out["stats"] = sc.stats()
json.dump(out, open(os.path.join(OUT, "gdn_first_look.json"), "w"), indent=1, default=str)
print(json.dumps(out["trace_top_ops_ms_count"], indent=0)); print(out["memory"])
