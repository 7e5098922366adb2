"""First chip look: set-up times, device time per rung, op names in a trace.
Measures the checkout this file lies in and writes under its `chiprun_out/`."""
import json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(ROOT, "chiprun_out")
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
import jax, numpy as np
from predictionio_tpu.parallel import mesh as mesh_mod
mesh_mod.MeshContext.create()
from predictionio_tpu.models import latent_moe as lm
from predictionio_tpu.serving.seqpath import PackedSequenceScorer
from pio_bench.engines.latent_moe_sequence import MODEL_KEYS
out = {}
cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", "joyai-llm-flash-l5.json")))
hf = {k: cfgj[k] for k in MODEL_KEYS}; hf["vocab_size"] = cfgj["items"]
cfg = lm.LatentMoEConfig.from_hf(hf, max_len=2048)
t0 = time.perf_counter(); P = lm.init_params(cfg, 3000000001); jax.block_until_ready(P)
out["init_s"] = time.perf_counter() - t0
t0 = time.perf_counter(); sc = PackedSequenceScorer(cfg, P); out["compile_warm_s"] = time.perf_counter() - t0
print(out, flush=True)
rng = np.random.default_rng(0)
def hist(n): return rng.integers(0, cfg.vocab_size, n).astype(np.int32)
times = {}
for label, hs in [("1x130", [hist(130)]), ("1x250", [hist(250)]), ("1x500", [hist(500)]), ("1x1000", [hist(1000)]), ("1x2048", [hist(2048)]),
                  ("3x130", [hist(130)]*3), ("8x128", [hist(128)]*8), ("16x250", [hist(250)]*16), ("32x250", [hist(250)]*32), ("4x2048", [hist(2048)]*4)]:
    sc.score_topk(hs, 20)
    ts = []
    for _ in range(5):
        t0 = time.perf_counter(); sc.score_topk(hs, 20); ts.append((time.perf_counter() - t0) * 1e3)
    times[label] = sorted(ts)[2]
out["score_topk_ms_median"] = times
print(times, flush=True)
os.makedirs(OUT, exist_ok=True)
tdir = os.path.join(OUT, "probe1_trace")
jax.profiler.start_trace(tdir)
for hs in ([hist(130)], [hist(130)]*3, [hist(2048)], [hist(250)]*32):
    for _ in range(3): sc.score_topk(hs, 20)
jax.profiler.stop_trace()
import glob
path = sorted(glob.glob(tdir + "/plugins/profile/*/*.xplane.pb"))[-1]
data = jax.profiler.ProfileData.from_file(path)
ops = {}
for plane in data.planes:
    if not plane.name.startswith("/device:TPU:0"):
        continue
    for line in plane.lines:
        if line.name == "XLA Ops":  # an event's name is its whole HLO line
            for e in line.events:
                d = ops.setdefault(e.name, [0.0, 0])
                d[0] += e.duration_ns / 1e6
                d[1] += 1
        if line.name == "XLA Modules":
            mods = {}
            for e in line.events:
                d = mods.setdefault(e.name, [0.0, 0])
                d[0] += e.duration_ns / 1e6
                d[1] += 1
            out["modules"] = mods
out["ops_top"] = sorted(([n, round(t, 3), c] for n, (t, c) in ops.items()), key=lambda x: -x[1])[:70]
out["mem"] = jax.devices()[0].memory_stats()
out["stats"] = sc.stats()
json.dump(out, open(os.path.join(OUT, "probe1.json"), "w"), indent=1, default=str)
import shutil; shutil.rmtree(tdir, ignore_errors=True)
for n, t, c in out["ops_top"][:45]: print(f"{t:10.3f} ms {c:5d}  {n[:110]}")
print({k: v for k, v in out.items() if k in ("init_s", "compile_warm_s", "modules")})
print("peak", out["mem"].get("peak_bytes_in_use"))
