"""What a packed sequence scorer's set-up is made of, per token rung: the
program's trace, its lowering, `compile()` (with a warm compile cache: the
cache's key and the executable's load) and the first and second run, s.
Measures the checkout this file lies in (copy it into another checkout to
measure that one).
  chiprun -- python3 tools/chip_probes/setup_split.py <config> [--out FILE]
Prints one line a rung and a total; `--out` keeps them as JSON."""
import importlib, json, os, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
os.chdir(ROOT)
import jax, jax.numpy as jnp, numpy as np
from predictionio_tpu.parallel import mesh as mesh_mod
mesh_mod.MeshContext.create()
cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", sys.argv[1] + ".json")))
eng = importlib.import_module("pio_bench.engines." + cfgj["engine"])
fam = importlib.import_module("predictionio_tpu.models." + {
    "latent_moe_sequence": "latent_moe", "gdn_hybrid_sequence": "gdn_hybrid",
    "window_moe_sequence": "window_moe", "ssm_parallel_sequence": "ssm_parallel"}[cfgj["engine"]])
serving = cfgj["serving"]
cfg = fam.Config.from_hf(eng.model_config(cfgj), max_len=serving["max_len"])
t0 = time.perf_counter(); P = fam.init_params(cfg, 4200000001); jax.block_until_ready(P)
out = {"config": sys.argv[1], "init_s": time.perf_counter() - t0, "rungs": {}}
print("init_s %.2f" % out["init_s"], flush=True)
for t in serving["token_ladder"]:
    flat = fam.flatten(fam.pack([np.zeros(1, np.int32)], t, serving["max_rows"]))
    def pio_seq_forward(P, flat):
        return fam.forward_flat(cfg, P, flat, t, cfgj["max_k"], score_backend="fused")
    marks = [time.perf_counter()]
    tr = jax.jit(pio_seq_forward).trace(P, jnp.asarray(flat)); marks.append(time.perf_counter())
    lo = tr.lower(); marks.append(time.perf_counter())
    ex = lo.compile(); marks.append(time.perf_counter())
    jax.block_until_ready(ex(P, flat)); marks.append(time.perf_counter())
    jax.block_until_ready(ex(P, flat)); marks.append(time.perf_counter())
    row = dict(zip(("trace", "lower", "compile", "run1", "run2"), np.diff(marks).tolist()))
    out["rungs"][str(t)] = row
    print(t, " ".join(f"{k} {v:.3f}" for k, v in row.items()), flush=True)
out["total"] = {k: sum(r[k] for r in out["rungs"].values()) for k in ("trace", "lower", "compile", "run1", "run2")}
print("total", {k: round(v, 2) for k, v in out["total"].items()})
if "--out" in sys.argv:
    path = sys.argv[sys.argv.index("--out") + 1]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    json.dump(out, open(path, "w"), indent=1)
