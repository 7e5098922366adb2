"""ISSUE 49, the first question: does a rung's executable, Mosaic kernels
and all, come back from `jax.experimental.serialize_executable` in ANOTHER
process as the program that was compiled — and what does the load cost
beside a warm `jit(...).lower(...).compile()` of the same rung?

    chiprun -- bash -c 'python3 tools/chip_probes/program_roundtrip.py write <config> <rungs> \
        && python3 tools/chip_probes/program_roundtrip.py read <config> <rungs>'

`write` compiles the rungs (cold: the copy has no compile cache), keeps each
as `chiprun_out/pr49.roundtrip/<config>.<rung>.bin` (pickle of the
serialized executable and its two tree definitions, compressed as JAX's
cache compresses) and the sha256 of every output on one seeded input.
`read`, a new process with the weights made from the same seed again, loads
each file, runs it on the same input and compares; then takes the same rung
through `jit(...).lower(...).compile()` (the compile cache warm from
`write`) and times both ways, `memory_analysis()`, `cost_analysis()` and the
host's cost of a call on both objects.  Exit 1 if an output differs.
Off the chip (`JAX_PLATFORMS=cpu`) the configuration's `rehearsal` widths
run: a check of the script, no device metric.
"""
import hashlib
import importlib
import json
import os
import pickle
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmark")]
os.chdir(ROOT)
import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax._src import compilation_cache as jcc  # noqa: E402
from jax.experimental import serialize_executable as se  # noqa: E402

from predictionio_tpu.parallel import mesh as mesh_mod  # noqa: E402

phase, name = sys.argv[1], sys.argv[2]
mesh_mod.MeshContext.create()
cfgj = json.load(open(os.path.join(ROOT, "benchmark", "configs", name + ".json")))
eng = importlib.import_module("pio_bench.engines." + cfgj["engine"])
fam = importlib.import_module(
    "predictionio_tpu.models." + cfgj["engine"].replace("_sequence", ""))
on_chip = jax.devices()[0].platform == "tpu"
serving = dict(cfgj["serving"])
if not on_chip:
    serving.update(cfgj["rehearsal"]["serving"])
rungs = ([int(t) for t in sys.argv[3].split(",")] if len(sys.argv) > 3
         else serving["token_ladder"])
cfg = fam.Config.from_hf(eng.model_config(cfgj), max_len=serving["max_len"])
SEED = 4900000001
P = fam.init_params(cfg, SEED)
jax.block_until_ready(P)
out_dir = os.path.join(ROOT, "chiprun_out", "pr49.roundtrip")
os.makedirs(out_dir, exist_ok=True)
doc = {"phase": phase, "config": name, "platform": jax.devices()[0].platform,
       "device_kind": jax.devices()[0].device_kind, "rungs": {}}


def seeded_input(t):
    rng = np.random.default_rng(SEED + t)
    left, lens = int(0.8 * t), []
    while left and len(lens) < serving["max_rows"]:
        lens.append(min(left, int(rng.integers(1, cfg.max_len + 1))))
        left -= lens[-1]
    hists = [rng.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]
    return fam.flatten(fam.pack(hists, t, serving["max_rows"]))


def digests(ex, flat):
    got = jax.device_get(ex(P, flat))
    return {k: hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest()[:24]
            for k, v in sorted(got.items())}


def call_cost_us(ex, flat, n=40):
    """The host's cost of one call: until the launch returns, the device
    idle before each."""
    costs = []
    for _ in range(n):
        t0 = time.perf_counter()
        o = ex(P, flat)
        costs.append(time.perf_counter() - t0)
        jax.block_until_ready(o)
    return round(1e6 * float(np.median(costs)), 1)


def compile_rung(t):
    def pio_seq_forward(P, flat):
        return fam.forward_flat(cfg, P, flat, t, cfgj["max_k"],
                                score_backend="fused" if on_chip else None)
    dummy = jax.device_put(fam.flatten(fam.pack(
        [np.zeros(1, np.int32)], t, serving["max_rows"])), jax.devices()[0])
    marks = [time.perf_counter()]
    tr = jax.jit(pio_seq_forward).trace(P, dummy); marks.append(time.perf_counter())
    lo = tr.lower(); marks.append(time.perf_counter())
    ex = lo.compile(); marks.append(time.perf_counter())
    return ex, lo, dict(zip(("trace_s", "lower_s", "compile_s"),
                            np.round(np.diff(marks), 3).tolist()))


bad = 0
for t in rungs:
    path = os.path.join(out_dir, f"{name}.{t}.bin")
    flat = seeded_input(t)
    row = {}
    if phase == "write":
        ex, lo, row = compile_rung(t)
        t0 = time.perf_counter(); text = lo.as_text(); t1 = time.perf_counter()
        row["as_text_s"], row["mlir_bytes"] = round(t1 - t0, 3), len(text)
        t0 = time.perf_counter()
        raw = pickle.dumps(se.serialize(ex))
        t1 = time.perf_counter()
        packed = jcc.compress_executable(raw)
        t2 = time.perf_counter()
        with open(path, "wb") as f:
            f.write(packed)
        row.update(serialize_s=round(t1 - t0, 3), compress_s=round(t2 - t1, 3),
                   write_s=round(time.perf_counter() - t2, 3),
                   raw_bytes=len(raw), stored_bytes=len(packed))
        row["outputs"] = digests(ex, flat)
    else:
        marks = [time.perf_counter()]
        with open(path, "rb") as f:
            packed = f.read()
        marks.append(time.perf_counter())
        raw = jcc.decompress_executable(packed); marks.append(time.perf_counter())
        loaded = se.deserialize_and_load(
            *pickle.loads(raw), execution_devices=[jax.devices()[0]])
        marks.append(time.perf_counter())
        row.update(zip(("read_s", "decompress_s", "deserialize_and_load_s"),
                       np.round(np.diff(marks), 3).tolist()))
        row["load_total_s"] = round(marks[-1] - marks[0], 3)
        row["outputs"] = digests(loaded, flat)
        ex, _, split = compile_rung(t)
        row["warm_compile"] = split
        row["outputs_compiled_here"] = digests(ex, flat)
        written = json.load(open(os.path.join(out_dir, f"{name}.write.json")))
        row["equal_to_written"] = row["outputs"] == written["rungs"][str(t)]["outputs"]
        row["equal_to_compiled_here"] = row["outputs"] == row["outputs_compiled_here"]
        bad += not (row["equal_to_written"] and row["equal_to_compiled_here"])
        row["memory_analysis_equal"] = (
            str(loaded.memory_analysis()) == str(ex.memory_analysis()))
        try:
            row["cost_analysis_loaded"] = bool(loaded.cost_analysis())
        except Exception as e:  # a probe: the answer is the finding
            row["cost_analysis_loaded"] = repr(e)[:200]
        row["call_us_compiled"] = call_cost_us(ex, flat)
        row["call_us_loaded"] = call_cost_us(loaded, flat)
        row["call_us_compiled_again"] = call_cost_us(ex, flat)
    doc["rungs"][str(t)] = row
    print(t, json.dumps(row), flush=True)
with open(os.path.join(out_dir, f"{name}.{phase}.json"), "w") as f:
    json.dump(doc, f, indent=1)
sys.exit(1 if bad else 0)
