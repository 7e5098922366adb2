"""PR 48: what ONE rung's serving program of a packed family costs the host
before the backend sees it, counted OFF the chip: seconds of
`jax.jit(f).trace(...)` and of `.lower(lowering_platforms=("tpu",))` at the
published widths on `ShapeDtypeStruct`s (no weights, nothing runs), the
MLIR's bytes and its `tpu_custom_call`s.  A COUNT of host work on whatever
CPU runs it — this sandbox's, not the chip's host, which reads ~1.6 x
slower (`results/pr42.review.setup_in_cell.jsonl`) — and no device metric:
what it is for is pricing a change to a program's Python (a kernel more a
layer, a branch shared) in `setup_s` before a chip is asked for.

    JAX_PLATFORMS=cpu python3 tools/chip_probes/trace_lower.py <checkout> <config name> <rung>[,<rung>...] [reps]

Each rung: the median (and the least) of `reps` (5) traces and lowerings
with `jax.clear_caches()` before each, so an inner `jit`'s cache starts
empty as it does in a fresh process's first rung.
"""
import importlib
import json
import os
import statistics
import sys
import time

tree, name, rungs = sys.argv[1], sys.argv[2], sys.argv[3]
reps = int(sys.argv[4]) if len(sys.argv) > 4 else 5
tree = os.path.abspath(tree)
sys.path[:0] = [tree, os.path.join(tree, "benchmark")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

with open(os.path.join(tree, "benchmark", "configs", name + ".json")) as f:
    c = json.load(f)
engine = importlib.import_module("pio_bench.engines." + c["engine"])
family = importlib.import_module(
    "predictionio_tpu.models." + c["engine"].replace("_sequence", ""))
# `model_config` takes the rehearsal's widths off the chip: the published
# ones are wanted here
published = c.get("published") or {}
hf = engine.model_config({**c, "rehearsal": {
    **c["rehearsal"], "model": {},
    # the window family's key, or the routed state-space family's
    "router_experts": published.get(
        "num_experts", published.get("num_local_experts"))}})
cfg = family.Config.from_hf(hf, max_len=c["serving"]["max_len"])
P = {n: jax.ShapeDtypeStruct(s, d)
     for n, (s, d) in family.param_shapes(cfg).items()}
for t in (int(r) for r in rungs.split(",")):
    flat = jax.ShapeDtypeStruct((4 * t + c["serving"]["max_rows"],), jnp.int32)

    def pio_seq_forward(P, flat):
        return family.forward_flat(cfg, P, flat, t, c["max_k"],
                                   score_backend="fused", interpret=False)

    walls = []
    for _ in range(reps):
        jax.clear_caches()
        t0 = time.perf_counter()
        traced = jax.jit(pio_seq_forward).trace(P, flat)
        t1 = time.perf_counter()
        lowered = traced.lower(lowering_platforms=("tpu",))
        walls.append((t1 - t0, time.perf_counter() - t1))
    text = lowered.as_text()
    shared = getattr(family, "_shared", None)
    print(json.dumps({
        "tree": tree, "config": name, "rung": t, "reps": reps,
        "trace_s": round(statistics.median(w[0] for w in walls), 3),
        "lower_s": round(statistics.median(w[1] for w in walls), 3),
        "least": [round(min(w[i] for w in walls), 3) for i in (0, 1)],
        "mlir_kb": len(text) // 1000,
        "tpu_custom_calls": text.count(
            "stablehlo.custom_call @tpu_custom_call"),
        "branches": shared.stats() if shared else None}), flush=True)
