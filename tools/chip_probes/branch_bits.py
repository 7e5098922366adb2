"""PR 48: every rung's program of a packed family on one seeded input, EVERY
output fetched (`RungPrograms.direct`) and kept as the sha256 of its bytes:
run from the parent's checkout and from the change's, the two files must be
equal line for line — the shared residual branches (`latent_moe.
SharedBranches`) are calls that XLA inlines before it fuses, so a rung's
executable computes what the parent's does, in its order.

    cd <checkout> && python3 <repo>/tools/chip_probes/branch_bits.py <out.json> <config name> [seed]

The scorer is built as the cell builds it (the configuration file's widths
on the chip, its `rehearsal` widths off it; seeded weights; its ladder and
rows), without a server.  A rung's input: seeded histories of seeded lengths
that fill 55-100 % of it (fewer where the rows run out), so a padded tail,
row boundaries and the tiles' trip count are all in it.  `bash
tools/chip_probes/pr48_bits.sh <tag>` runs both sides of both families and
compares.
"""
import hashlib
import importlib
import json
import os
import sys

BENCH = os.path.join(os.getcwd(), "benchmark")
sys.path[:0] = [BENCH, os.getcwd()]

import jax  # noqa: E402
import numpy as np  # noqa: E402

out, name = sys.argv[1], sys.argv[2]
seed = int(sys.argv[3]) if len(sys.argv) > 3 else 4800000201
with open(os.path.join(BENCH, "configs", name + ".json")) as f:
    cfg = json.load(f)
engine = importlib.import_module("pio_bench.engines." + cfg["engine"])
from predictionio_tpu.parallel.mesh import MeshContext  # noqa: E402
from predictionio_tpu.serving.seqpath import PackedSequenceScorer  # noqa: E402

MeshContext.create()  # places the compile cache as the cell's run does
on_chip = jax.devices()[0].platform == "tpu"
serving = dict(cfg["serving"])
if not on_chip:
    serving.update(cfg["rehearsal"]["serving"])
family = importlib.import_module(
    "predictionio_tpu.models." + cfg["engine"].replace("_sequence", ""))
config = family.Config.from_hf(engine.model_config(cfg),
                               max_len=serving["max_len"])
sc = PackedSequenceScorer(
    config, family.init_params(config, seed), max_k=cfg["max_k"],
    ladder=serving["token_ladder"], max_rows=serving["max_rows"])
doc = {"config": name, "seed": seed, "platform": jax.devices()[0].platform,
       "device_kind": jax.devices()[0].device_kind,
       "compile_count": sc.compile_count,
       # PR 49: rungs taken from the program store (None: a tree without it)
       "programs_loaded": sc.stats().get("programs_loaded"),
       "warmup_executions": sc.warmup_executions, "rungs": {}}
rng = np.random.default_rng(seed)
for t in sc.ladder:
    left, lens = int(rng.integers(int(0.55 * t) + 1, t + 1)), []
    while left and len(lens) < sc.max_rows:
        lens.append(min(left, int(rng.integers(1, config.max_len + 1))))
        left -= lens[-1]
    hists = [rng.integers(0, config.vocab_size, n).astype(np.int32)
             for n in lens]
    got = sc.forward(hists)  # `RungPrograms.direct`: every output fetched
    assert len(got.pop("batch")["tokens"]) == t, "another rung's program ran"
    doc["rungs"][str(t)] = {
        "tokens": int(sum(lens)), "rows": len(lens),
        "outputs": {k: hashlib.sha256(np.ascontiguousarray(v).tobytes()
                                      ).hexdigest()[:24]
                    for k, v in sorted(got.items())}}
os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
with open(out, "w") as f:
    json.dump(doc, f, indent=1)
print(json.dumps({k: v for k, v in doc.items() if k != "rungs"}))
