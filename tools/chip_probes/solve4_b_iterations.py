"""One-off: which iteration of 4-chip ALS goes non-finite, and does buffer
donation matter?"""
import json, os, sys, time
from functools import partial
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import numpy as np, jax, jax.numpy as jnp
from predictionio_tpu.models import als
from predictionio_tpu.parallel.mesh import MeshContext, DATA_AXIS, pad_to_multiple
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import solve4_data
N = int(os.environ.get("SOLVE4_RATINGS", 5_000_000))
inter = solve4_data.make(162_000, 59_000, N)
cfg = als.ALSConfig(rank=10, iterations=3, seed=21)
devs = jax.devices()
results = []

def describe(F, b, n_shards):
    bad = ~np.isfinite(F).all(axis=1)
    rows = np.flatnonzero(bad)
    starts = np.cumsum([0] + [x.shape[1] for x in b.idx])
    local = rows % b.per_shard
    return {"bad_rows": int(bad.sum()), "bad_by_shard": np.bincount(rows // b.per_shard, minlength=n_shards).tolist(),
            "bad_by_bucket": np.bincount(np.searchsorted(starts, local, side="right") - 1, minlength=len(b.widths)).tolist() if len(rows) else [],
            "absmax_finite": float(np.abs(F[~bad]).max()) if (~bad).any() else None}

def loop(ctx, label, roundtrip=False, iters=3):
    t0 = time.time()
    n_shards = ctx.axis_size(DATA_AXIS)
    ub, ib, u_perm, i_perm = als._dense_blocks_for(inter, cfg, n_shards)
    sharding = ctx.sharding(DATA_AXIS, None); sh_rows = ctx.sharding(DATA_AXIS)
    ku, kv = jax.random.split(jax.random.PRNGKey(cfg.seed))
    def init(k, n, perm):
        base = jax.random.normal(k, (pad_to_multiple(n, n_shards), cfg.rank), jnp.float32) / np.sqrt(cfg.rank)
        return jax.device_put(base[np.argsort(perm)], sharding)
    U, V = init(ku, inter.n_users, u_perm), init(kv, inter.n_items, i_perm)
    put = lambda b: tuple(jax.device_put(jnp.asarray(a), sh_rows) for i in range(len(b.widths)) for a in (b.idx[i], b.rat[i], b.msk[i]))
    u_bufs, i_bufs = put(ub), put(ib)
    step = als._make_dense_step(ctx.mesh, ub, ib, cfg)
    out = {"label": label, "n_shards": n_shards, "iterations": []}
    for it in range(iters):
        U, V = step(U, V, u_bufs, i_bufs)
        Uh, Vh = np.asarray(U), np.asarray(V)
        out["iterations"].append({"U": describe(Uh, ub, n_shards), "V": describe(Vh, ib, n_shards)})
        if roundtrip:  # fresh device buffers: nothing of the previous call is aliased
            U, V = jax.device_put(Uh, sharding), jax.device_put(Vh, sharding)
    out["seconds"] = round(time.time() - t0, 1)
    print("RESULT " + json.dumps(out), flush=True)
    results.append(out)

ctx4 = MeshContext.create()
m = als.train_als(ctx4, inter, als.ALSConfig(rank=10, iterations=2, seed=21))
print("RESULT " + json.dumps({"label": "train_als on 4 chips, 2 iterations", "user_factors_finite": bool(np.isfinite(m.user_factors).all()), "item_factors_finite": bool(np.isfinite(m.item_factors).all())}), flush=True)
loop(ctx4, "4 chips, the trainer's step, 3 iterations")
loop(ctx4, "4 chips, factors round-tripped through the host between iterations", roundtrip=True)
loop(MeshContext.create(devices=devs[:2]), "2 chips, 3 iterations")
loop(MeshContext.create(devices=devs[:1]), "1 chip, 3 iterations")

class _NoDonate:
    def __getattr__(self, n):
        return getattr(jax, n)
    @staticmethod
    def jit(f=None, **kw):
        kw.pop("donate_argnums", None)
        return jax.jit(f, **kw) if f is not None else partial(jax.jit, **kw)
als.jax = _NoDonate()
loop(ctx4, "4 chips, step built without donate_argnums")
als.jax = jax
os.makedirs("chiprun_out", exist_ok=True)
json.dump(results, open("chiprun_out/solve4_b_iterations.json", "w"), indent=1)
print("SOLVE4_B_DONE")
