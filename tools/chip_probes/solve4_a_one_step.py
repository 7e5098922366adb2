"""One-off: localise the non-finite factors of 4-chip sharded ALS training."""
import json, os, sys, time
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import numpy as np, jax, jax.numpy as jnp
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models import als
from predictionio_tpu.parallel.mesh import MeshContext, DATA_AXIS, pad_to_multiple
from predictionio_tpu.tools.loadtest import zipf_mandelbrot_weights
print("devices", jax.devices())
N = int(os.environ.get("SOLVE4_RATINGS", 5_000_000))

def make(n_u, n_i, n=N, seed=21):
    rng = np.random.default_rng(seed)
    cover = max(n_u, n_i)
    users = np.empty(n, np.int64); items = np.empty(n, np.int64)
    users[:cover] = np.arange(cover) % n_u; items[:cover] = np.arange(cover) % n_i
    users[cover:] = rng.choice(n_u, n - cover, p=zipf_mandelbrot_weights(n_u, s=0.7, q=50.0))
    items[cover:] = rng.choice(n_i, n - cover, p=zipf_mandelbrot_weights(n_i, s=1.1, q=50.0))
    inter = Interactions(user=users.astype(np.int32), item=items.astype(np.int32),
        rating=rng.integers(1, 6, n).astype(np.float32), t=np.zeros(n), user_map=None, item_map=None)
    inter.user_map = BiMap({f"u{i}": i for i in range(n_u)}); inter.item_map = BiMap({f"i{i}": i for i in range(n_i)})
    return inter

def one_step(ctx, inter, cfg, label):
    """One jitted iteration through the trainer's own step builder; report
    where U (solved first, from the random V) and V are non-finite."""
    t0 = time.time()
    n_shards = ctx.axis_size(DATA_AXIS)
    ub, ib, u_perm, i_perm = als._dense_blocks_for(inter, cfg, n_shards)
    n_up = pad_to_multiple(inter.n_users, n_shards); n_ip = pad_to_multiple(inter.n_items, n_shards)
    sharding = ctx.sharding(DATA_AXIS, None); sh_rows = ctx.sharding(DATA_AXIS)
    ku, kv = jax.random.split(jax.random.PRNGKey(cfg.seed))
    scale = 1.0 / np.sqrt(cfg.rank)
    def init(k, n_pad, perm):
        base = jax.random.normal(k, (n_pad, cfg.rank), jnp.float32) * scale
        return jax.device_put(base[np.argsort(perm)], sharding)
    U0, V0 = init(ku, n_up, u_perm), init(kv, n_ip, i_perm)
    init_ok = bool(np.isfinite(np.asarray(U0)).all() and np.isfinite(np.asarray(V0)).all())
    def put(b):
        return tuple(jax.device_put(jnp.asarray(a), sh_rows) for i in range(len(b.widths)) for a in (b.idx[i], b.rat[i], b.msk[i]))
    step = als._make_dense_step(ctx.mesh, ub, ib, cfg)
    U, V = step(U0, V0, put(ub), put(ib))
    U, V = np.asarray(U), np.asarray(V)
    out = {"label": label, "n_shards": n_shards, "init_finite": init_ok, "seconds": round(time.time() - t0, 1)}
    for name, F, b in (("U", U, ub), ("V", V, ib)):
        bad = ~np.isfinite(F).all(axis=1)
        per = b.per_shard
        rows = np.flatnonzero(bad)
        out[name] = {"bad_rows": int(bad.sum()), "of": len(F), "per_shard": per,
                     "bad_by_shard": np.bincount(rows // per, minlength=n_shards).tolist(),
                     "first_bad_local": (rows % per)[:6].tolist(), "last_bad_local": (rows % per)[-3:].tolist(),
                     "nan": int(np.isnan(F).any(axis=1).sum()), "inf": int(np.isinf(F).any(axis=1).sum()),
                     "bucket_starts": np.cumsum([0] + [x.shape[1] for x in b.idx]).tolist()[:16], "widths": b.widths[:16]}
    print("RESULT " + json.dumps(out), flush=True)
    return out

devs = jax.devices()
ctx4 = MeshContext.create()
ctx2 = MeshContext.create(devices=devs[:2])
ctx1 = MeshContext.create(devices=devs[:1])
results = []
inter = make(162_000, 59_000)
cfg = als.ALSConfig(rank=10, iterations=1, seed=21)
results.append(one_step(ctx4, inter, cfg, "4 chips, as the smoke trains"))
results.append(one_step(ctx1, inter, cfg, "1 chip of the same host"))
results.append(one_step(ctx2, inter, cfg, "2 chips"))
results.append(one_step(ctx4, inter, als.ALSConfig(rank=10, iterations=1, seed=21, rebalance=False), "4 chips, rebalance off"))
results.append(one_step(ctx4, make(162_016, 59_008), cfg, "4 chips, per-shard rows a multiple of 8 (40504 / 14752)"))
from predictionio_tpu.ops import quantize
orig = quantize.contraction_precision
als_mod_prec = als._F32_PRECISION
import predictionio_tpu.ops.quantize as q
q.contraction_precision = lambda dt: None
results.append(one_step(ctx4, inter, cfg, "4 chips, default matmul precision"))
q.contraction_precision = orig
os.makedirs("chiprun_out", exist_ok=True)
json.dump(results, open("chiprun_out/solve4_a_one_step.json", "w"), indent=1)
print("SOLVE4_A_DONE")
