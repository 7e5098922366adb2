"""One-off: which intermediate of the sharded half-step is wrong on >1 chip?
Runs the trainer's reference half-step math under the trainer's shard_map
specs, returns A, b, cnt and x, and compares sampled rows with float64."""
import json, os, sys
from functools import partial
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from predictionio_tpu.models import als
from predictionio_tpu.parallel.mesh import MeshContext, DATA_AXIS, pad_to_multiple, shard_map
import solve4_data
HI = jax.lax.Precision.HIGHEST
REG, RANK = 0.01, 10

def half_local(*args, n_buckets):
    bufs, opp = args[:3 * n_buckets], args[3 * n_buckets]
    As, bs, cnts = [], [], []
    for i in range(n_buckets):
        idx, rat, msk = bufs[3 * i][0], bufs[3 * i + 1][0], bufs[3 * i + 2][0]
        W = opp[idx] * msk[:, :, None]
        As.append(jnp.einsum("edk,edl->ekl", W, W, preferred_element_type=jnp.float32, precision=HI))
        bs.append(jnp.einsum("edk,ed->ek", W, rat, preferred_element_type=jnp.float32, precision=HI))
        cnts.append(msk.sum(-1))
    A, b, cnt = jnp.concatenate(As), jnp.concatenate(bs), jnp.concatenate(cnts)
    x = als._solve_normal_equations(A, b, cnt, jnp.zeros((RANK, RANK)), RANK, REG, False)
    return A, b, cnt, x

def run(ctx, inter, label, v_input="sharded", check_vma=True, sample=64):
    n_shards = ctx.axis_size(DATA_AXIS)
    cfg = als.ALSConfig(rank=RANK, iterations=1, seed=21)
    ub, ib, u_perm, i_perm = als._dense_blocks_for(inter, cfg, n_shards)
    n_ip = pad_to_multiple(inter.n_items, n_shards)
    V0 = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (n_ip, RANK), jnp.float32)) / np.sqrt(RANK)
    sh_rows = ctx.sharding(DATA_AXIS)
    Vd = jax.device_put(V0, ctx.sharding(DATA_AXIS, None) if v_input == "sharded" else ctx.replicated())
    bufs = tuple(jax.device_put(jnp.asarray(a), sh_rows) for i in range(len(ub.widths)) for a in (ub.idx[i], ub.rat[i], ub.msk[i]))
    nb = len(ub.widths)
    fn = jax.jit(shard_map(partial(half_local, n_buckets=nb), mesh=ctx.mesh,
        in_specs=tuple(P(DATA_AXIS) for _ in range(3 * nb)) + (P(),),
        out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS, None)), check_vma=check_vma))
    A, b, cnt, x = (np.asarray(o) for o in fn(*bufs, Vd))
    # float64 reference for sampled (shard, local row) pairs
    rng = np.random.default_rng(0)
    starts = np.cumsum([0] + [a.shape[1] for a in ub.idx])
    errs = {"A": 0.0, "b": 0.0, "cnt": 0.0, "x": 0.0}; scale = {"A": 0.0, "b": 0.0, "cnt": 0.0, "x": 0.0}
    V64 = V0.astype(np.float64)
    for _ in range(sample):
        p = int(rng.integers(0, n_shards)); j = int(rng.integers(0, ub.per_shard))
        bi = int(np.searchsorted(starts, j, side="right") - 1); r = j - starts[bi]
        idx, rat, msk = ub.idx[bi][p, r], ub.rat[bi][p, r].astype(np.float64), ub.msk[bi][p, r].astype(np.float64)
        W = V64[idx] * msk[:, None]
        ref = {"A": W.T @ W, "b": W.T @ rat, "cnt": msk.sum()}
        ref["x"] = np.linalg.solve(ref["A"] + (REG * ref["cnt"] + 1e-6) * np.eye(RANK), ref["b"])
        g = p * ub.per_shard + j
        got = {"A": A[g], "b": b[g], "cnt": cnt[g], "x": x[g]}
        for k in errs:
            errs[k] = max(errs[k], float(np.abs(got[k] - ref[k]).max())); scale[k] = max(scale[k], float(np.abs(ref[k]).max()))
    out = {"label": label, "n_shards": n_shards, "v_input": v_input, "check_vma": check_vma,
           "max_abs_err": errs, "ref_absmax": scale, "x_absmax": float(np.abs(x).max())}
    print("RESULT " + json.dumps(out), flush=True)
    return out

devs = jax.devices()
ctx = {n: MeshContext.create(devices=devs[:n]) for n in (1, 2, 4) if n <= len(devs)}
res = []
small = solve4_data.make(3000, 2000, 60_000)
full = solve4_data.make(162_000, 59_000, int(os.environ.get("SOLVE4_RATINGS", 5_000_000)))
for n, c in ctx.items():
    res.append(run(c, small, f"small, {n} chip(s)"))
for n, c in ctx.items():
    res.append(run(c, full, f"full width, {n} chip(s)"))
if 4 in ctx:
    res.append(run(ctx[4], full, "full width, 4 chips, V handed in replicated", v_input="replicated"))
    res.append(run(ctx[4], full, "full width, 4 chips, check_vma off", check_vma=False))
os.makedirs("chiprun_out", exist_ok=True)
json.dump(res, open("chiprun_out/solve4_c_intermediates.json", "w"), indent=1)
print("SOLVE4_C_DONE")
