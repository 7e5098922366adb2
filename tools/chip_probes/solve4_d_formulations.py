"""One-off: which formulation of the batched k x k solve is right on >1 chip?"""
import json, os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from predictionio_tpu.models import als
from predictionio_tpu.parallel.mesh import MeshContext, DATA_AXIS, pad_to_multiple, shard_map
import solve4_data
HI = jax.lax.Precision.HIGHEST
REG, RANK = 0.01, 10

def normal_eq(bufs, opp, n_buckets):
    As, bs, cnts = [], [], []
    for i in range(n_buckets):
        idx, rat, msk = bufs[3 * i][0], bufs[3 * i + 1][0], bufs[3 * i + 2][0]
        W = opp[idx] * msk[:, :, None]
        As.append(jnp.einsum("edk,edl->ekl", W, W, preferred_element_type=jnp.float32, precision=HI))
        bs.append(jnp.einsum("edk,ed->ek", W, rat, preferred_element_type=jnp.float32, precision=HI))
        cnts.append(msk.sum(-1))
    return jnp.concatenate(As), jnp.concatenate(bs), jnp.concatenate(cnts)

def ridge(A, cnt):
    return A + (REG * cnt + 1e-6)[:, None, None] * jnp.eye(RANK, dtype=jnp.float32)[None]

def solve_cho(A, b, cnt):
    c = jax.scipy.linalg.cho_factor(ridge(A, cnt))
    return jax.scipy.linalg.cho_solve(c, b[:, :, None])[:, :, 0]

def solve_unrolled(A, b, cnt):
    """Batched Cholesky + two substitutions from plain ops, k unrolled."""
    A = ridge(A, cnt); k = RANK
    cols = []  # cols[j]: (n, k) column j of L, zeros above the diagonal
    for j in range(k):
        s = A[:, :, j]
        for p in range(j):
            s = s - cols[p] * cols[p][:, j:j + 1]
        d = jnp.sqrt(s[:, j:j + 1])
        col = jnp.where(jnp.arange(k)[None, :] >= j, s / d, 0.0)
        cols.append(col)
    L = jnp.stack(cols, axis=2)  # (n, k, k) lower
    y = []
    for i in range(k):  # L y = b
        acc = b[:, i]
        for p in range(i):
            acc = acc - L[:, i, p] * y[p]
        y.append(acc / L[:, i, i])
    x = [None] * k
    for i in reversed(range(k)):  # L^T x = y
        acc = y[i]
        for p in range(i + 1, k):
            acc = acc - L[:, p, i] * x[p]
        x[i] = acc / L[:, i, i]
    return jnp.stack(x, axis=1)

def run(ctx, inter, label, where, solver):
    n_shards = ctx.axis_size(DATA_AXIS)
    cfg = als.ALSConfig(rank=RANK, iterations=1, seed=21)
    ub, ib, _, _ = als._dense_blocks_for(inter, cfg, n_shards)
    n_ip = pad_to_multiple(inter.n_items, n_shards)
    V0 = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (n_ip, RANK), jnp.float32)) / np.sqrt(RANK)
    Vd = jax.device_put(V0, ctx.sharding(DATA_AXIS, None))
    sh_rows = ctx.sharding(DATA_AXIS)
    bufs = tuple(jax.device_put(jnp.asarray(a), sh_rows) for i in range(len(ub.widths)) for a in (ub.idx[i], ub.rat[i], ub.msk[i]))
    nb = len(ub.widths)
    in_specs = tuple(P(DATA_AXIS) for _ in range(3 * nb)) + (P(),)
    if where == "inside":
        def local(*args):
            A, b, cnt = normal_eq(args[:3 * nb], args[3 * nb], nb)
            return solver(A, b, cnt)
        fn = jax.jit(shard_map(local, mesh=ctx.mesh, in_specs=in_specs, out_specs=P(DATA_AXIS, None)))
    else:
        def local(*args):
            return normal_eq(args[:3 * nb], args[3 * nb], nb)
        sm = shard_map(local, mesh=ctx.mesh, in_specs=in_specs, out_specs=(P(DATA_AXIS, None, None), P(DATA_AXIS, None), P(DATA_AXIS)))
        fn = jax.jit(lambda *a: solver(*sm(*a)))
    xd = fn(*bufs, Vd)
    x = np.asarray(xd)
    rng = np.random.default_rng(0)
    starts = np.cumsum([0] + [a.shape[1] for a in ub.idx])
    V64 = V0.astype(np.float64); err = 0.0
    for _ in range(96):
        p = int(rng.integers(0, n_shards)); j = int(rng.integers(0, ub.per_shard))
        bi = int(np.searchsorted(starts, j, side="right") - 1); r = j - starts[bi]
        idx, rat, msk = ub.idx[bi][p, r], ub.rat[bi][p, r].astype(np.float64), ub.msk[bi][p, r].astype(np.float64)
        W = V64[idx] * msk[:, None]
        ref = np.linalg.solve(W.T @ W + (REG * msk.sum() + 1e-6) * np.eye(RANK), W.T @ rat)
        err = max(err, float(np.abs(x[p * ub.per_shard + j] - ref).max()))
    out = {"label": label, "n_shards": n_shards, "solve": f"{solver.__name__} {where} shard_map",
           "x_max_abs_err": err, "x_absmax": float(np.abs(x).max()), "out_sharding": str(xd.sharding.spec)}
    print("RESULT " + json.dumps(out), flush=True)
    return out

devs = jax.devices()
n = min(4, len(devs))
ctxn, ctx1 = MeshContext.create(devices=devs[:n]), MeshContext.create(devices=devs[:1])
small = solve4_data.make(3000, 2000, 60_000)
full = solve4_data.make(162_000, 59_000, int(os.environ.get("SOLVE4_RATINGS", 5_000_000)))
res = []
for data, name in ((small, "small"), (full, "full width")):
    res.append(run(ctx1, data, f"{name}, 1 chip", "inside", solve_cho))
    res.append(run(ctxn, data, f"{name}, {n} chips", "inside", solve_cho))
    res.append(run(ctxn, data, f"{name}, {n} chips", "outside", solve_cho))
    res.append(run(ctxn, data, f"{name}, {n} chips", "inside", solve_unrolled))
os.makedirs("chiprun_out", exist_ok=True)
json.dump(res, open("chiprun_out/solve4_d_formulations.json", "w"), indent=1)
print("SOLVE4_D_DONE")
