"""One-off: what precision do the f32 contractions get on the chip by default?"""
import json, os, sys
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
import jax, jax.numpy as jnp, numpy as np
print("device", jax.devices()[0].platform, jax.devices()[0].device_kind)
rng = np.random.default_rng(0)
out = {}
# normal-equation contraction, one dense bucket: (n_b, D, k) = (4096, 128, 10)
W = (rng.standard_normal((4096, 128, 10)) / np.sqrt(10)).astype(np.float32)
r = rng.integers(1, 6, (4096, 128)).astype(np.float32)
A64 = np.einsum("edk,edl->ekl", W.astype(np.float64), W.astype(np.float64))
b64 = np.einsum("edk,ed->ek", W.astype(np.float64), r.astype(np.float64))
for name, prec in (("default", None), ("highest", jax.lax.Precision.HIGHEST)):
    A = jax.jit(lambda W: jnp.einsum("edk,edl->ekl", W, W, preferred_element_type=jnp.float32, precision=prec))(W)
    b = jax.jit(lambda W, r: jnp.einsum("edk,ed->ek", W, r, preferred_element_type=jnp.float32, precision=prec))(W, r)
    out[f"normal_eq_A_{name}"] = float(np.abs(np.asarray(A) - A64).max() / np.abs(A64).max())
    out[f"normal_eq_b_{name}"] = float(np.abs(np.asarray(b) - b64).max() / np.abs(b64).max())
# scoring contraction (64, 10) x (59000, 10)^T
U = (rng.standard_normal((64, 10)) / np.sqrt(10)).astype(np.float32)
V = (rng.standard_normal((59000, 10)) / np.sqrt(10)).astype(np.float32)
S64 = U.astype(np.float64) @ V.astype(np.float64).T
scale = np.linalg.norm(U, axis=1).max() * np.linalg.norm(V, axis=1).max()
for name, prec in (("default", None), ("highest", jax.lax.Precision.HIGHEST)):
    S = jax.jit(lambda U, V: jnp.matmul(U, V.T, precision=prec))(U, V)
    out[f"score_{name}"] = float(np.abs(np.asarray(S) - S64).max() / scale)
# batched 10x10 Cholesky solve (no precision argument exists for it)
A = A64 + 0.5 * np.eye(10)
x64 = np.linalg.solve(A, b64[..., None])[..., 0]
def solve(A, b):
    c = jax.scipy.linalg.cho_factor(A)
    return jax.scipy.linalg.cho_solve(c, b[:, :, None])[:, :, 0]
x = jax.jit(solve)(A.astype(np.float32), b64.astype(np.float32))
out["cho_solve_rel_err"] = float(np.abs(np.asarray(x) - x64).max() / np.abs(x64).max())
out["cho_solve_cond_max"] = float(np.linalg.cond(A).max())
print("PRECISION " + json.dumps(out))
os.makedirs("chiprun_out", exist_ok=True)
json.dump(out, open("chiprun_out/precision.json", "w"), indent=1)
