"""Is the full-probe IVF scan bit-identical to the exact scan ON THE TPU?

The exact scan contracts against the full-width item matrix, the pruned
scan against one cluster block at a time.  XLA:CPU rounds the two 1-3 f32
ulps apart (tests/conftest.py: ``CPU_WIDTH_MAX_ULP``), so the tier-1
identity tests assert ``np.array_equal`` only on a TPU — where tier-1 does
not run.  This probe is that branch's evidence: every factor dtype, the
backend ``auto`` picks and ``reference``, rungs 1 / 8 / 64, at the tests'
shape and at 65,536 x 128; one JSON line each in
``chiprun_out/ivf_identity.jsonl``.

    chiprun -- python tools/chip_probes/ivf_identity.py
"""
import json
import os
import sys

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402

from predictionio_tpu.ops import ivf  # noqa: E402
from predictionio_tpu.ops.quantize import quantize_factors  # noqa: E402
from predictionio_tpu.parallel.mesh import MeshContext  # noqa: E402
from predictionio_tpu.serving.fastpath import BucketedScorer  # noqa: E402


def clustered(n_items, rank, nlist, n_users, seed=7):
    rng = np.random.default_rng(seed)
    centers = (rng.normal(size=(nlist, rank)) * 4.0).astype(np.float32)

    def around(n):
        return (
            centers[rng.integers(0, nlist, size=n)]
            + rng.normal(size=(n, rank)) * 0.25
        ).astype(np.float32)

    V = around(n_items)
    return around(n_users), V


def max_ulp(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(
        np.abs(a - b) / np.spacing(np.maximum(np.abs(a), np.abs(b)))
    ))


def main():
    ctx = MeshContext.create()
    dev = jax.devices()[0]
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/ivf_identity.jsonl", "a") as out:
        for n_items, rank, nlist, k in ((96, 8, 6, 10), (65536, 128, 64, 100)):
            U, V = clustered(n_items, rank, nlist, n_users=64)
            index = ivf.build_index(V, nlist, nprobe=nlist)
            for dtype in ("f32", "bf16", "int8"):
                args, kw = (U, V), {"max_k": k}
                if dtype != "f32":
                    Uq, us = quantize_factors(U, dtype)
                    Vq, vs = quantize_factors(V, dtype)
                    args = (Uq, Vq)
                    kw.update(
                        factor_dtype=dtype, user_scale=us, item_scale=vs
                    )
                for backend in ("auto", "reference"):
                    if backend != "auto":
                        kw["backend"] = backend
                    exact = BucketedScorer(ctx, *args, **kw)
                    pruned = BucketedScorer(
                        ctx, *args, ivf_index=index, retrieval="ivf", **kw
                    )
                    for b in (1, 8, 64):
                        users = np.arange(b) % U.shape[0]
                        ei, ev = map(np.asarray, exact.score_topk(users, k))
                        pi, pv = map(np.asarray, pruned.score_topk(users, k))
                        row = {
                            "platform": dev.platform,
                            "kind": dev.device_kind,
                            "n_items": n_items, "rank": rank,
                            "nlist": nlist, "dtype": dtype,
                            "backend": backend, "rung": b,
                            "idx_equal": bool(np.array_equal(ei, pi)),
                            "val_equal": bool(np.array_equal(ev, pv)),
                            "max_ulp": max_ulp(ev, pv),
                        }
                        out.write(json.dumps(row) + "\n")
                        out.flush()
                        print(row)


if __name__ == "__main__":
    main()
