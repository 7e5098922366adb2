"""What does one dispatch of the score program cost on the device, per rung
and per k, and does the fused kernel beat the XLA reference at any rung?

ISSUE 26 read the kernel's time as latency per merge trip (k trips a block,
whatever the rung).  This probe holds tables of the benchmark's width on the
chip (`benchmark/configs/als-wgde-d128.json`: 5.7 M x 128, f32, N(0, 1/rank)
from the seed, no server, no batcher) and times the bare compiled program:

* fused, rungs x k in {1, 10, 100} — time that scales with k and not with
  the rung is the trips; time that scales with neither is the block sweep;
* fused at other `block_items` (k = 100), for the per-grid-step cost, at
  every rung of `--block-rungs`; and the rung-1 user repeated to 8 and to 16
  rows at each width (`tile_rows`: what a 1-row request costs as a whole
  sublane tile — ISSUE 30's row floor is decided from these rows);
* where the tree's kernel takes the mask as a lane row (PR 30 on), the
  program with the row built once against the program that converts a bool
  mask every dispatch (`mask_row`: `hoisted_ms` / `converted_ms`);
* the `reference` backend (XLA gather, matmul, `lax.top_k`) at k = 100, at
  every rung whose program fits beside `--resident-gb` of live tables (the
  deployment holds both tables twice: 11.69 GB).

Each timing is a loop of `--n` calls, every one ended by
`block_until_ready` (`ms`), and the same calls enqueued back to back and
waited for once (`piped_ms`: the device alone, host gaps hidden).  Where
the tree's kernel has `with_stats` (PR 26 on) the merge's counters are
read once per shape, and every row carries a digest of the leaderboard it
returned (equal across trees = bit-identical).  `--tree DIR` imports `predictionio_tpu` from
another checkout (the parent's, unpacked by `git archive`), so one call
can time both:

    chiprun --timeout 1500 -- bash -c '
      python tools/chip_probes/score_sweep.py --label change &&
      python tools/chip_probes/score_sweep.py --label parent \
          --tree .bench_archive/parent'

Writes chiprun_out/score_sweep.<label>.json; under JAX_PLATFORMS=cpu pass
`--shrink 2000 --n 2` to check the script itself.
"""

import argparse
import hashlib
import inspect
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--label", default="change")
    ap.add_argument("--tree", default=ROOT)
    ap.add_argument("--n", type=int, default=10)
    ap.add_argument("--shrink", type=int, default=1)
    ap.add_argument("--seed", type=int, default=2600000001)
    ap.add_argument("--rungs", default="1,8,16,32,64")
    ap.add_argument("--k-rungs", default="1,16,64",
                    help="rungs timed at every k; the others at max_k only")
    ap.add_argument("--ks", default="1,10,100",
                    help="the parent's kernel does not compile at k = 1: give it 2")
    ap.add_argument("--blocks", default="",
                    help="other block_items to time at k = max_k")
    ap.add_argument("--block-rungs", default="",
                    help="rungs timed at every block (default: --k-rungs)")
    ap.add_argument("--reference", type=int, default=1)
    ap.add_argument("--reference-rungs", default="",
                    help="rungs the reference backend runs (default: all)")
    ap.add_argument("--resident-gb", type=float, default=11.69)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.tree))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from predictionio_tpu.ops import score_kernel
    from predictionio_tpu.ops.topk import gather_score_topk

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "als-wgde-d128.json")) as f:
        cfg = json.load(f)
    n_u, n_i = cfg["users"] // args.shrink, cfg["items"] // args.shrink
    rank, max_k = cfg["rank"], cfg["max_k"]
    n_pad = score_kernel.pad_block_items(n_i)
    has_stats = "with_stats" in inspect.signature(
        score_kernel.fused_gather_score_topk).parameters
    # PR 30 on: the tile is sized by the kernel (any block width runs over
    # the table as it is padded, and the mask may come as a lane row)
    geometry = getattr(score_kernel, "tile_geometry", None)

    ku, kv = jax.random.split(jax.random.PRNGKey(args.seed % (2**31)))
    std = (1.0 / rank) ** 0.5
    U = jax.random.normal(ku, (n_u, rank), jnp.float32) * std
    V = jax.random.normal(kv, (n_pad, rank), jnp.float32) * std
    pad_mask = jnp.arange(n_pad) >= n_i
    jax.block_until_ready((U, V, pad_mask))
    rng = np.random.default_rng(args.seed)
    rungs = [int(r) for r in args.rungs.split(",")]
    k_rungs = {int(r) for r in args.k_rungs.split(",")}
    users = {b: jnp.asarray(rng.integers(0, n_u, b).astype(np.int32))
             for b in rungs}

    def timed(fn, u_idx, n, V=V, pad_mask=pad_mask):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(U, V, pad_mask, u_idx).compile()
        row = {"compile_s": time.perf_counter() - t0}
        mem = compiled.memory_analysis()
        if mem is not None:
            row["temp_bytes"] = int(mem.temp_size_in_bytes)
        out = jax.block_until_ready(compiled(U, V, pad_mask, u_idx))
        # the same seed in two trees draws the same tables: equal digests
        # are bit-identical leaderboards
        row["digest"] = hashlib.sha1(
            np.asarray(out[0]).tobytes() + np.asarray(out[1]).tobytes()
        ).hexdigest()[:16]
        if len(out) == 3:
            passes, blocks = (int(x) for x in np.asarray(out[2]).ravel()[:2])
            row["merge_passes"], row["merge_blocks"] = passes, blocks
        ms = []
        for _ in range(n):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(U, V, pad_mask, u_idx))
            ms.append((time.perf_counter() - t0) * 1e3)
        t0 = time.perf_counter()
        outs = [compiled(U, V, pad_mask, u_idx) for _ in range(n)]
        jax.block_until_ready(outs)
        row["piped_ms"] = (time.perf_counter() - t0) * 1e3 / n
        row["ms"] = {"p50": float(np.median(ms)), "min": min(ms),
                     "max": max(ms)}
        return row

    def fused(k, block=None):
        kw = {"with_stats": True} if has_stats else {}

        def fn(U, V, mask, u_idx):
            if block is None:
                return gather_score_topk(U, V, u_idx, k, item_mask=mask,
                                         backend="fused", **kw)
            return score_kernel.fused_gather_score_topk(
                U, V, u_idx, k, mask, block_items=block, **kw)
        return fn

    def reference(U, V, mask, u_idx):
        return gather_score_topk(U, V, u_idx, max_k, item_mask=mask,
                                 backend="reference")

    dev = jax.devices()[0]
    out = {"label": args.label, "tree": os.path.abspath(args.tree),
           "device": str(dev.device_kind), "platform": dev.platform,
           "items_padded": n_pad, "rank": rank, "n": args.n,
           "block_items": score_kernel.BLOCK_I, "with_stats": has_stats,
           "geometry": {
               str(b): list(geometry(b, rank, jnp.float32, n_pad))
               for b in rungs} if geometry else None,
           "fused": [], "blocks": [], "tile_rows": [], "mask_row": [],
           "reference": []}

    def note(kind, row):
        out[kind].append(row)
        print(json.dumps({kind: row}), flush=True)

    for b in rungs:
        for k in [int(x) for x in args.ks.split(",")]:
            if k != max_k and b not in k_rungs:
                continue
            note("fused", {"rung": b, "k": k,
                           **timed(fused(k), users[b], args.n)})
    blocks = [int(x) for x in args.blocks.split(",") if x]
    block_rungs = sorted(
        {int(r) for r in args.block_rungs.split(",") if r} or k_rungs)
    if blocks:
        if geometry:
            Vw, mask_w = V, pad_mask
        else:
            # this tree's kernel needs a block that divides the table, and
            # the table's padding is a multiple of BLOCK_I only (11,133
            # blocks, an odd number): pad a copy to the widest block, the
            # rest excluded
            wide = -(-n_pad // max(blocks)) * max(blocks)
            Vw = jnp.pad(V, ((0, wide - n_pad), (0, 0)))
            mask_w = jnp.arange(wide) >= n_i

        def at_block(kind, row, u_idx):
            try:
                row.update(timed(fused(max_k, row["block_items"]), u_idx,
                                 args.n, Vw, mask_w))
            except Exception as e:  # Mosaic may refuse a tile this wide
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            note(kind, row)

        for block in blocks:
            for b in block_rungs:
                if b in users:
                    at_block("blocks", {
                        "rung": b, "k": max_k, "block_items": block,
                        "items_padded": int(Vw.shape[0])}, users[b])
            if 1 in users:
                # the one-row request as 8 and as 16 equal rows: a repeated
                # row adds no merge pass, so this is the tile's cost alone
                for rows in (8, 16):
                    at_block("tile_rows", {
                        "rung": 1, "tile_rows": rows, "k": max_k,
                        "block_items": block}, jnp.tile(users[1], rows))
        del Vw, mask_w
    if geometry:
        mask_row = jax.block_until_ready(
            jnp.asarray(score_kernel.item_mask_row(np.asarray(pad_mask))))
        for b in rungs:
            conv = timed(fused(max_k), users[b], args.n)
            hoist = timed(fused(max_k), users[b], args.n, V, mask_row)
            note("mask_row", {
                "rung": b, "k": max_k,
                "converted_ms": conv["piped_ms"],
                "hoisted_ms": hoist["piped_ms"],
                "same_answer": conv["digest"] == hoist["digest"]})
        del mask_row
    if args.reference:
        # stand in for the rest of what a deployment keeps on the device
        live = 4.0 * rank * (n_u + n_pad)
        fill = int(max(0.0, args.resident_gb * 1e9 / args.shrink - live))
        filler = jnp.zeros((fill // 4,), jnp.float32)
        jax.block_until_ready(filler)
        out["reference_beside_bytes"] = int(live + fill)
        ref_rungs = [int(r) for r in args.reference_rungs.split(",") if r]
        for b in ref_rungs or rungs:
            row = {"rung": b, "k": max_k}
            try:
                row.update(timed(reference, users[b], max(2, args.n // 2)))
            except Exception as e:  # the finding IS whether it fits
                row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            note("reference", row)
        del filler
    stats = getattr(dev, "memory_stats", lambda: None)() or {}
    out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(ROOT, "chiprun_out", f"score_sweep.{args.label}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
