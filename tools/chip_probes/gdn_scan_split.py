"""What the gated-delta scan's device time is made of (ISSUE 35): one linear
layer's scan alone (30 heads, dk 96, dv 192, bf16) per token rung, DEVICE
time from one profiler trace (module `jit_<variant>` on the line `XLA
Modules`, and inside it the Pallas ops by their own names).

* ``--parent DIR``: another checkout's `ops/gated_delta.py` (PR 34's one
  kernel: `git archive <commit> | tar -x -C DIR`), whole and with parts of
  its step STUBBED on a patched copy of its source — the triangular inverse
  left at the identity (`no_inverse`), the products with the carried state
  gone (`no_state`: K S, Q S, K^T U), `T @ rhs` gone (`no_u`), all of them
  (`bare`: masks, decays, K K^T, Q K^T and (QK) U are left).  The outputs of
  a stubbed kernel are wrong; only its time is read.
* this checkout's scan: whole (`pio.gdn_scan_prep` and `pio.gdn_scan`
  apart), with half the axis a padded tail (`n_real` = T / 2), with the
  pre-pass's doubling levels gone (`prep_no_inverse`) and the step's `T @
  rhs` gone (`step_no_u`), and heads a grid step of either kernel swept.
* the largest absolute difference between the two checkouts' outputs.
* ``--side-ops N``: the N slowest of the whole scan's OTHER device ops, by
  HLO line: the side inputs `_side_inputs` makes in plain XLA, which the
  module's median holds and neither kernel's time does.
* ``--alt name=path``: another draft of this checkout's module (a file
  under the repo root), whole at the sweep rungs, and its output's largest
  difference from this checkout's.

    chiprun -- python tools/chip_probes/gdn_scan_split.py --parent .bench_archive/parent

writes `chiprun_out/gdn_scan_split.json`.  Under `JAX_PLATFORMS=cpu` with
`--rungs 128 --reps 1` it checks the script itself (no device plane: times
are null).
"""
import argparse
import bisect
import glob
import json
import os
import shutil
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.join(ROOT, "predictionio_tpu", "ops", "gated_delta.py")
H, DK, DV = 30, 96, 192

PARENT_STUBS = {
    "whole": [],
    "no_inverse": [("    while b < chunk:\n        off = ", "    while b < 0:\n        off = ")],
    "no_state": [("ks = _dot(k, s0c) * cont", "ks = jnp.zeros((chunk, v.shape[1]), jnp.float32) * cont"),
                 ("qs = _dot(q, s0c) * cont", "qs = jnp.zeros((chunk, v.shape[1]), jnp.float32) * cont"),
                 ("+ _dot((kw * kf).astype(cdt), ub, first))", "+ jnp.zeros((k.shape[1], v.shape[1]), jnp.float32))")],
    "no_u": [("u = _dot32(t, beta * (v.astype(jnp.float32) - cdec * ks))",
              "u = t[:, :1] * beta * (v.astype(jnp.float32) - cdec * ks)")],
}
PARENT_STUBS["bare"] = PARENT_STUBS["no_inverse"] + PARENT_STUBS["no_state"] + PARENT_STUBS["no_u"]
CHANGE_STUBS = {
    "whole": [],
    "prep_no_inverse": [("for off in levels[1:]:", "for off in []:")],
    "step_no_u": [("u = [_dot32(t_ref[hh], beta[hh]", "u = [t_ref[hh][:, :1] * (beta[hh]")],
}


def load(path, name, edits, src=None):
    src = src or open(path).read()
    for old, new in edits:
        assert old in src, (path, old)
        src = src.replace(old, new, 1)
    mod = types.ModuleType(name)
    mod.__file__ = path
    exec(compile(src, path, "exec"), mod.__dict__)
    return mod


def inputs(t, rows_of):
    ks = jax.random.split(jax.random.key(t), 5)
    q, k = (jax.random.normal(ks[i], (H, t, DK), jnp.float32) for i in (0, 1))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * DK ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (H, t, DV), jnp.bfloat16)
    g = -jnp.exp(jax.random.uniform(ks[3], (H, t), jnp.float32, -6, 1))
    beta = jax.random.uniform(ks[4], (H, t), jnp.float32, 0, 2)
    seg = (np.arange(t) // rows_of * rows_of).astype(np.int32)
    return (q.astype(jnp.bfloat16), k.astype(jnp.bfloat16), v, g, beta, jnp.asarray(seg))


def named(name, fn):
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--rungs", default="256,512,1024,2048,4096,8192")
    ap.add_argument("--stub-rungs", default="256,2048,8192")
    ap.add_argument("--sweep-rungs", default="256,8192")
    ap.add_argument("--prep-heads", default="2,6,10,30")
    ap.add_argument("--step-heads", default="1,2,3,6,10")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--side-ops", type=int, default=0, help="list this many of the whole scan's XLA ops (the side "
                    "inputs), slowest first, by HLO line")
    ap.add_argument("--alt", default="", help="name=path[,name=path]: other drafts of this module, timed whole "
                    "at the sweep rungs and compared with this checkout's output")
    a = ap.parse_args()
    rungs = [int(x) for x in a.rungs.split(",")]
    stub_rungs = {int(x) for x in a.stub_rungs.split(",")} & set(rungs)
    sweep_rungs = {int(x) for x in a.sweep_rungs.split(",")} & set(rungs)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind}, "shape": {"heads": H, "dk": DK, "dv": DV},
           "reps": a.reps, "ms": {}, "max_abs_diff_parent_vs_change": {}}
    jobs = []  # (module name, jitted fn, args)

    def job(tag, t, fn, args):
        jobs.append((f"{tag}.T{t}".replace(".", "_"), named(f"{tag}.T{t}".replace(".", "_"), fn), args))

    parent_path = a.parent and os.path.join(a.parent, "predictionio_tpu", "ops", "gated_delta.py")
    for t in rungs:
        args = inputs(t, 200)
        whole = {}
        if parent_path:
            for stub, edits in PARENT_STUBS.items():
                if stub != "whole" and t not in stub_rungs:
                    continue
                m = load(parent_path, f"parent_{stub}", edits)
                job(f"parent.{stub}", t, lambda *x, m=m: m.gdn_scan(*x), args)
                if stub == "whole":
                    whole["parent"] = jobs[-1]
        for stub, edits in CHANGE_STUBS.items():
            if stub != "whole" and t not in stub_rungs:
                continue
            m = load(HERE, f"change_{stub}", edits)
            job(f"change.{stub}", t, lambda *x, m=m, t=t: m.gdn_scan(*x, n_real=t), args)
            if stub == "whole":
                whole["change"] = jobs[-1]
                job("change.half_padded", t, lambda *x, m=m, t=t: m.gdn_scan(*x, n_real=t // 2), args)
        if t in sweep_rungs:
            # heads a grid step: of the pre-pass (the module's first use of the constant), of the step (its second)
            use = "_heads_a_step(heads, HEADS_PER_STEP)"
            for which, values in enumerate((a.prep_heads, a.step_heads)):
                for n in (int(x) for x in values.split(",")):
                    parts = open(HERE).read().split(use)
                    assert len(parts) == 3, "the module no longer names the constant twice"
                    parts[which] += f"_heads_a_step(heads, {n})"
                    parts[1 - which] += use
                    m = load(HERE, f"change_heads{which}_{n}", [], src="".join(parts))
                    job(f"change.{('PREP', 'STEP')[which]}_HEADS{n}", t, lambda *x, m=m, t=t: m.gdn_scan(*x, n_real=t), args)
            for name, path in (x.split("=") for x in a.alt.split(",") if x):
                m = load(os.path.join(ROOT, path), f"alt_{name}", [])
                job(f"alt.{name}", t, lambda *x, m=m, t=t: m.gdn_scan(*x, n_real=t), args)
                got = [np.asarray(f(*args), np.float32) for f in (whole["change"][1], jobs[-1][1])]
                out["max_abs_diff_parent_vs_change"][f"alt.{name}.T{t}"] = float(np.abs(got[0] - got[1]).max())
        if len(whole) == 2:
            got = [np.asarray(w[1](*args), np.float32) for w in (whole["parent"], whole["change"])]
            out["max_abs_diff_parent_vs_change"][f"T{t}"] = {
                "max_abs_diff": float(np.abs(got[0] - got[1]).max()), "max_abs": float(np.abs(got[0]).max()),
                "bit_identical": bool((got[0] == got[1]).all())}
            print("diff", t, out["max_abs_diff_parent_vs_change"][f"T{t}"], flush=True)
    for name, fn, args in jobs:  # compile and warm outside the trace
        jax.block_until_ready(fn(*args))
        print("warm", name, flush=True)
    tdir = os.path.join(out_dir, "gdn_scan_split_trace")
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(tdir)
    for name, fn, args in jobs:
        for _ in range(a.reps):
            jax.block_until_ready(fn(*args))
    jax.profiler.stop_trace()
    mods, ops = [], []
    for path in sorted(glob.glob(tdir + "/plugins/profile/*/*.xplane.pb"))[-1:]:
        data = jax.profiler.ProfileData.from_file(path)
        planes = sorted((p for p in data.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
        for line in (planes[0].lines if planes else ()):
            if line.name == "XLA Modules":
                mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events)
            elif line.name == "XLA Ops":
                ops = [(e.start_ns, e.duration_ns, e.name.split(" = ", 1)[0], e.name) for e in line.events]
    starts = [m[0] for m in mods]
    # module name -> {"runs": [ms], "ops": {own name without its number: ms summed}, "xla": {the other ops' HLO lines: ms summed}}
    per = {}
    for s, e, name in mods:
        per.setdefault(name, {"runs": [], "ops": {}, "xla": {}})["runs"].append((e - s) / 1e6)
    for s, d, name, hlo in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= mods[i][1]:
            continue
        if "gdn_scan" in name:
            key = name.lstrip("%").rsplit(".", 1)[0] if name.rsplit(".", 1)[-1].isdigit() else name.lstrip("%")
            d_ops = per[mods[i][2]]["ops"]
        else:  # the side inputs: XLA's own ops, named by their HLO line
            key, d_ops = hlo[:240], per[mods[i][2]]["xla"]
        d_ops[key] = d_ops.get(key, 0.0) + d / 1e6
    for name, _, _ in jobs:
        hit = [v for k, v in per.items() if k.startswith("jit_" + name + "(") or k == "jit_" + name]
        if not hit:
            out["ms"][name] = None
            continue
        runs = sorted(hit[0]["runs"])
        out["ms"][name] = {"module_median": runs[len(runs) // 2], "runs": len(runs),
                           **{k: v / len(runs) for k, v in hit[0]["ops"].items()}}
        if a.side_ops and name.startswith("change_whole"):
            top = sorted(hit[0]["xla"].items(), key=lambda kv: -kv[1])[:a.side_ops]
            out["ms"][name]["side_input_ops"] = [{"ms": v / len(runs), "hlo": k} for k, v in top]
        print(name, out["ms"][name], flush=True)
    shutil.rmtree(tdir, ignore_errors=True)
    json.dump(out, open(os.path.join(out_dir, "gdn_scan_split.json"), "w"), indent=1)


if __name__ == "__main__":
    main()
