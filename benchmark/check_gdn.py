#!/usr/bin/env python3
"""The hybrid sequence configuration's limits, read on the chip over eight
seeds or more: the sound program, and controls below its stated precision.

    chiprun -- python3 benchmark/check_gdn.py --config olmo-hybrid-7b-l16 \
        --seeds 8 --first-seed 3400000001

One ``PackedSequenceScorer`` is compiled once (the programs take the weights
as an argument).  For each seed: seeded weights and histories; ``--rows``
seeded users dispatched ONE BY ONE plus one packed dispatch per compiled
token count; then, by the cell's own rules (``engines/gdn_hybrid_sequence``,
``reference.check_topk``, ``reference_gdn.compare_trunk``):

* ``head`` — the program's scores against float64 ``h_last . E``;
* ``trunk`` — ``h_last`` against the plain f32 reference;
* ``served`` — the same users dispatched PACKED (four at a time), their
  scores against float64 scores of the one-by-one ``h_last`` (what the
  cell's audit compares a served answer with);
* the controls, which have to come out as NOT correct: the trunk with the
  SwiGLU weights rounded to 8 bits (float8 e4m3; the reference keeps the
  bf16 originals), the reference with the q/k normalisation left out, the
  head with its product accumulated in bf16, and each packed answer held
  against ANOTHER user's ``h_last``.

Writes one JSON line per seed to ``chiprun_out/check_gdn.<config>.jsonl``;
exits 1 if a sound reading passes a limit of the configuration's
``guarantees``, or if the head's or the served control or EVERY trunk
control stays under it.  The benchmark's own runs never call this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=3_400_000_001)
    ap.add_argument("--rows", type=int, default=24)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at the rehearsal widths; never a finding")
    ap.add_argument("--shrink", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pio_bench import reference, reference_gdn, seeded
    from pio_bench.engines import gdn_hybrid_sequence as family
    from predictionio_tpu.models import gdn_hybrid as gh
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    cfg["users"] //= args.shrink
    cfg["items"] //= args.shrink
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("check_gdn: no TPU", file=sys.stderr)
        return 3
    hf = family.model_config(cfg)
    serving = dict(cfg["serving"], **({} if on_chip
                                      else cfg["rehearsal"]["serving"]))
    g, k = cfg["guarantees"], cfg["max_k"]
    mcfg = gh.GDNHybridConfig.from_hf(hf, max_len=serving["max_len"])
    history = dict(cfg["history"],
                   max=min(cfg["history"]["max"], serving["max_len"]))

    def fresh(seed):
        return gh.init_params(mcfg, seed)

    t0 = time.perf_counter()
    params = fresh(args.first_seed)
    scorer = PackedSequenceScorer(
        mcfg, params, max_k=k, ladder=serving["token_ladder"],
        max_rows=serving["max_rows"])
    print(f"[check_gdn] {len(scorer.ladder)} programs compiled and warm in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    def head_check(P, rows, idx, vals, want, tol):
        head = np.asarray(P["head"][:cfg["items"]], dtype=np.float32)
        U = np.stack([r["h_last"] for r in rows])
        res = reference.check_topk(
            U, head, np.arange(len(rows)), idx, vals, want, tol)
        return {n: res[n] for n in ("score_over_tol", "beat_over_tol",
                                    "order_over_tol", "ok")}

    out_path = os.path.join(ROOT, "chiprun_out",
                            f"check_gdn.{args.config}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    bad = 0
    with open(out_path, "a") as out:
        for s in range(args.seeds):
            seed = args.first_seed + s
            t_seed = time.perf_counter()
            if s:  # the last seed's weights went through 8 bits
                scorer._params = params = None  # one model at a time
                scorer._params = params = fresh(seed)
            hists = family.make_histories(
                seed, cfg["users"], cfg["items"], history)
            dep = types.SimpleNamespace(
                seed=seed, cfg=cfg, histories=hists,
                max_len=serving["max_len"])
            users = seeded.rng(seed, seeded.STREAM_RUNGS).choice(
                cfg["users"], args.rows, replace=False)
            alone = [hists.of(int(u), serving["max_len"]) for u in users]
            shapes = list(family.shape_batches(dep, scorer).values())

            def sample(rows_alone, rows_shaped):
                return family.trunk_sample(rows_alone, rows_shaped,
                                           g["trunk_rows_per_shape"])

            # -- sound
            r_alone = family.direct_rows(scorer, [[h] for h in alone])
            r_shaped = family.direct_rows(scorer, shapes)
            rows = r_alone + r_shaped
            head = head_check(
                params, rows, [r["idx"] for r in rows],
                [r["vals"] for r in rows], [k] * len(rows),
                g["score_tolerance"])
            packed = []
            group = max(1, min(4, scorer.ladder[-1] // serving["max_len"]))
            for i in range(0, len(alone), group):
                packed += family.direct_rows(scorer, [alone[i:i + group]])
            served = head_check(
                params, r_alone, [r["idx"] for r in packed],
                [r["vals"] for r in packed], [k] * len(packed),
                g["served_tolerance"])
            t_ref = time.perf_counter()
            judged = sample(r_alone, r_shaped)
            # the reference once a row: the controls below run the SAME
            # histories through other programs
            wants = [reference_gdn.forward(hf, params, r["history"])
                     for r in judged]
            trunk = reference_gdn.rel_errors(judged, wants)
            t_ref = time.perf_counter() - t_ref
            # -- control: each packed answer against ANOTHER user's h_last
            served_ctl = head_check(
                params, r_alone[1:] + r_alone[:1],
                [r["idx"] for r in packed], [r["vals"] for r in packed],
                [k] * len(packed), g["served_tolerance"])
            # -- control: the head's product accumulated in bf16
            U = jnp.asarray(np.stack([r["h_last"] for r in r_alone]),
                            jnp.bfloat16)
            lo_vals, lo_idx = jax.lax.top_k(jnp.dot(
                U, params["head"][:cfg["items"]].T,
                preferred_element_type=jnp.bfloat16).astype(jnp.float32), k)
            head_ctl = head_check(
                params, r_alone, list(np.asarray(lo_idx)),
                list(np.asarray(lo_vals)), [k] * len(r_alone),
                g["score_tolerance"])
            # -- control: the reference without the q/k normalisation
            qk_ctl = reference_gdn.compare_trunk(
                hf, params, r_alone[:4], normalize_qk=False)
            # -- control: the SwiGLU weights through 8 bits
            scorer._params = None  # or the originals stay alive beside them
            for name in sorted(params):
                if name.endswith((".w1", ".w2", ".w3")):
                    params[name] = params[name].astype(
                        jnp.float8_e4m3fn).astype(jnp.bfloat16)
            scorer._params = params
            c_alone = family.direct_rows(scorer, [[h] for h in alone])
            c_shaped = family.direct_rows(scorer, shapes)
            fp8_ctl = reference_gdn.rel_errors(
                sample(c_alone, c_shaped), wants)
            limit = g["trunk_tolerance"]
            sound_ok = (head["ok"] and served["ok"]
                        and trunk["h_last_rel_err"] <= limit)
            trunk_controls_over = [
                name for name, c in (("no_qk_norm", qk_ctl),
                                     ("fp8_swiglu", fp8_ctl))
                if c["h_last_rel_err"] > limit]
            control_fails = (not head_ctl["ok"] and not served_ctl["ok"]
                             and bool(trunk_controls_over))
            bad += (not sound_ok) + (not control_fails)
            line = {
                "seed": seed, "device": jax.devices()[0].device_kind,
                "rows_alone": len(r_alone), "rows_shaped": len(r_shaped),
                "tokens": int(sum(len(r["history"]) for r in rows)),
                "limits": {n: g[n] for n in (
                    "score_tolerance", "served_tolerance",
                    "trunk_tolerance")},
                "head": head, "served": served, "trunk": trunk,
                "control_head_bf16_accumulation": head_ctl,
                "control_served_another_users_state": served_ctl,
                "control_trunk_reference_without_qk_norm": qk_ctl,
                "control_trunk_fp8_swiglu_weights": fp8_ctl,
                "trunk_controls_over_the_limit": trunk_controls_over,
                "sound_ok": sound_ok, "control_fails": control_fails,
                "reference_seconds": round(t_ref, 1),
                "seconds": round(time.perf_counter() - t_seed, 1)}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
    print(f"[check_gdn] {args.seeds} seeds, {bad} verdicts out of place")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
