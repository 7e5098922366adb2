#!/usr/bin/env python3
"""The state-space / attention configuration with routed experts behind
every layer: its limits, read on the chip over several seeds — the sound
program, and the controls that must fail.

    chiprun -- python3 benchmark/check_smoe.py \
        --config granite-4.0-h-small-l10-ep2 --seeds 8 --first-seed 4600000401

One ``PackedSequenceScorer`` is compiled once (the programs take the weights
as an argument).  For each seed: seeded weights and histories; ``--rows``
seeded users dispatched ONE BY ONE (``--long`` of them drawn from the users
whose history is longer than 4,096 events) plus one packed dispatch per
compiled token count; then, by the cell's own rules
(``engines/ssm_moe_sequence``, ``reference.check_topk``,
``reference_smoe.compare``):

* ``head`` — the program's scores against float64 ``h_last . E``;
* ``trunk`` — the f32 residual stream and ``h_last`` against the plain
  reference given the same held experts, routing forced to the program's
  picks, and how admissible the picks are;
* ``served`` — the same users dispatched PACKED (as many at a time as the
  top rung takes), their scores against float64 scores of the one-by-one
  ``h_last`` (what the cell's audit compares a served answer with);
* the controls, which have to come out as NOT correct: the head with its
  product accumulated in bf16; the trunk with the HELD experts' weights
  rounded to 8 bits (float8 e4m3; the reference keeps the bf16 originals);
  each packed answer held against ANOTHER user's ``h_last``; and the eleven
  mechanisms a program of this family could get wrong with well-formed
  answers, each as the reference computed that way against the sound
  program, on the three shortest trunk rows (``REFERENCE_CONTROLS``).

Writes one JSON line per seed to ``chiprun_out/check_smoe.<config>.jsonl``;
exits 1 if a sound reading passes a limit of the configuration's
``guarantees`` or a control stays under every one.  The benchmark's own runs
never call this.
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

REFERENCE_CONTROLS = {
    "control_shared_expert_dropped": "drop_shared",
    "control_softmax_over_all_72": "softmax_over_all",
    "control_residual_multiplier_left_out": "no_residual_multiplier",
    "control_embedding_multiplier_left_out": "no_embedding_multiplier",
    "control_attention_scale_rsqrt_head": "attention_scale_rsqrt",
    "control_rotary_on_the_attention_layer": "rope_on_attention",
    "control_attention_layer_dropped": "drop_attention",
    "control_scan_dropped": "drop_scan",
    "control_conv_bias_dropped": "no_conv_bias",
    "control_gated_norm_in_two_groups": "gated_norm_two_groups",
    "control_unheld_experts_as_if_held": "unheld_as_held",
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=4_600_000_401)
    ap.add_argument("--rows", type=int, default=6)
    ap.add_argument("--long", type=int, default=2)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at the rehearsal widths; never a finding")
    ap.add_argument("--shrink", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pio_bench import reference, reference_smoe, seeded
    from pio_bench.engines import gdn_hybrid_sequence as fixed
    from pio_bench.engines import ssm_moe_sequence as family
    from predictionio_tpu.models import ssm_moe as sm
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    cfg["users"] //= args.shrink
    cfg["items"] //= args.shrink
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("check_smoe: no TPU", file=sys.stderr)
        return 3
    hf = family.model_config(cfg)
    g, k = dict(cfg["guarantees"]), cfg["max_k"]
    serving = dict(cfg["serving"])
    history = dict(cfg["history"])
    long_over = family.LONG
    if not on_chip:
        serving.update(cfg["rehearsal"]["serving"])
        history.update(cfg["rehearsal"].get("history", {}))
        g.update(cfg["rehearsal"]["guarantees"])
        long_over = serving["max_len"] // 2
    history["max"] = min(history["max"], serving["max_len"])
    mcfg = sm.SSMMoEConfig.from_hf(hf, max_len=serving["max_len"])

    def fresh(seed):
        return sm.init_params(mcfg, seed)

    t0 = time.perf_counter()
    params = fresh(args.first_seed)
    scorer = PackedSequenceScorer(
        mcfg, params, max_k=k, ladder=serving["token_ladder"],
        max_rows=serving["max_rows"])
    print(f"[check_smoe] {len(scorer.ladder)} programs compiled and warm "
          f"in {time.perf_counter() - t0:.1f} s", flush=True)
    lengths = fixed.fixed_lengths(cfg["users"], history)
    long_users = np.flatnonzero(lengths > long_over)
    experts = [n for n in params if n.rpartition(".")[2] in sm.EXPERT_TABLES]

    def head_check(P, rows, idx, vals, want, tol):
        head = np.asarray(P["head"][:cfg["items"]], dtype=np.float32)
        U = np.stack([r["h_last"] for r in rows])
        res = reference.check_topk(
            U, head, np.arange(len(rows)), idx, vals, want, tol)
        return {n: res[n] for n in ("score_over_tol", "beat_over_tol",
                                    "order_over_tol", "ok")}

    def trunk_fails(t):
        return bool(family.trunk_problems(t, g))

    out_path = os.path.join(ROOT, "chiprun_out",
                            f"check_smoe.{args.config}.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    bad = 0
    with open(out_path, "a") as out:
        for s in range(args.seeds):
            seed = args.first_seed + s
            t_seed = time.perf_counter()
            if s:
                scorer._params = params = None  # one model at a time
                scorer._params = params = fresh(seed)
            hists = fixed.make_histories(
                seed, cfg["users"], cfg["items"], history)
            dep = types.SimpleNamespace(
                seed=seed, cfg=cfg, histories=hists,
                max_len=serving["max_len"])
            gen = seeded.rng(seed, seeded.STREAM_RUNGS)
            n_long = min(args.long, len(long_users))
            users = np.concatenate([
                gen.choice(long_users, n_long, replace=False),
                gen.choice(cfg["users"], args.rows - n_long, replace=False)])
            alone = [hists.of(int(u), serving["max_len"]) for u in users]
            shapes = list(family.shape_batches(dep, scorer).values())
            # -- sound
            r_alone = family.direct_rows(scorer, [[h] for h in alone])
            r_shaped = family.direct_rows(scorer, shapes)
            rows = r_alone + r_shaped
            head = head_check(
                params, rows, [r["idx"] for r in rows],
                [r["vals"] for r in rows], [k] * len(rows),
                g["score_tolerance"])
            packed, group, n_tok = [], [], 0
            for h in alone + [None]:  # as many a dispatch as the top rung takes
                if h is None or n_tok + len(h) > scorer.ladder[-1]:
                    packed += family.direct_rows(scorer, [group])
                    group, n_tok = [], 0
                if h is not None:
                    group.append(h)
                    n_tok += len(h)
            served = head_check(
                params, r_alone, [r["idx"] for r in packed],
                [r["vals"] for r in packed], [k] * len(packed),
                g["served_tolerance"])
            t_rows = family.trunk_sample(r_alone, r_shaped,
                                         g["trunk_rows_per_shape"])
            refs = reference_smoe.references(hf, params, t_rows)
            trunk = reference_smoe.compare(t_rows, refs)
            # -- controls: the reference computed wrongly, against the
            # sound program, on the three shortest rows
            short = sorted(t_rows, key=lambda r: len(r["history"]))[:3]
            wrong = {
                name: reference_smoe.compare_trunk(
                    hf, params, short, controls=(control,))
                for name, control in REFERENCE_CONTROLS.items()}
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
            # -- control: the HELD experts' weights through 8 bits, against
            # the references the ORIGINALS gave (the one-by-one rows are the
            # first of `t_rows`, the long ones first of those); forced to
            # the picks of the sound program's rows they were made with
            scorer._params = None  # or the originals stay alive beside them
            for name in experts:
                params[name] = params[name].astype(
                    jnp.float8_e4m3fn).astype(jnp.bfloat16)
            scorer._params = params
            few = range(n_long, len(alone)) if len(alone) > n_long else [0]
            c_alone = family.direct_rows(scorer, [[alone[j]] for j in few])
            trunk_ctl = reference_smoe.compare(c_alone, [refs[j] for j in few])
            sound_ok = (head["ok"] and served["ok"]
                        and not trunk_fails(trunk))
            controls = {
                "control_head_bf16_accumulation": not head_ctl["ok"],
                "control_served_another_users_state": not served_ctl["ok"],
                "control_trunk_fp8_expert_weights": trunk_fails(trunk_ctl),
                **{name: trunk_fails(t) for name, t in wrong.items()},
            }
            control_fails = all(controls.values())
            bad += (not sound_ok) + (not control_fails)
            line = {
                "seed": seed, "device": jax.devices()[0].device_kind,
                "rows_alone": len(r_alone), "rows_shaped": len(r_shaped),
                "trunk_rows": len(t_rows),
                "trunk_rows_long": sum(
                    len(r["history"]) > long_over for r in t_rows),
                "trunk_events": int(sum(len(r["history"]) for r in t_rows)),
                "control_rows_events": [len(r["history"]) for r in short],
                "limits": {n: g[n] for n in (
                    "score_tolerance", "served_tolerance", "trunk_tolerance",
                    "h_last_tolerance", "route_tolerance")},
                "head": head, "served": served, "trunk": trunk,
                "control_head_bf16_accumulation": head_ctl,
                "control_served_another_users_state": served_ctl,
                "control_trunk_fp8_expert_weights": trunk_ctl,
                **wrong,
                "controls_fail": controls,
                "sound_ok": sound_ok, "control_fails": control_fails,
                "seconds": round(time.perf_counter() - t_seed, 1)}
            out.write(json.dumps(line) + "\n")
            out.flush()
            short_line = {
                "seed": seed, "sound_ok": sound_ok,
                "control_fails": control_fails,
                "head": round(head["score_over_tol"], 4),
                "served": round(served["score_over_tol"], 4),
                "trunk": {n: round(trunk[n], 5) for n in (
                    "added_rel_err", "h_last_rel_err", "route_violation")},
                "flips": [trunk["flipped_decisions"], trunk["decisions"]],
                "head_ctl": round(head_ctl["score_over_tol"], 2),
                "served_ctl": round(served_ctl["score_over_tol"], 2),
                "fp8": [round(trunk_ctl[n], 5) for n in (
                    "added_rel_err", "h_last_rel_err")],
                **{name[8:]: [round(t[n], 4) for n in (
                    "added_rel_err", "h_last_rel_err")]
                   for name, t in wrong.items()},
                "not_failing": [n for n, v in controls.items() if not v],
                "seconds": line["seconds"]}
            print(json.dumps(short_line), flush=True)
    print(f"[check_smoe] {args.seeds} seeds, {bad} verdicts out of place")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
