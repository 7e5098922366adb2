#!/usr/bin/env python3
"""The window/global-attention configuration's limits, read on the chip
over a dozen seeds: the sound program, and the controls that must fail.

    chiprun -- python3 benchmark/check_window.py --config trinity-large-l5-ep8 \
        --seeds 12 --first-seed 3900000001

One ``PackedSequenceScorer`` is compiled once (the programs take the weights
as an argument).  For each seed: seeded weights and histories; ``--rows``
seeded users dispatched ONE BY ONE (``--long`` of them drawn from the users
whose history is longer than the window) plus one packed dispatch per
compiled token count; then, by the cell's own rules
(``engines/window_moe_sequence``, ``reference.check_topk``,
``reference_wmoe.compare_trunk``):

* ``head`` — the program's scores against float64 ``h_last . E``;
* ``trunk`` — ``h_last`` and the routing picks against the plain reference
  given the same held experts;
* ``served`` — the same users dispatched PACKED (as many at a time as the
  top rung takes), their scores against float64 scores of the one-by-one
  ``h_last`` (what the cell's audit compares a served answer with);
* the controls, which have to come out as NOT correct: the trunk with the
  HELD experts' weights rounded to 8 bits (float8 e4m3; the reference keeps
  the bf16 originals); the head with its product accumulated in bf16; each
  packed answer held against ANOTHER user's ``h_last``; and the three
  mechanisms a program of this family could get wrong with well-formed
  answers, each as the reference computed that way against the sound
  program — the window DROPPED on the rows longer than it, rotary applied on
  the GLOBAL layer, key/value head ``h % 8`` for ``h // 6``.

Writes one JSON line per seed to
``chiprun_out/check_window.<config>.jsonl``; exits 1 if a sound reading
passes a limit of the configuration's ``guarantees`` or a control stays
under every one.  The benchmark's own runs never call this.
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_900_000_001)
    ap.add_argument("--rows", type=int, default=6)
    ap.add_argument("--long", type=int, default=3)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal at the rehearsal widths; never a finding")
    ap.add_argument("--shrink", type=int, default=1)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pio_bench import reference, reference_wmoe, seeded
    from pio_bench.engines import gdn_hybrid_sequence as fixed
    from pio_bench.engines import window_moe_sequence as family
    from predictionio_tpu.models import window_moe as wm
    from predictionio_tpu.serving.seqpath import PackedSequenceScorer

    with open(os.path.join(HERE, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    cfg["users"] //= args.shrink
    cfg["items"] //= args.shrink
    on_chip = jax.devices()[0].platform == "tpu"
    if not on_chip and not args.allow_cpu:
        print("check_window: no TPU", file=sys.stderr)
        return 3
    hf = family.model_config(cfg)
    g, k = dict(cfg["guarantees"]), cfg["max_k"]
    serving = dict(cfg["serving"])
    history = dict(cfg["history"])
    if not on_chip:
        serving.update(cfg["rehearsal"]["serving"])
        history.update(cfg["rehearsal"].get("history", {}))
        g.update(cfg["rehearsal"]["guarantees"])
    history["max"] = min(history["max"], serving["max_len"])
    window = hf["sliding_window"]
    mcfg = wm.WindowMoEConfig.from_hf(hf, max_len=serving["max_len"])

    def fresh(seed):
        return wm.init_params(mcfg, seed)

    t0 = time.perf_counter()
    params = fresh(args.first_seed)
    scorer = PackedSequenceScorer(
        mcfg, params, max_k=k, ladder=serving["token_ladder"],
        max_rows=serving["max_rows"])
    print(f"[check_window] {len(scorer.ladder)} programs compiled and warm "
          f"in {time.perf_counter() - t0:.1f} s", flush=True)
    lengths = fixed.fixed_lengths(cfg["users"], history)
    long_users = np.flatnonzero(lengths > window)

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
                            f"check_window.{args.config}.jsonl")
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

            def trunk_rows(rows_alone, rows_shaped):
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
            t_rows = trunk_rows(r_alone, r_shaped)
            trunk = reference_wmoe.compare_trunk(hf, params, t_rows)
            # -- controls: the reference computed wrongly, against the
            # sound program.  The window dropped shows only beyond it
            over = [r for r in t_rows if len(r["history"]) > window]
            short = sorted(t_rows, key=lambda r: len(r["history"]))[:3]
            no_window = reference_wmoe.compare_trunk(
                hf, params, over, controls=("drop_window",))
            rope_global = reference_wmoe.compare_trunk(
                hf, params, short, controls=("rope_on_global",))
            kv_modulo = reference_wmoe.compare_trunk(
                hf, params, short, controls=("kv_modulo",))
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
            # -- control: the held experts' weights through 8 bits
            scorer._params = None  # or the originals stay alive beside them
            for name in sorted(params):
                if ".e_w" in name:
                    params[name] = params[name].astype(
                        jnp.float8_e4m3fn).astype(jnp.bfloat16)
            scorer._params = params
            few = alone[n_long:] or alone[:1]
            c_alone = family.direct_rows(scorer, [[h] for h in few])
            scorer._params = params = None
            scorer._params = params = fresh(seed)  # the reference's originals
            trunk_ctl = reference_wmoe.compare_trunk(hf, params, c_alone)
            sound_ok = (head["ok"] and served["ok"]
                        and not trunk_fails(trunk))
            controls = {
                "control_head_bf16_accumulation": not head_ctl["ok"],
                "control_served_another_users_state": not served_ctl["ok"],
                "control_trunk_fp8_held_expert_weights":
                    trunk_fails(trunk_ctl),
                "control_window_dropped": bool(over) and trunk_fails(
                    no_window),
                "control_rope_on_the_global_layer": trunk_fails(rope_global),
                "control_kv_head_modulo": trunk_fails(kv_modulo),
            }
            control_fails = all(controls.values())
            bad += (not sound_ok) + (not control_fails)
            line = {
                "seed": seed, "device": jax.devices()[0].device_kind,
                "rows_alone": len(r_alone), "rows_shaped": len(r_shaped),
                "trunk_rows": len(t_rows), "trunk_rows_over_window": len(over),
                "trunk_events": int(sum(len(r["history"]) for r in t_rows)),
                "limits": {n: g[n] for n in (
                    "score_tolerance", "served_tolerance", "trunk_tolerance",
                    "h_last_tolerance", "route_tolerance")},
                "head": head, "served": served, "trunk": trunk,
                "control_head_bf16_accumulation": head_ctl,
                "control_served_another_users_state": served_ctl,
                "control_trunk_fp8_held_expert_weights": trunk_ctl,
                "control_window_dropped": no_window,
                "control_rope_on_the_global_layer": rope_global,
                "control_kv_head_modulo": kv_modulo,
                "controls_fail": controls,
                "sound_ok": sound_ok, "control_fails": control_fails,
                "seconds": round(time.perf_counter() - t_seed, 1)}
            out.write(json.dumps(line) + "\n")
            out.flush()
            print(json.dumps(line), flush=True)
    print(f"[check_window] {args.seeds} seeds, {bad} verdicts out of place")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
