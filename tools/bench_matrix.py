#!/usr/bin/env python
"""One-shot TPU bench matrix → BENCH_TPU_MANUAL.json.

The four-cell table VERDICT r3 asked for (rebalance × distribution), plus
the bf16 cell, the dense-vs-segment solver A/B, serving latency, and the
measured-utilization fields — all from repeated ``bench.py`` runs so each
cell carries the full honesty contract. Run it on the chip::

    python tools/bench_matrix.py            # full 25M×20 matrix
    BENCH_RATINGS=1000000 BENCH_ITERS=3 python tools/bench_matrix.py  # smoke

Cells run in order of value (primary first) so a run that dies midway
still leaves the most important numbers on disk: the artifact is REWRITTEN
after every cell.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "BENCH_TPU_MANUAL.json")

# EVERY matrix axis is pinned in every cell — an ambient BENCH_REBALANCE/
# BENCH_DTYPE/PIO_ALS_SOLVER left over from a manual run must never change
# what a labeled cell measures. Only the primary cell runs the expensive
# extras (serving latency, solver A/B, measured utilization).
_PIN = {"BENCH_REBALANCE": "1", "BENCH_DTYPE": "f32"}
_LEAN = {"BENCH_SERVING": "0", "BENCH_SOLVER_AB": "0", "BENCH_MEASURED": "0",
         "BENCH_INGEST": "0", "BENCH_OBS": "0", "BENCH_DURABILITY": "0",
         "BENCH_KERNEL": "0", "BENCH_TRAIN_KERNEL": "0", "BENCH_FLEET": "0",
         "BENCH_ELASTIC": "0", "BENCH_SHARDED": "0", "BENCH_RETRIEVAL": "0",
         "BENCH_FRESHNESS": "0", "BENCH_POD": "0", "BENCH_TENANT": "0",
         "BENCH_CANARY": "0"}

# (cell name, env overrides) — primary first
CELLS = [
    ("uniform_rebalance", {**_PIN, "BENCH_DIST": "uniform"}),
    ("zipf_rebalance", {**_PIN, **_LEAN, "BENCH_DIST": "zipf"}),
    ("uniform_norebalance", {**_PIN, **_LEAN, "BENCH_DIST": "uniform",
                             "BENCH_REBALANCE": "0"}),
    ("zipf_norebalance", {**_PIN, **_LEAN, "BENCH_DIST": "zipf",
                          "BENCH_REBALANCE": "0"}),
    ("uniform_bf16", {**_PIN, **_LEAN, "BENCH_DIST": "uniform",
                      "BENCH_DTYPE": "bf16"}),
]


def run_cell(name: str, overrides: dict) -> dict:
    env = dict(os.environ)
    env.pop("PIO_ALS_SOLVER", None)  # cells measure the default solver
    env.update(overrides)
    print(f"=== cell {name}: {overrides}", file=sys.stderr, flush=True)
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, cwd=REPO,
    )
    sys.stderr.write(r.stderr[-2000:])
    if r.returncode != 0:
        return {"error": f"rc={r.returncode}", "stderr_tail": r.stderr[-500:]}
    try:
        record = json.loads(r.stdout.strip().splitlines()[-1])
    except (json.JSONDecodeError, IndexError) as e:
        return {"error": f"unparseable bench output: {e}"}
    record["cell_wall_sec"] = round(time.time() - t0, 1)
    return record


def main() -> int:
    artifact = {
        "generated_unix": time.time(),
        "note": (
            "rebalance × distribution matrix + bf16 cell (VERDICT r3 "
            "item 4); each cell is one full bench.py run with its own "
            "honesty fields"
        ),
        "cells": {},
    }
    # ALL cells stage into the side file; the TPU artifact is (over)written
    # only once EVERY cell proves genuine — a run that lost its chip or any
    # CPU cell can never corrupt prior TPU evidence
    staging = OUT.replace(".json", ".staging.json")
    for name, overrides in CELLS:
        artifact["cells"][name] = run_cell(name, overrides)
        with open(staging, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"=== wrote {staging} after {name}", file=sys.stderr,
              flush=True)

    def genuine(cell: dict) -> bool:
        return cell.get("platform") == "tpu" and not cell.get("fallback")

    all_tpu = all(genuine(c) for c in artifact["cells"].values())
    final = OUT if all_tpu else staging
    if all_tpu:
        os.replace(staging, OUT)
        print(f"=== all cells genuine TPU: promoted to {OUT}",
              file=sys.stderr)
    else:
        print(
            f"=== non-TPU cell(s) present: results stay in {staging}; "
            "the TPU artifact is untouched", file=sys.stderr,
        )
    primary = artifact["cells"].get("uniform_rebalance", {})
    # serving trajectory alongside the training metric: the primary cell
    # runs the http/scorer latency bench; surface its headline numbers at
    # the top level so round-over-round serving regressions are one grep
    http = (primary.get("predict_latency_ms") or {}).get("http") or {}
    serving = {
        "http_p50_ms": http.get("p50"),
        "http_p99_ms": http.get("p99"),
        "qps": http.get("qps"),
        "batch_occupancy": http.get("batch_occupancy"),
        "recompiles": http.get("recompiles"),
    }
    # the Zipf gap: skewed-traffic QPS over uniform QPS with the skew path
    # on (result cache + single-flight + hot-set). `zipf_gate: false` means
    # skewed traffic is SLOWER than uniform — the seed measured 0.57x, the
    # serving caches exist to hold this >= 1.0
    zipf = http.get("zipf") or {}
    serving["zipf_ratio"] = zipf.get("ratio_vs_uniform")
    serving["zipf_hit_rate"] = (zipf.get("zipf") or {}).get("hit_rate")
    serving["zipf_coalesce_rate"] = (zipf.get("zipf") or {}).get(
        "coalesce_rate"
    )
    serving["zipf_gate"] = (
        serving["zipf_ratio"] >= 1.0
        if isinstance(serving["zipf_ratio"], (int, float)) else None
    )
    artifact["serving"] = serving
    # resilience counters from the same loadtest: a NON-chaos bench run
    # must be clean (zero shed/deadline/degraded) — `clean: false` here is
    # a regression gate, same grep-ability as the serving block
    resilience = http.get("resilience") or {
        "shed": None, "deadline_exceeded": None, "breaker_open": None,
        "degraded": None, "query_errors": None, "clean": None,
    }
    artifact["resilience"] = resilience
    # ingest trajectory: the primary cell's sqlite ingest bench — the
    # batched-vs-per-event-commit ratio is THE acceptance number for the
    # write path, so it gets the same top-level grep-ability
    ingest = primary.get("ingest") or {}
    artifact["ingest"] = {
        "vs_baseline": ingest.get("vs_baseline"),
        "batched_events_per_sec": ingest.get("batched_events_per_sec"),
        "buffered_events_per_sec": ingest.get("buffered_events_per_sec"),
        "ack_p99_ms": ingest.get("ack_p99_ms"),
        "avg_flush_batch": ingest.get("avg_flush_batch"),
        "flush_errors": ingest.get("flush_errors"),
    }
    # durability cost from the primary cell: fast-ack throughput under each
    # WAL fsync policy — `group_vs_off` > 2 means the group-commit fsync is
    # no longer amortizing and the durability default is taxing ingest
    durability = primary.get("durability") or {}
    artifact["durability"] = {
        "fast_ack_events_per_sec": durability.get("fast_ack_events_per_sec"),
        "group_vs_off": durability.get("group_vs_off"),
        "always_vs_off": durability.get("always_vs_off"),
        "replay_sec_per_10k": durability.get("replay_sec_per_10k"),
    }
    # telemetry overhead gate from the primary cell: p50 with every request
    # traced vs telemetry compiled out — `gate_pass: false` means the obs
    # subsystem is taxing the hot loop beyond its <3% budget
    obs = primary.get("observability") or {}
    artifact["observability"] = {
        "overhead_ratio": obs.get("overhead_ratio"),
        "gate_pass": obs.get("gate_pass"),
        "p50_on_ms": obs.get("p50_on_ms"),
        "p50_off_ms": obs.get("p50_off_ms"),
        "metric_series": obs.get("metric_series"),
    }
    # serving-utilization gate (ISSUE 8): the live device accountant must
    # report real, non-null rates under the primary cell's loadtest — a
    # null or zero here means the serving path stopped recording
    # cost-annotated dispatches and MFU went back to being unmeasured
    su = http.get("serving_utilization") or {}
    artifact["serving_utilization"] = {
        "busy_fraction": su.get("busy_fraction"),
        "flops_per_s": su.get("flops_per_s"),
        "mfu": su.get("mfu"),
        "hbm_util": su.get("hbm_util"),
        "dispatches": su.get("dispatches"),
        "gate_pass": all(
            isinstance(su.get(k), (int, float)) and su.get(k) > 0
            for k in ("busy_fraction", "flops_per_s", "mfu")
        ),
    }
    # score-kernel gate (ISSUE 9): the fused Pallas kernel must sit at or
    # above the XLA reference — on the analytic intensity model always,
    # and on measured scores/s when the cell ran on silicon — and the
    # int8 factor variant must at least halve the resident footprint
    kern = primary.get("kernel") or {}
    f32_cell = (kern.get("dtypes") or {}).get("f32") or {}
    artifact["kernel"] = {
        "intensity_gain_f32": kern.get("intensity_gain_f32"),
        "int8_resident_vs_f32": kern.get("int8_resident_vs_f32"),
        "measured_gain_f32": f32_cell.get("measured_gain"),
        "measured_scores_per_sec_f32": f32_cell.get(
            "measured_scores_per_sec"
        ),
        "gate_pass": kern.get("gate_pass"),
    }
    # train-kernel gate (ISSUE 13): the fused gather-contract TRAINING
    # kernel must price strictly above the sector-amplified reference on
    # the analytic intensity model for every compute dtype, the int8
    # compute path's one-pass V read must be ≤ half the f32 bytes, and
    # fused-vs-reference f32 factors must come out bit-equal on the cell's
    # live equivalence train (on a TPU the block is skipped with the
    # dispatch rule's reason: an explicit ``fused`` is refused there)
    tkern = primary.get("train_kernel") or {}
    artifact["train_kernel"] = {
        "intensity_gain_f32": tkern.get("intensity_gain_f32"),
        "int8_vread_vs_f32": tkern.get("int8_vread_vs_f32"),
        "factors_bit_equal_f32": tkern.get("factors_bit_equal_f32"),
        "skipped": tkern.get("skipped"),
        "gate_pass": tkern.get("gate_pass"),
    }
    # fleet gate (ISSUE 10): with one injected slow replica, hedged p99
    # must come in at or under HALF the unhedged p99, and a rolling
    # deploy under load must be invisible to clients (zero non-200s) —
    # either failing means the router's tail-tolerance story regressed
    flt = primary.get("fleet") or {}
    roll = flt.get("roll") or {}
    hedge_ratio = flt.get("hedged_vs_unhedged_p99")
    artifact["fleet"] = {
        "qps_1_replica": flt.get("qps_1_replica"),
        "qps_3_replicas": flt.get("qps_3_replicas"),
        "scaling_3_over_1": flt.get("scaling_3_over_1"),
        "p99_unhedged_slow_ms": flt.get("p99_unhedged_slow_ms"),
        "p99_hedged_ms": flt.get("p99_hedged_ms"),
        "hedged_vs_unhedged_p99": hedge_ratio,
        "roll_client_errors": roll.get("client_errors"),
        "roll_ok": roll.get("ok"),
        "gate_pass": (
            isinstance(hedge_ratio, (int, float)) and hedge_ratio <= 0.5
            and roll.get("client_errors") == 0
        ),
    }
    # elastic gate (ISSUE 11): "SLO held while scaling" — a flash-crowd
    # scenario with a seeded mid-surge replica kill -9 must finish with
    # zero client-visible errors and flash-phase p99 within SLO, AND the
    # autoscaler must have both grown and drained the fleet, AND the
    # preemption must actually have fired (a chaos run where the kill
    # never landed proves nothing)
    ela = primary.get("elastic") or {}
    artifact["fleet"]["elastic"] = {
        "p99_while_scaling_ms": ela.get("p99_while_scaling_ms"),
        "slo_p99_ms": ela.get("slo_p99_ms"),
        "client_errors": ela.get("client_errors"),
        "shed": ela.get("shed"),
        "scale_ups": ela.get("scale_ups"),
        "scale_downs": ela.get("scale_downs"),
        "preemptions": ela.get("preemptions"),
        "gate_pass": ela.get("gate_pass"),
    }
    # streaming-freshness gate (ISSUE 17): sustained loadtest ingest with
    # the autoscaler active — every micro-generation must seal and be
    # acked by the full fleet, event→prediction-visible p99 must stay
    # within PIO_FRESHNESS_SLO_MS, and zero fast-acked events may be lost
    fresh = primary.get("freshness") or {}
    artifact["freshness"] = {
        "batches": fresh.get("batches"),
        "sealed": fresh.get("sealed"),
        "visible_p99_ms": fresh.get("visible_p99_ms"),
        "apply_wall_ms": fresh.get("apply_wall_ms"),
        "slo_ms": fresh.get("slo_ms"),
        "lost_acked_events": fresh.get("lost_acked_events"),
        "query_errors": fresh.get("query_errors"),
        "gate_pass": fresh.get("gate_pass"),
    }
    # sharded-serving gate (ISSUE 12): a catalog sized past one device's
    # (simulated) HBM budget, served partitioned under Zipf load — sharded
    # answers must be bit-identical to the replicated reference, per-shard
    # utilization must be non-null, and the popularity-aware plan's
    # max/min attributed busy balance must stay <= 1.5 (the naive
    # round-robin balance rides along uncapped for comparison)
    shd = (primary.get("multichip") or {}).get("sharded_serving") or {}
    shd_plans = shd.get("plans") or {}
    artifact["multichip"] = {
        "sharded_serving": {
            "catalog_bytes": shd.get("catalog_bytes"),
            "per_device_budget_bytes": shd.get("per_device_budget_bytes"),
            "n_shards": shd.get("n_shards"),
            "popularity_busy_balance": (
                shd_plans.get("popularity") or {}
            ).get("busy_balance"),
            "round_robin_busy_balance": (
                shd_plans.get("round_robin") or {}
            ).get("busy_balance"),
            "exact_match": all(
                (p or {}).get("exact_match") is True
                for p in shd_plans.values()
            ) if shd_plans else None,
            "gate_pass": shd.get("gate_pass"),
        },
    }
    # pod-serving gate (ISSUE 18): a real 2-process jax.distributed CPU
    # mesh serves a 2-host-group plan through the two-tier merge — the
    # pod answers must be bit-identical to the single-process replicated
    # reference AND the measured cross-host merge traffic must stay <=
    # the H*B*k*8 derivation in docs/perf_roofline.md (the flat
    # S*B*local_k collective rides along for the reduction factor)
    podb = (primary.get("multichip") or {}).get("pod_serving") or {}
    artifact["multichip"]["pod_serving"] = {
        "processes": podb.get("processes"),
        "host_groups": podb.get("host_groups"),
        "n_shards": podb.get("n_shards"),
        "exact_match": podb.get("exact_match"),
        "cross_host_merge_bytes": podb.get("cross_host_merge_bytes"),
        "cross_host_merge_bytes_derived": podb.get(
            "cross_host_merge_bytes_derived"
        ),
        "reduction_factor": podb.get("reduction_factor"),
        "gate_pass": podb.get("gate_pass"),
    }
    # IVF retrieval gate (ISSUE 16): at the default nprobe the pruned scan
    # must keep recall@10 >= 0.95 against the exact scorer while touching
    # <= 0.2 of the catalog's padded rows — both halves of the trade at
    # once, measured on the primary cell's clustered catalog
    rtr = primary.get("retrieval") or {}
    artifact["retrieval"] = {
        "nlist": rtr.get("nlist"),
        "nprobe": rtr.get("nprobe"),
        "recall_at_10": rtr.get("recall_at_10"),
        "scanned_fraction": rtr.get("scanned_fraction"),
        "analytic_scan_speedup": rtr.get("analytic_scan_speedup"),
        "measured": rtr.get("measured"),
        "gate_pass": rtr.get("gate_pass"),
    }
    # multi-tenant gate (ISSUE 19): one tenant saturating its qps quota
    # must be shed with quota-attributed 503s while the second tenant's
    # p99 stays inside its SLO with zero errors/sheds, AND the composed
    # IVF→fused-ALS pipeline must beat single-stage exact ALS on
    # scores/s at <= 1.5x the exact path's p99
    ten = primary.get("tenant") or {}
    ten_nn = ten.get("noisy_neighbor") or {}
    ten_pipe = ten.get("pipeline") or {}
    artifact["tenant"] = {
        "alpha_shed": (ten_nn.get("alpha") or {}).get("shed"),
        "alpha_shed_reasons": (ten_nn.get("alpha") or {}).get(
            "shed_reasons"
        ),
        "beta_errors": (ten_nn.get("beta") or {}).get("errors"),
        "beta_p99_ms": (ten_nn.get("beta") or {}).get("p99_ms"),
        "slo_ms": ten_nn.get("slo_ms"),
        "noisy_neighbor_gate": ten_nn.get("gate_pass"),
        "pipeline_speedup": ten_pipe.get("speedup"),
        "pipeline_scores_per_s": ten_pipe.get("pipeline_scores_per_s"),
        "exact_scores_per_s": ten_pipe.get("exact_scores_per_s"),
        "pipeline_p99_ms": ten_pipe.get("pipeline_p99_ms"),
        "exact_p99_ms": ten_pipe.get("exact_p99_ms"),
        "pipeline_gate": ten_pipe.get("gate_pass"),
        "gate_pass": ten.get("gate_pass"),
    }
    # canary gate (ISSUE 20): a deliberately bad candidate generation
    # canaried under load must be detected and auto-rolled-back with ZERO
    # client-visible errors, a blast radius no bigger than the canary
    # fraction (1/3 + slack for routing jitter), and a durable quarantine
    # receipt that survives restart (newest-COMPLETED selection resolves
    # the baseline) and refuses a re-deploy of the same generation
    cnr = primary.get("canary") or {}
    blast = cnr.get("blast_radius")
    artifact["canary"] = {
        "rolled_back": cnr.get("rolled_back"),
        "rollback_reason": cnr.get("rollback_reason"),
        "client_errors": cnr.get("client_errors"),
        "client_ok": cnr.get("client_ok"),
        "blast_radius": blast,
        "candidate_p99_ms": cnr.get("candidate_p99_ms"),
        "shadow_pairs": cnr.get("shadow_pairs"),
        "receipt_on_disk": cnr.get("receipt_on_disk"),
        "receipt_blocks_redeploy": cnr.get("receipt_blocks_redeploy"),
        "gate_pass": (
            cnr.get("rolled_back") is True
            and cnr.get("client_errors") == 0
            and isinstance(blast, (int, float)) and blast <= 0.5
            and cnr.get("receipt_on_disk") is True
            and cnr.get("receipt_blocks_redeploy") is True
        ),
    }
    # static-analysis gate: perf numbers from a repo carrying hot-path or
    # race hazards are not publishable — `pio analyze` must report zero
    # errors for the matrix to count
    ana_t0 = time.monotonic()
    ana = subprocess.run(
        [sys.executable, "-m", "predictionio_tpu.tools.cli",
         "analyze", "--format", "json", "--root", REPO],
        cwd=REPO, capture_output=True, text=True,
    )
    ana_wall = time.monotonic() - ana_t0
    # the interprocedural engine must stay cheap enough to run in tier-1:
    # a budget gate on wall time keeps it from quietly becoming unrunnable
    ana_budget_s = 60.0
    try:
        report = json.loads(ana.stdout)
        counts = report.get("counts", {})
        by_analyzer = report.get("by_analyzer") or {}
        artifact["analysis"] = {
            "errors": counts.get("error"),
            "warnings": counts.get("warning"),
            "baselined": report.get("baselined"),
            "errors_by_analyzer": {
                name: sev.get("error", 0)
                for name, sev in sorted(by_analyzer.items())
            },
            "callgraph": report.get("callgraph"),
            "wall_s": round(ana_wall, 2),
            "budget_s": ana_budget_s,
            "gate_pass": (
                counts.get("error") == 0 and ana_wall < ana_budget_s
            ),
        }
    except (json.JSONDecodeError, AttributeError):
        artifact["analysis"] = {
            "errors": None, "warnings": None, "baselined": None,
            "errors_by_analyzer": None, "callgraph": None,
            "wall_s": round(ana_wall, 2), "budget_s": ana_budget_s,
            "gate_pass": False,
            "stderr": (ana.stderr or "")[-500:],
        }
    with open(final, "w") as f:
        json.dump(artifact, f, indent=1)
    print(json.dumps({
        "artifact": final,
        "primary_value": primary.get("value"),
        "on_tpu": all_tpu,
        **serving,
        "resilience": resilience,
        "ingest": artifact["ingest"],
        "durability": artifact["durability"],
        "observability": artifact["observability"],
        "serving_utilization": artifact["serving_utilization"],
        "kernel": artifact["kernel"],
        "train_kernel": artifact["train_kernel"],
        "fleet": artifact["fleet"],
        "multichip": artifact["multichip"],
        "tenant": artifact["tenant"],
        "canary": artifact["canary"],
        "analysis": artifact["analysis"],
    }))
    return 0 if all_tpu else 1


if __name__ == "__main__":
    sys.exit(main())
