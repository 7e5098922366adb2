#!/bin/bash
# PR 48: where a cell's set-up goes, run after run in ONE call: each run is a
# cell's run through `setup_in_cell.py` from an unpacked archive under
# .bench_archive/ (`parent`: `git archive` of the parent with this tree's
# BENCHMARK.json and benchmark/ laid over it; `change`: `git archive $(git
# write-tree)`), in the order given — whole (set-up, a 40 s window, the
# audit: the result line's `setup_s` beside the split) or, with
# SETUP_IN_CELL_STOP=1, the set-up alone.
#   chiprun --timeout 3000 -- bash tools/chip_probes/pr48_setup.sh <tag> <side>:<cell>:<seed>:<trace 0|1>[:<VAR>=<value>[,<VAR>=<value>]] ...
# A side's first run of a cell may find its programs uncompiled (a cold run:
# `cache_misses` > 0 says so); trace and lowering are paid either way.
# Results: chiprun_out/<tag>.setup_in_cell.jsonl (one line a run: the result
# line's metrics, seconds, phases, per rung trace / lowering / cache retrieval
# / backend compile and the inner traces, cache hits and misses, the cache
# directory's bytes before and after, since PR 49 `programs_loaded` and the
# program store's bytes and entries beside them), each run's log and whole JSON under
# chiprun_out/<tag>/.
tag=$1; shift
root=$(pwd); mkdir -p $root/chiprun_out/$tag
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset} JAX_COMPILATION_CACHE_MAX_SIZE=${JAX_COMPILATION_CACHE_MAX_SIZE:-unset}"
n=$(ls $root/chiprun_out/$tag/*.json 2>/dev/null | wc -l)
for run in "$@"; do
  IFS=: read side cell seed trace vars <<< "$run"
  n=$((n + 1)); name=$(printf "%02d" $n).$side.$cell.seed$seed.trace$trace${vars:+.$vars}
  ( cd $root/.bench_archive/$side && env ${vars//,/ } \
      python3 $root/tools/chip_probes/setup_in_cell.py \
      $root/chiprun_out/$tag/$name.json \
      --workload $cell --seed $seed --seconds 40 --trace $trace ) \
    > $root/chiprun_out/$tag/$name.log 2>&1
  echo "$name rc=$? $(grep -E "set-up done|latency ms" $root/chiprun_out/$tag/$name.log | cut -c1-160 | tr '\n' ' ')"
  tail -1 $root/chiprun_out/$tag/$name.log | grep '^{"correct"' > $root/chiprun_out/$tag/$name.result
  [ -n "$JAX_COMPILATION_CACHE_DIR" ] && du -sm $JAX_COMPILATION_CACHE_DIR 2>/dev/null
done
python3 - $tag <<'PY'
import glob, json, sys
tag = sys.argv[1]
with open(f"chiprun_out/{tag}.setup_in_cell.jsonl", "w") as out:
    for path in sorted(glob.glob(f"chiprun_out/{tag}/*.json")):
        d = json.load(open(path))
        order, side, rest = path.split("/")[-1][:-5].split(".", 2)
        try:
            result = json.load(open(path[:-5] + ".result"))
        except ValueError:  # a set-up alone, or a run that failed
            result = None
        ph, per_rung = {}, {}
        for p in d["phases"]:
            ph[p["name"]] = round(ph.get(p["name"], 0.0) + p["s"], 3)
        for p in d["phases"]:
            if not p["name"].startswith("compile."):
                continue
            ev = [e for e in d["events"]
                  if p["at"] <= e["at"] <= p["at"] + p["s"] + 1e-3]
            kind = lambda k: sorted(e["s"] for e in ev if k in e["name"])
            traces = kind("jaxpr_trace_duration")
            per_rung[p["name"][8:]] = {
                "trace": round(traces[-1], 3) if traces else None,
                "lower": round(sum(kind("jaxpr_to_mlir_module")), 3),
                "cache_retrieval": round(sum(kind("cache_retrieval")), 3),
                "backend_compile": round(sum(kind("backend_compile")), 3),
                "inner_traces": [round(s, 3) for s in traces[:-1]]}
        ca, cb = d.get("cache_after") or {}, d.get("cache_before") or {}
        sa, sb = d.get("store_after") or {}, d.get("store_before") or {}
        fp = ((d.get("root") or {}).get("fastpath") or [{}])[0]
        line = {
            "run": int(order), "side": side, "cell_and": rest,
            "result": result and {
                "correct": result["correct"], "failed": result["failed"],
                "attempted": result["attempted"], "device": result["device"],
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()}},
            "seconds": d.get("seconds"), "phases": ph, "per_rung": per_rung,
            "cache_hits": sum(v for k, v in d["counts"].items()
                              if k.endswith("cache_hits")),
            "cache_misses": sum(v for k, v in d["counts"].items()
                                if k.endswith("cache_misses")),
            "counts": {k.rsplit("/", 1)[-1]: v for k, v in d["counts"].items()},
            "cache_bytes": [cb.get("bytes"), ca.get("bytes")],
            "cache_entries": [cb.get("entries"), ca.get("entries")],
            # PR 49: the program store beside the cache, before and after
            "programs_loaded": fp.get("programs_loaded"),
            "store_bytes": [sb.get("bytes"), sa.get("bytes")],
            "store_entries": [sb.get("entries"), sa.get("entries")],
            "written_mb": [round(r["bytes"] / 1e6, 1) for r in ca.get("large", [])
                           if r["written_by_this_run"]],
            "stats": {k: fp.get(k) for k in (
                "compile_count", "programs_loaded", "compile_s",
                "warmup_executions", "branch_traces", "branch_calls")}}
        out.write(json.dumps(line) + "\n")
        tl = {t: (r["trace"], r["lower"], r["cache_retrieval"], r["backend_compile"])
              for t, r in per_rung.items()}
        print(order, side, rest, json.dumps({
            "result": result and {
                "correct": result["correct"], "failed": result["failed"],
                **{k: round(v["value"], 3) for k, v in result["metrics"].items()
                   if k in ("setup_s", "serve.p50_ms", "serve.p95_ms",
                            "setup.compile_s", "setup.program_load_share",
                            "setup.branch_trace_share")}},
            "seconds": line["seconds"], "compile": {k: v for k, v in ph.items() if k.startswith("compile.")},
            "trace,lower,retrieval,backend": tl,
            "hits": line["cache_hits"], "misses": line["cache_misses"],
            "cache_mb": [round((b or 0) / 1e6, 1) for b in line["cache_bytes"]],
            "store_mb": [round((b or 0) / 1e6, 1) for b in line["store_bytes"]],
            "stats": line["stats"]}))
PY
