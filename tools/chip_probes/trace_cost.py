"""What the always-on instrumentation costs per call on the serving host,
with no profiler session (ISSUE 37): the spans a request and a dispatch
enter, the clock reads, and the faulthandler watchdog a batch run arms and
cancels.  Touches no device; `chiprun -- python3 tools/chip_probes/trace_cost.py`
reads the machine the benchmark's server runs on.  Prints one JSON line, ns a call.
"""
import faulthandler
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402  (annotation() finds the profiler through it)

from predictionio_tpu.obs import tracing  # noqa: E402


def per_call(fn, n=200_000):
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        best = min(best, (time.perf_counter() - t0) / n)
    return round(best * 1e9, 1)


def bare_span():
    with tracing.annotation("pio_req.parse"):
        pass


def id_span():
    with tracing.annotation("pio_req.handle", id="bench-123"):
        pass


def arm_cancel():
    faulthandler.dump_traceback_later(2.0, file=sys.stderr)
    faulthandler.cancel_dump_traceback_later()


rec = tracing.Dispatch(1, False, 1, 0, t_run=0.0, collect_s=0.0,
                       slow_after_s=2.0)
rec.rung = 8
trace = tracing.Trace("bench-123")


def launch_span():
    with tracing.launch():
        pass


def stage_with_record():
    with tracing.stage("h2d"):
        pass


out = {
    "loop_overhead": per_call(lambda: None),
    "perf_counter": per_call(time.perf_counter),
    "span_no_session": per_call(bare_span),
    "span_with_id_no_session": per_call(id_span),
    "trace_annotate": per_call(lambda: trace.annotate(parse_ms=0.1)),
    "faulthandler_arm_and_cancel": per_call(arm_cancel, 20_000),
}
with tracing.scope((), dispatch=rec):
    out["launch_span_no_session"] = per_call(launch_span)
    out["stage_with_a_record_no_session"] = per_call(stage_with_record)
out["cpus"], out["jax"] = os.cpu_count(), jax.__version__
print(json.dumps(out))
