"""One run of the sequence cell, exactly as `benchmark/run.py` makes it, in
which ONE dispatch stands still: `--at` seconds into the window the packed
scorer's `score_topk` sleeps `--hold` seconds before it runs, on whichever
thread made that dispatch (the batcher's worker, or a lone request's
handler), holding the batcher's run lock as a dispatch that waits on the
device does (`docs/operations.md`, "A batch held the batcher").  The
question: does the configuration's admission gate (`serving.max_inflight`)
ride it out, i.e. `failed` 0 and the backlog drained inside the client's
timeout?  `--gate N` overrides the gate (256 is the program's default, the
one this cell was refused under).  Run from the root of the checkout to be
measured:

    cd <checkout> && python3 <repo>/tools/chip_probes/seq_gate_stall.py \
        --at 10 --hold 8 [--gate 256] \
        --workload joyai-flash-l5.serve-steady --seed N --seconds 40 --trace 0

The result line is still the last line of stdout; its latencies are those of
a run with a stall made on purpose, never a cell's result.
"""
import argparse
import os
import sys
import time

BENCH = os.path.join(os.getcwd(), "benchmark")
sys.path[:0] = [BENCH, os.getcwd()]  # run.py, and the program

import run as bench_run  # noqa: E402

ap = argparse.ArgumentParser(add_help=False)
ap.add_argument("--at", type=float, required=True)
ap.add_argument("--hold", type=float, required=True)
ap.add_argument("--gate", type=int, default=None)
mine, rest = ap.parse_known_args()
sys.argv[1:] = rest

from predictionio_tpu.serving.query_server import QueryServer  # noqa: E402
from predictionio_tpu.serving.seqpath import PackedSequenceScorer  # noqa: E402

state = {"go": None, "held": False}
say, score_topk, init = (bench_run.say, PackedSequenceScorer.score_topk,
                         QueryServer.__init__)


def say_and_note_the_window(msg):
    say(msg)
    if msg.startswith("set-up done"):  # the line before the generator's "go"
        state["go"] = time.perf_counter()


def stands_still_once(self, histories, k):
    go = state["go"]
    if (go is not None and not state["held"]
            and time.perf_counter() - go >= mine.at):
        state["held"] = True
        say(f"seq_gate_stall: a dispatch of {len(histories)} rows stands "
            f"still for {mine.hold} s, {time.perf_counter() - go:.2f} s into "
            "the window")
        time.sleep(mine.hold)
    return score_topk(self, histories, k)


def with_this_gate(self, *a, **kw):
    if mine.gate is not None:
        kw["max_inflight"] = mine.gate
    init(self, *a, **kw)
    say(f"seq_gate_stall: the gate admits {self.max_inflight} in flight")


bench_run.say = say_and_note_the_window
PackedSequenceScorer.score_topk = stands_still_once
QueryServer.__init__ = with_this_gate
sys.exit(bench_run.main())
