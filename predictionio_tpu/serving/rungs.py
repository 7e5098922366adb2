"""One dispatch protocol for every scorer that serves from a ladder of
programs compiled ahead of time.

:class:`serving.fastpath.BucketedScorer` (rungs of ROW counts) and
:class:`serving.seqpath.PackedSequenceScorer` (rungs of TOKEN counts) differ
in what a rung's program computes, how a batch is padded to it and what they
count.  What they share is HOW a program gets to the device and its answer
back, and what the micro-batcher is told about it:

* construction: every rung's program made ready — LOADED from the
  :mod:`serving.program_store` where that engages and holds it, else traced,
  lowered and compiled (and then kept there) → the :class:`LaunchGate` built
  from the compiler's own byte counts → every rung run once on its input *in
  the form a dispatch hands it over* → the launch lag measured on the lowest
  rung;
* a dispatch (:meth:`RungPrograms.run`): the dispatch record is told the
  ``rung``, the ``lag`` and whether ``more`` launches of the run follow (the
  batcher times its launch-ahead by them) → stage ``device_compute``, holding
  the gate (two programs are enqueued only where both fit the device): the
  compiled call inside ``pio.launch``, the device→host copy of exactly the
  fetched outputs requested on the not-yet-ready arrays the launch returned,
  so the copies queue behind the program, and the ONE wait, a ``device_get``
  that returns when the program has run and its outputs have landed.  A wait
  for the program first and a get after it costs one more wake-up of the
  dispatching thread (PERF.md §6, PR 38).

A change to the readback, the launch or the admission is made here, once.
"""

from __future__ import annotations

import threading
import time
from typing import Callable, Optional, Sequence

import jax

from predictionio_tpu.obs import tracing as _tracing
from predictionio_tpu.serving import program_store as _program_store
from predictionio_tpu.serving.launch_gate import (
    LaunchGate, measure_lag, program_bytes,
)


class RungPrograms:
    """The compiled programs of one scorer, warm, and the way through them.

    The scorer hands over what differs: ``lower(rung)`` (that rung's
    program traced and lowered: what ``.compile()`` is called on),
    ``warm_args(rung)`` (the call's arguments as a dispatch passes them),
    ``fetch(outs)`` (the outputs a dispatch reads back, as a pytree; the
    rest stay on the device) and ``describe(rung)``: the rung's statics as
    the program store's key takes them (JSON-able: everything of the scorer
    that reaches the trace) and the arguments ``lower`` lowers it on.
    Without ``describe`` every rung is compiled.
    """

    def __init__(self, device, ladder: Sequence, lower: Callable,
                 warm_args: Callable, fetch: Callable,
                 describe: Optional[Callable] = None):
        self.ladder = tuple(ladder)
        self._fetch = fetch
        self._lock = threading.Lock()
        # rungs made ready, loaded or compiled
        self.compile_count = 0
        # of those, taken from the program store: no trace, no lowering
        self.programs_loaded = 0
        self.warmup_executions = 0
        self.hits = {r: 0 for r in self.ladder}
        # dispatches whose readback was requested before the wait, counted
        # where it is requested: equals stats()["calls"]
        self.readbacks_queued = 0
        self.fns = {}
        t0 = time.perf_counter()
        store = _program_store.open_store() if describe else None
        saves = store and _program_store.SavesBesideCompiles(store)
        for r in self.ladder:
            self.fns[r] = self._ready(r, lower, describe, saves)
            self.compile_count += 1
        if saves:
            saves.finish()
        # the wall of the ladder: a rung's load from the program store, or
        # its trace, lowering and the backend's compile or the persistent
        # cache's read, rung after rung
        self.compile_s = time.perf_counter() - t0
        # run() is entered by two threads at once (the batcher's
        # launch-ahead)
        self.gate = LaunchGate(
            device, {r: program_bytes(f) for r, f in self.fns.items()})
        # every rung executed once: a lazily-materialized kernel (Pallas
        # included) can never surface its first-dispatch cost under traffic
        self._warm(warm_args)
        # the host hears of a program's end this much after it (the batcher
        # aims its launch-ahead by it): the lowest rung's program on the
        # warm-up's input, twice in a row on the idle device
        low = self.ladder[0]
        args = warm_args(low)
        self.launch_lag_s = measure_lag(
            lambda: self._request(self.fns[low](*args)), jax.device_get)

    def _ready(self, rung, lower, describe, saves):
        """``rung``'s executable: the store's where it holds the program
        this rung would lower to on ONE device, else compiled now and kept
        there for the next deploy (written beside the next rung's backend
        compile, which needs no GIL)."""
        pre = None
        if saves:
            statics, args = describe(rung)
            on = _program_store.lowered_on(args)
            if on is not None:
                pre = _program_store.preimage(statics, args, on)
                loaded = saves.store.load(pre, on)
                if loaded is not None:
                    self.programs_loaded += 1
                    return loaded
        lowered = lower(rung)
        if saves:
            saves.start()
        compiled = lowered.compile()
        if pre is not None:
            saves.add(pre, compiled, lowered)
        return compiled

    def _warm(self, warm_args: Callable) -> None:
        for r in self.ladder:
            jax.block_until_ready(self.fns[r](*warm_args(r)))
            self.warmup_executions += 1

    def _request(self, outs):
        """The fetched outputs, each one's device→host copy requested NOW."""
        back = self._fetch(outs)
        for x in jax.tree_util.tree_leaves(back):
            x.copy_to_host_async()
        return back

    def run(self, rung, staged_args: Callable, more: bool = False):
        """One dispatch's device part at ``rung``.

        ``staged_args()`` returns the call's arguments; it runs once the
        record knows the rung, so the scorer's own host stages
        (``batch_assembly``, ``h2d``) inside it carry ``rung=`` on their
        spans.  ``more``: the scorer has further launches to make in the
        same batch run, so the run's end cannot be told from this one.
        Returns the fetched outputs on the host and the clock before the
        launch and after the wait.
        """
        for tr in _tracing.active_traces():
            tr.annotate(bucket=rung)
        disp = _tracing.active_dispatch()
        if disp is not None:
            disp.rung, disp.lag, disp.more = rung, self.launch_lag_s, more
        args = staged_args()
        with _tracing.stage("device_compute"), self.gate.flight(rung):
            t0 = time.perf_counter()
            with _tracing.launch():
                outs = self.fns[rung](*args)
            # asked for at launch, not after the wake-up
            back = self._request(outs)
            with self._lock:
                self.readbacks_queued += 1
            # the ONE wait, INSIDE the stage: async dispatch can't smear
            # device time past it.  Launched behind a program in flight,
            # the stage also holds the time this one sat queued
            got = jax.device_get(back)
            t1 = time.perf_counter()
        with self._lock:
            self.hits[rung] += 1
        return got, t0, t1

    def direct(self, rung, args):
        """``rung``'s program on ``args``, EVERY output fetched; for audits
        and tests, counts nothing."""
        return jax.device_get(self.fns[rung](*args))

    def stats(self) -> dict:
        """The counters every scorer reports, under their ``GET /`` names."""
        with self._lock:
            return {
                "compile_count": self.compile_count,
                "programs_loaded": self.programs_loaded,
                "compile_s": round(self.compile_s, 4),
                "warmup_executions": self.warmup_executions,
                "bucket_hits": {str(r): n for r, n in self.hits.items()},
                "calls": sum(self.hits.values()),
                "readbacks_queued": self.readbacks_queued,
                # launches that waited for the program in flight because
                # the two would not fit the device together
                "held_launches": self.gate.held,
                "launch_lag_ms": round(self.launch_lag_s * 1e3, 4),
            }
