"""The sequence family's serving fast path: a resident model and one device
program per token count, compiled ahead of time.

The second scorer beside :class:`serving.fastpath.BucketedScorer` (which is
ALS-shaped: two factor tables and a ladder of ROW counts).  Here the model's
parameter pytree stays on the device, one dispatch is the histories of the
batcher's rows PACKED end to end on one token axis
(``models/latent_moe.pack``), and the ladder is of TOKEN counts: a dispatch
pads to the next rung, rows pad to a fixed count (a padded row repeats row
0, which costs the score kernel nothing).  Every rung is made ready (lowered
and compiled, or loaded from the program store: ``serving/rungs.py``) and
run once before the scorer is handed out, so no request compiles; as
with the bucketed scorer ``compile_count`` moves only then.

ONE scorer for every packed sequence family.  The family is the module the
model's config class lives in (``models/latent_moe``, ``models/gdn_hybrid``)
and hands over what differs: ``FAMILY`` (its name in ``stats()``),
``pack`` / ``flatten`` (the host side of a dispatch), ``forward_flat`` (the
device program) and ``DispatchCounters`` (its own counters, and ``fetch``:
the program's small outputs they are counted from).  The ladder, the
packing, the cut over the top rung and the sequence counters are here,
once; how a rung's program is compiled, warmed, launched and read back is
:class:`serving.rungs.RungPrograms`, shared with the bucketed scorer.

A dispatch is ``batch_assembly`` (the pack) → ``h2d`` (one small index
array, which rides the compiled call) → ``device_compute`` (the whole
forward pass and the head's top-k, ``pio_seq_forward``, and the one wait
for the (rows, k) values and indices and the program's small counters) →
``d2h`` (the rows asked for), the stage names every dispatch record and
request trace already has.  What stays on the device unless asked for:
``h_last`` and the routing picks (:meth:`forward` returns them, for audits
and tests).
"""

from __future__ import annotations

import functools
import importlib
import threading
from typing import Optional, Sequence

import jax
import numpy as np

from predictionio_tpu.obs import tracing as _tracing
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops.topk import resolve_backend
from predictionio_tpu.serving.program_store import config_statics
from predictionio_tpu.serving.rungs import RungPrograms

# token counts a dispatch pads to; the top rung also bounds one dispatch
TOKEN_LADDER = (256, 512, 1024, 2048, 4096, 8192)
# rows of one dispatch: MicroBatcher's default max_batch
MAX_ROWS = 64
# what BucketedScorer compiles its leaderboard to by default
MAX_K = 100


class PackedSequenceScorer:
    """AOT-compiled packed forward + top-k over a device-resident model."""

    def __init__(self, config, params: dict, *,
                 max_k: int = MAX_K, ladder: Sequence[int] = TOKEN_LADDER,
                 max_rows: int = MAX_ROWS, backend: Optional[str] = None,
                 device=None):
        self.config = config
        # the model family: the module of the config it was handed
        self._family = importlib.import_module(type(config).__module__)
        self.k = min(max_k, config.vocab_size)
        self.ladder = tuple(sorted({int(t) for t in ladder}))
        if config.max_len > self.ladder[-1]:
            raise ValueError(
                f"max_len {config.max_len} exceeds the top rung "
                f"{self.ladder[-1]}: one history must fit one dispatch")
        self.max_rows = int(max_rows)
        self.backend = resolve_backend(backend)
        self._device = device or jax.devices()[0]
        self._lock = threading.Lock()
        # resident: a no-op for arrays already on the device (seeded init),
        # one upload for a model that came back from a pickle
        self._params = jax.device_put(params, self._device)
        self.resident_bytes = sum(
            int(np.prod(v.shape)) * v.dtype.itemsize
            for v in self._params.values())
        self.queries = 0  # rows dispatched
        self.tokens = 0  # real tokens dispatched
        self.padded_tokens = 0
        # (query, key) pairs attention must visit: sum of n(n+1)/2 over rows
        self.causal_pairs = 0
        self.merge_passes = 0
        self._own = self._family.DispatchCounters(config)
        # read back: the leaderboard, the merge counter, the family's own
        fetched = ("values", "indices", "merge") + self._own.fetch
        self._rungs = RungPrograms(
            self._device, self.ladder, self._lower,
            warm_args=lambda t: self._call_args([np.zeros(1, np.int32)], t),
            fetch=lambda out: {n: out[n] for n in fetched if n in out},
            describe=self._describe)
        self._fns = self._rungs.fns

    compile_count = property(lambda self: self._rungs.compile_count)
    warmup_executions = property(lambda self: self._rungs.warmup_executions)

    # -- compile ---------------------------------------------------------
    def _program(self, t: int):
        cfg, k, be = self.config, self.k, self.backend
        forward_flat = self._family.forward_flat

        def pio_seq_forward(P, flat):
            return forward_flat(cfg, P, flat, t, k, score_backend=be)

        return pio_seq_forward

    def _lower_args(self, t: int) -> tuple:
        return self._params, self._put(self._family.pack(
            [np.zeros(1, np.int32)], t, self.max_rows))

    def _lower(self, t: int):
        """The ``t``-token program traced and lowered, ahead of time."""
        return jax.jit(self._program(t)).lower(*self._lower_args(t))

    def _describe(self, t: int) -> tuple:
        """What ``_program(t)`` closes over, for the program store's key,
        and the arguments it is lowered on."""
        return {
            "scorer": "PackedSequenceScorer",
            "family": self._family.__name__,
            "config": config_statics(self.config),
            "rung": t, "k": self.k, "backend": self.backend,
            "max_rows": self.max_rows,
        }, self._lower_args(t)

    def _put(self, batch: dict):
        return jax.device_put(self._family.flatten(batch), self._device)

    # -- dispatch --------------------------------------------------------
    def _chunks(self, histories):
        """Greedy cuts of the rows into dispatches of at most ``max_rows``
        rows and the top rung's tokens."""
        top, start, n_tok = self.ladder[-1], 0, 0
        for i, h in enumerate(histories):
            if i - start == self.max_rows or n_tok + len(h) > top:
                yield start, i
                start, n_tok = i, 0
            n_tok += len(h)
        yield start, len(histories)

    def rung_for(self, n_tokens: int) -> int:
        return next(t for t in self.ladder if t >= n_tokens)

    def forward(self, histories) -> dict:
        """One direct dispatch of ``histories`` (they must fit one), every
        output of the program fetched — ``h_last`` (and a family's own, such as
        the routing picks) included —
        plus the ``batch`` layout.  For audits and tests; counts nothing."""
        n_tok = sum(len(h) for h in histories)
        batch = self._family.pack(
            histories, self.rung_for(n_tok), self.max_rows)
        out = self._rungs.direct(
            len(batch["tokens"]), (self._params, self._put(batch)))
        out["batch"] = batch
        return out

    def _call_args(self, rows, t: int) -> tuple:
        """The ``t``-token program's arguments for ``rows``: the weights as
        they are resident NOW and the packed histories."""
        with _tracing.stage("batch_assembly"):
            batch = self._family.pack(rows, t, self.max_rows)
        with _tracing.stage("h2d"):
            # the flat host array rides the compiled call, whose own
            # argument handling places it: no device_put (a host ↔ device
            # round trip) of its own
            return self._params, self._family.flatten(batch)

    def score_topk(self, histories, k: int):
        """Top-``k`` (indices, values), one row per history (item-index
        arrays, oldest first, each non-empty and at most ``max_len``)."""
        if k > self.k:
            raise ValueError(f"k={k} exceeds compiled top-k width {self.k}")
        idx_parts, val_parts = [], []
        for lo, hi in self._chunks(histories):
            rows = histories[lo:hi]
            n_tok = sum(len(h) for h in rows)
            t = self.rung_for(n_tok)
            # more: rows past the top rung are still to launch
            got, _, _ = self._rungs.run(
                t, functools.partial(self._call_args, rows, t),
                more=hi < len(histories))
            with _tracing.stage("d2h"):
                # the readback's residue on the host: the rows asked for
                idx_rows = got["indices"][: len(rows), :k]
                val_rows = got["values"][: len(rows), :k]
            self._count(t, rows, n_tok, got)
            idx_parts.append(idx_rows)
            val_parts.append(val_rows)
        return np.concatenate(idx_parts), np.concatenate(val_parts)

    def _count(self, t, rows, n_tok, got) -> None:
        with self._lock:
            self.queries += len(rows)
            self.tokens += n_tok
            self.padded_tokens += t - n_tok
            self.causal_pairs += sum(len(h) * (len(h) + 1) // 2 for h in rows)
            self._own.add(t, len(rows), n_tok, got)
            if "merge" in got:
                passes = int(got["merge"][0])
                self.merge_passes += passes
                disp = _tracing.active_dispatch()
                if disp is not None:
                    disp.merge_passes += passes

    def stats(self) -> dict:
        """Counters for ``GET /`` (``fastpath``); monotone except the
        configuration."""
        head = self._params["head"]
        with self._lock:
            return {
                "family": self._family.FAMILY,
                "token_ladder": list(self.ladder),
                "max_rows": self.max_rows,
                "top_k": self.k,
                "backend": self.backend,
                # the head's score tile (every dispatch is max_rows rows),
                # as BucketedScorer reports its rungs'
                "block_items": _score_kernel.tile_report(
                    (self.max_rows,), head.shape[1], head.dtype,
                    head.shape[0],
                ) if self.backend == "fused" else None,
                "resident_bytes": self.resident_bytes,
                **self._rungs.stats(),
                "queries": self.queries,
                "tokens": self.tokens,
                "padded_tokens": self.padded_tokens,
                "causal_pairs": self.causal_pairs,
                "merge_passes": self.merge_passes,
                **self._own.stats(),
            }
