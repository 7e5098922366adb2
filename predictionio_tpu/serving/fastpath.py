"""Bucketed pre-compiled serving fast path: device-resident score+top-k.

The query server's device work is one fused gather→score→top-k program
(:func:`predictionio_tpu.ops.topk.gather_score_topk`), but naively jitting
it per batch size would retrace for every distinct size and pay compile
latency on live traffic.  This module removes both costs:

* **Bucket ladder** — batches are padded up to a fixed ladder of sizes
  (:data:`BUCKETS`); the padded tail rows are scored and discarded on host
  (they cost one extra matmul row each), and the padded ITEM tail is masked
  inside the program via ``top_k_with_mask``.  Only ``len(BUCKETS)``
  programs ever exist.
* **AOT warmup** — every bucket's program is compiled at construction time
  with ``jax.jit(...).lower(...).compile()`` (deploy/reload, never on a
  request thread), so no query ever pays trace or compile latency.  Calls
  go straight to the pre-built executable; a recompile is structurally
  impossible on the serve path, and :meth:`BucketedScorer.stats` exposes
  the compile/hit counters that prove it.

Factor placement is backend-dependent and happens ONCE at construction
(Cloudburst's model-next-to-compute rule, arXiv:2007.05832); per-call
traffic is the (B,) user-index upload and the (B, k) result readback.
``PIO_SERVING_SHARDING`` selects between two placements:

* **replicated** — a full copy of the factor matrices on every device;
  the catalog is capped at a single chip's HBM.
* **sharded** — item factors PARTITIONED across the mesh per an explicit
  :class:`~predictionio_tpu.serving.sharding.ShardingPlan`: each query
  fans out, every shard runs the same fused ``gather_score_topk`` over
  only its local item block, and one small all-gather of per-shard
  (B, local_k) leaderboards plus an on-device two-key merge
  (``ops.topk.merge_topk``) yields answers bit-identical to the
  replicated reference — the (B, n_items) score matrix never crosses a
  link.  ``auto`` (the default) serves sharded only when the model
  declares a plan AND the mesh has the devices for it, so every existing
  caller keeps replicated behavior unchanged.

RETRIEVAL (``PIO_RETRIEVAL=exact|ivf|auto``, default ``auto``): with an
:class:`~predictionio_tpu.ops.ivf.IVFIndex` declared at publish, the
replicated placement can serve the IVF-pruned scan instead of the full
one — the compiled program scores the batch against the ``nlist``
centroids, picks a probe set of clusters, and runs the SAME fused
``gather_score_topk`` over only those clusters' contiguous blocks (laid
out by ``build_layout`` exactly like shard blocks), merging per-probe
leaderboards with ``merge_topk``.  Because per-cluster blocks are
ascending by global id, probing EVERY cluster (``nprobe == nlist``)
returns answers bit-identical to the exact path — the same tie-order
proof as the sharded merge.  The probe budget scales with the rung —
``P_b = clamp(nprobe·b, min_probes, nlist)`` — so the per-query
amortized scanned fraction stays ≈ ``nprobe/nlist`` at every batch size
while the probed set covers each row's union of likely clusters
(``min_probes`` guarantees the probed clusters always hold ≥ k real
items).  IVF composes with the replicated placement only; a sharded plan
takes precedence and retrieval degrades to exact with a warning.

HOW a rung's program is compiled, warmed, launched and read back — and what
the micro-batcher is told about it — is :class:`serving.rungs.RungPrograms`,
shared with the packed sequence scorer; the placements, the padding, the
chunking over the top rung and the counters are here.
"""

from __future__ import annotations

import functools
import logging
import os
import threading
from typing import Optional

import jax
import numpy as np

from predictionio_tpu.obs import devprof as _devprof
from predictionio_tpu.obs import tracing as _tracing
from predictionio_tpu.ops import ivf as _ivf
from predictionio_tpu.ops import quantize as _quantize
from predictionio_tpu.ops import score_kernel as _score_kernel
from predictionio_tpu.ops.topk import (
    gather_score_topk, merge_topk, resolve_backend, two_tier_merge_topk,
)
from predictionio_tpu.parallel.mesh import (
    DATA_AXIS, HOST_AXIS, MeshContext, pad_to_multiple, shard_map,
)
from predictionio_tpu.serving import sharding as _sharding
from predictionio_tpu.serving.rungs import RungPrograms

logger = logging.getLogger(__name__)

# The batch-size ladder. Powers of two above a singleton lane: 1 serves the
# trickle case with zero padding, 64 matches MicroBatcher's default
# max_batch. Rows between rungs pad to the next rung (worst waste: 6 rows
# at rung 8); whether a batch arrives between rungs is the batcher's
# decision (serving/batching.MicroBatcher._cut): it hands over all its rows
# when a run at the next rung finishes them sooner than a cut at the rung
# below and a second run would, by the run times it has measured per rung.
BUCKETS = (1, 8, 16, 32, 64)


def bucket_for(n: int, buckets=BUCKETS) -> Optional[int]:
    """Smallest ladder rung ≥ n, or None when n overflows the ladder."""
    for b in buckets:
        if n <= b:
            return b
    return None


SERVING_BACKENDS = ("replicated", "sharded", "auto")


def resolve_serving_backend(
    requested: Optional[str] = None,
    *,
    plan=None,
    ctx: Optional[MeshContext] = None,
) -> str:
    """Resolve the factor placement: ``"replicated"`` or ``"sharded"``.

    ``requested`` overrides ``PIO_SERVING_SHARDING`` (default ``auto``).
    ``auto`` serves sharded only when a :class:`ShardingPlan` with more
    than one shard is declared AND the mesh has at least that many
    devices — on a 1-device mesh, or for any model without a plan, it is
    exactly the replicated path, so existing callers see no behavior
    change.  An explicit ``sharded`` without a plan is a configuration
    error; a plan wider than the mesh degrades to replicated with a
    warning (the plan is an optimization, never a point of failure).
    """
    req = (
        requested or os.environ.get("PIO_SERVING_SHARDING") or "auto"
    ).strip().lower()
    if req not in SERVING_BACKENDS:
        raise ValueError(
            f"PIO_SERVING_SHARDING must be one of {SERVING_BACKENDS}, "
            f"got {req!r}"
        )
    if req == "replicated":
        return "replicated"
    n_dev = ctx.n_devices if ctx is not None else 1
    if req == "sharded":
        if plan is None:
            raise ValueError(
                "PIO_SERVING_SHARDING=sharded requires a ShardingPlan "
                "declared at publish (PIO_SHARD_COUNT/PIO_SHARD_HBM_BUDGET)"
            )
        if plan.n_shards > n_dev:
            logger.warning(
                "sharding plan wants %d shards but the mesh has %d "
                "devices; serving replicated", plan.n_shards, n_dev,
            )
            return "replicated"
        return "sharded"
    # auto
    if plan is not None and 1 < plan.n_shards <= n_dev:
        return "sharded"
    return "replicated"


class BucketedScorer:
    """Pre-compiled per-bucket score+top-k over device-resident factors."""

    def __init__(
        self,
        ctx: MeshContext,
        user_factors: np.ndarray,
        item_factors: np.ndarray,
        max_k: int = 100,
        buckets=BUCKETS,
        factor_dtype: str = "f32",
        user_scale: Optional[np.ndarray] = None,
        item_scale: Optional[np.ndarray] = None,
        backend: Optional[str] = None,
        plan=None,
        sharding: Optional[str] = None,
        ivf_index=None,
        retrieval: Optional[str] = None,
    ):
        self.ctx = ctx
        self.n_users = user_factors.shape[0]
        self.n_items = item_factors.shape[0]
        # score-kernel backend for THIS scorer generation, resolved once at
        # construction (auto → fused only on TPU)
        self.backend = resolve_backend(backend)
        self.factor_dtype = factor_dtype
        if factor_dtype == "int8" and (user_scale is None or item_scale is None):
            raise ValueError("int8 factors require user_scale and item_scale")
        self.k = min(max_k, self.n_items)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        # factor placement: replicated full copies, or item blocks
        # partitioned per the publish-time ShardingPlan (PIO_SERVING_SHARDING)
        self.plan = plan
        self.sharding = resolve_serving_backend(sharding, plan=plan, ctx=ctx)
        self._shard_acct: Optional[_sharding.ShardAccounting] = None
        # retrieval path (PIO_RETRIEVAL): IVF prunes the replicated scan;
        # it composes with the replicated placement only — a sharded plan
        # already partitions the scan across devices, and stacking the two
        # layouts would shard cluster blocks mid-block
        self.ivf_index = ivf_index
        retr = _ivf.resolve_retrieval(retrieval, index=ivf_index)
        if retr == "ivf" and self.sharding == "sharded":
            logger.warning(
                "IVF retrieval composes with replicated placement only; "
                "the sharding plan takes precedence — serving exact sharded"
            )
            retr = "exact"
        self.retrieval = retr
        if factor_dtype == "f32":
            user_factors = np.asarray(user_factors, np.float32)
            item_factors = np.asarray(item_factors, np.float32)
        # pod layout: plans with >1 host group run the two-tier merge over
        # a 2-D (host, data) mesh; placement/readback must then go through
        # the multi-process-safe helpers below
        self._pod = bool(
            self.sharding == "sharded"
            and getattr(plan, "host_groups", 1) > 1
        )
        self._pod_spans = False
        if self.sharding == "sharded":
            self._init_sharded_placement(
                user_factors, item_factors, user_scale, item_scale
            )
            # merged_k drives the cross-host tier-2 byte accounting; the
            # flat merge (including a rejected pod carve) has no tier 2
            self._shard_acct = _sharding.ShardAccounting(
                self.plan, self._local_k,
                merged_k=self.k if self._pod else None,
            )
        elif self.retrieval == "ivf":
            self._init_ivf_placement(
                user_factors, item_factors, user_scale, item_scale
            )
        else:
            self._init_replicated_placement(
                user_factors, item_factors, user_scale, item_scale
            )
        self.resident_factor_bytes = sum(
            int(a.nbytes)
            for a in (self._U, self._V, self._Uscale, self._Vscale)
            if a is not None
        ) + getattr(self, "_ivf_extra_bytes", 0)
        # IVF scan accounting (guarded by self._lock with the other
        # counters): probed blocks and scanned padded rows per dispatch,
        # against the exact path's would-have-scanned rows
        self._ivf_dispatches = 0
        self._ivf_probed_blocks = 0
        self._ivf_scanned_rows = 0
        self._ivf_dispatch_rows = 0
        self._lock = threading.Lock()
        self.queries = 0
        self.padded_rows = 0
        # the fused kernel's merge counters, summed over dispatches: passes
        # that inserted, blocks that merged anything (ops/score_kernel.py)
        self.merge_passes = 0
        self.merge_blocks = 0
        # device-utilization accountant: each bucket is cost-annotated at
        # compile time below, each dispatch records its device wall, and
        # the query server's bridge exports the windowed pio_device_*
        # gauges. One scorer == one model generation, so the accountant's
        # window never mixes generations.
        self.devprof = _devprof.DeviceUtilization(
            device_kind=ctx.mesh.devices.flat[0].device_kind
        )
        # per-bucket annotated HBM bytes, kept host-side so the sharded
        # merge-time attribution doesn't re-enter the accountant per call
        self._cost_bytes: dict[int, float] = {}
        # score_topk is entered by two threads at once (the batcher's
        # launch-ahead): the accountant is charged no second of the device
        # twice
        self._last_return = 0.0
        # AOT: every rung compiled and run once before the first request
        self._rungs = RungPrograms(
            ctx.mesh.devices.flat[0], self.buckets, self._lower,
            warm_args=lambda b: self._call_args(np.zeros(b, np.int32)),
            fetch=self._fetch,
            # the sharded path never touches the program store
            describe=None if self.sharding == "sharded" else self._describe)
        self._fns = self._rungs.fns
        for b, compiled in self._fns.items():
            self._annotate_cost(b, compiled)

    compile_count = property(lambda self: self._rungs.compile_count)

    def _put_repl(self, x: np.ndarray):
        """Replicate a host array on the serving mesh, multi-process safe.

        Pod meshes that span processes can't ``device_put`` (remote
        shards are non-addressable); every process supplies the same host
        copy through the shard-callback path.  SPMD contract: all
        processes dispatch the same batches in the same order.
        """
        if self._pod_spans:
            return self._shard_ctx.place(x)
        import jax.numpy as jnp

        return jax.device_put(jnp.asarray(x), self._repl)

    def _call_args(self, padded: np.ndarray) -> tuple:
        """A rung's arguments: the factors as they are resident NOW (a
        delta may have patched them) and the padded rows as the host array
        itself.  The call's own argument handling places it under the
        program's input sharding, so it gets no ``device_put`` (a host ↔
        device round trip) of its own first.  Across processes one host's
        array cannot feed remote shards: there it is placed."""
        with _tracing.stage("h2d"):
            rows = self._put_repl(padded) if self._pod_spans else padded
            return (*self._static_args, rows)

    def _fetch(self, outs: tuple) -> tuple:
        """What a dispatch reads back of a program's REPLICATED outputs:
        all of them ((vals, idx), and the merge counters where the program
        was compiled with them).  Multi-process safe: any one addressable
        shard of a replicated array is the whole value."""
        if self._pod_spans:
            return tuple(x.addressable_data(0) for x in outs)
        return outs

    def _init_replicated_placement(
        self, user_factors, item_factors, user_scale, item_scale
    ) -> None:
        """Full factor copies on every device (the pre-sharding layout)."""
        ctx = self.ctx
        if self.backend == "fused":
            # the fused kernel streams the item matrix in fixed-size blocks
            self._n_items_pad = _score_kernel.pad_block_items(self.n_items)
        else:
            self._n_items_pad = pad_to_multiple(self.n_items, 8)
        self._repl = ctx.replicated()
        pad_i = self._n_items_pad - self.n_items
        self._U = ctx.replicate(np.asarray(user_factors))
        self._V = ctx.replicate(
            np.pad(np.asarray(item_factors), ((0, pad_i), (0, 0)))
        )
        if self.factor_dtype == "int8":
            self._Uscale = ctx.replicate(np.asarray(user_scale, np.float32))
            self._Vscale = ctx.replicate(
                np.pad(
                    np.asarray(item_scale, np.float32),
                    ((0, pad_i), (0, 0)),
                    constant_values=1.0,
                )
            )
        else:
            self._Uscale = self._Vscale = None
        pad_mask = np.arange(self._n_items_pad) >= self.n_items
        if self.backend == "fused":
            # the lane row the kernel reads, built once: a bool mask would
            # be converted by every dispatch
            pad_mask = _score_kernel.item_mask_row(pad_mask)
        self._item_pad_mask = ctx.replicate(pad_mask)
        # everything the compiled programs take except the per-call indices
        if self.factor_dtype == "int8":
            # construction-time: no other thread holds the scorer yet
            self._static_args = (  # pio: ignore[race-unguarded-rebind]
                self._U, self._V, self._Uscale, self._Vscale,
                self._item_pad_mask,
            )
        else:
            self._static_args = (  # pio: ignore[race-unguarded-rebind]
                self._U, self._V, self._item_pad_mask)

    def _init_ivf_placement(
        self, user_factors, item_factors, user_scale, item_scale
    ) -> None:
        """Replicated factors in IVF cluster-block layout + centroids.

        The item matrix is permuted into the index's cluster blocks via
        the SAME ``build_layout`` the sharded path uses — every cluster
        a contiguous kernel-aligned block of ``cap_pad`` rows, real slots
        ascending by global id (the tie-order invariant), global ids and
        a pad mask riding alongside flat.  The compiled program slices
        probe blocks out of this one replicated array, so compared to the
        exact replicated placement the only extra residency is the
        centroid matrix, the id/pad maps, and the per-cluster padding.
        ``_n_items_pad`` becomes the PER-PROBE block size; the dispatch
        cost annotation multiplies it by the rung's probe budget.
        """
        ctx = self.ctx
        index = self.ivf_index
        index.validate(self.n_items)
        plan = index.plan
        if self.backend == "fused":
            pad_to = _score_kernel.pad_block_items
        else:
            def pad_to(n):
                return pad_to_multiple(n, 8)
        layout = _sharding.build_layout(plan, pad_to)
        # written once here (an __init__ helper, before the scorer is
        # shared) and never rebound after
        self._ivf_layout = layout  # pio: ignore[race-unguarded-rebind]
        self._n_items_pad = layout.cap_pad
        # what the exact path would have scanned per row — the
        # scanned-fraction denominator
        self._exact_items_pad = int(pad_to(self.n_items))
        self._local_k = min(self.k, layout.cap_pad)  # pio: ignore[race-unguarded-rebind]
        # deploy-time probe budget: PIO_IVF_NPROBE overrides the
        # publish-time default, clamped to [1, nlist]
        env_nprobe = os.environ.get("PIO_IVF_NPROBE", "")
        nprobe = (
            int(env_nprobe) if env_nprobe.strip() else int(index.nprobe)
        )
        self._nprobe = max(1, min(nprobe, index.nlist))
        # smallest probe count whose clusters are GUARANTEED to hold >= k
        # real items (sum of the P smallest cluster sizes >= k), so padded
        # slots can never win a final leaderboard slot
        sizes = np.sort(plan.shard_sizes())
        self._min_probes = int(
            np.searchsorted(np.cumsum(sizes), self.k) + 1
        )
        self._probes = {  # pio: ignore[race-unguarded-rebind]
            b: min(
                index.nlist, max(self._min_probes, self._nprobe * b)
            )
            for b in self.buckets
        }
        self._repl = ctx.replicated()
        self._U = ctx.replicate(np.asarray(user_factors))
        self._V = ctx.replicate(
            layout.take_rows(np.asarray(item_factors))
        )
        C = np.asarray(index.centroids, np.float32)
        self._C = ctx.replicate(C)
        gid = layout.gid
        pad_mask = layout.pad_mask
        self._ivf_gid = ctx.replicate(gid)
        self._item_pad_mask = ctx.replicate(pad_mask)
        if self.factor_dtype == "int8":
            self._Uscale = ctx.replicate(np.asarray(user_scale, np.float32))
            self._Vscale = ctx.replicate(
                layout.take_rows(
                    np.asarray(item_scale, np.float32), fill=1.0
                )
            )
            self._static_args = (
                self._U, self._V, self._Uscale, self._Vscale,
                self._C, self._ivf_gid, self._item_pad_mask,
            )
        else:
            self._Uscale = self._Vscale = None
            self._static_args = (
                self._U, self._V, self._C, self._ivf_gid,
                self._item_pad_mask,
            )
        self._ivf_extra_bytes = (
            int(C.nbytes) + int(gid.nbytes) + int(pad_mask.nbytes)
        )

    def _init_sharded_placement(
        self, user_factors, item_factors, user_scale, item_scale
    ) -> None:
        """Item factors partitioned across the plan's shard submesh.

        Every shard's item block is padded to one common kernel-aligned
        capacity so the concatenated (S·cap_pad, rank) matrix shards
        evenly over the mesh 'data' axis; per-slot global ids and a pad
        mask ride alongside.  ``_n_items_pad`` becomes the PER-DEVICE
        block size — each device scores only its shard, which is the
        whole point — so the devprof cost annotation stays per-device
        truthful.  User factors and the (B,) query indices are replicated
        (users were never the HBM problem; items are).
        """
        import jax.numpy as jnp

        plan = self.plan
        plan.validate(self.n_items)
        if self.backend == "fused":
            pad_to = _score_kernel.pad_block_items
        else:
            def pad_to(n):
                return pad_to_multiple(n, 8)
        layout = _sharding.build_layout(plan, pad_to)
        self._shard_layout = layout
        self._n_items_pad = layout.cap_pad
        # per-shard leaderboard width: a shard with fewer than k real
        # items simply contributes its whole block; S·local_k ≥ k always
        # holds because S·cap_pad ≥ n_items ≥ self.k
        self._local_k = min(self.k, layout.cap_pad)  # pio: ignore[race-unguarded-rebind]
        if self._pod:
            # 2-D (host, data) mesh: shard s lands on host row s // G —
            # the plan's contiguous group blocks, by construction of the
            # process-major prefix carve.  A carve whose host rows do not
            # align with process boundaries is rejected by pod_submesh
            # (the two-tier merge's locality and ownership claims would
            # both be false); serving degrades to the flat merge.
            try:
                sc = self.ctx.pod_submesh(plan.n_shards, plan.host_groups)
                shard_axes = (HOST_AXIS, DATA_AXIS)
            except ValueError as e:
                logger.warning(
                    "pod layout rejected (%s); serving the flat "
                    "single-tier merge instead", e,
                )
                # construction-time rebind, before the scorer is shared
                self._pod = False  # pio: ignore[race-unguarded-rebind]
        if not self._pod:
            sc = self.ctx.submesh(plan.n_shards)
            shard_axes = DATA_AXIS
        self._shard_ctx = sc
        # set once during construction, read-only under traffic
        self._pod_spans = self._pod and sc.spans_processes  # pio: ignore[race-unguarded-rebind]
        self._repl = sc.replicated()
        self._U = sc.place(user_factors)
        self._V = sc.place(
            layout.take_rows(np.asarray(item_factors)), shard_axes, None
        )
        if self.factor_dtype == "int8":
            self._Uscale = sc.place(np.asarray(user_scale, np.float32))
            self._Vscale = sc.place(
                layout.take_rows(
                    np.asarray(item_scale, np.float32), fill=1.0
                ),
                shard_axes, None,
            )
        else:
            self._Uscale = self._Vscale = None
        self._shard_gid = sc.place(layout.gid, shard_axes)
        self._item_pad_mask = sc.place(layout.pad_mask, shard_axes)
        if self.factor_dtype == "int8":
            self._static_args = (
                self._U, self._V, self._Uscale, self._Vscale,
                self._shard_gid, self._item_pad_mask,
            )
        else:
            self._static_args = (
                self._U, self._V, self._shard_gid, self._item_pad_mask,
            )
        per_shard = int(self._V.nbytes) // plan.n_shards
        if self._Vscale is not None:
            per_shard += int(self._Vscale.nbytes) // plan.n_shards
        self.resident_shard_bytes = [per_shard] * plan.n_shards

    # -- streaming micro-generations (core/delta.py) -------------------------

    def _layout_slots(self) -> Optional[dict]:
        """global item id → laid-out row slot, for the active item layout."""
        layout = None
        if self.sharding == "sharded":
            layout = self._shard_layout
        elif self.retrieval == "ivf":
            layout = self._ivf_layout
        if layout is None:
            return None
        slots = getattr(self, "_delta_item_slots", None)
        if slots is None:
            gid = np.asarray(layout.gid)
            mask = np.asarray(layout.pad_mask)
            slots = {
                int(g): int(s) for s, g in enumerate(gid) if not mask[s]
            }
            # built once on first delta, read-only after
            self._delta_item_slots = slots  # pio: ignore[race-unguarded-rebind]
        return slots

    def apply_delta_rows(
        self, user_idx, user_rows, item_idx=None, item_rows=None
    ) -> dict:
        """Patch factor rows in place on the device-resident buffers.

        The micro-generation apply path: replacement rows land through a
        functional scatter on arrays whose shapes and dtypes never
        change, so every AOT-compiled bucket keeps serving the same
        executables — ``compile_count`` stays flat across any number of
        deltas (the invariant the streaming bench asserts).  User rows go
        to the replicated user matrix on every placement; item rows are
        routed to their owning shard/cluster slot through the active
        ShardingPlan layout.  Quantized factors are re-quantized row-wise
        (same per-row-scale scheme as publish).
        """
        import jax.numpy as jnp

        if self._pod_spans:
            # `.at[].set` needs the whole array addressable; a pod mesh's
            # remote shards aren't.  Documented degrade (operations.md,
            # "Pod-scale serving"): streaming deltas don't compose with
            # multi-process serving — the next full reload picks them up.
            logger.warning(
                "apply_delta_rows skipped: factors span processes on a "
                "pod mesh; deltas apply at the next full publish/reload"
            )
            return {
                "users": 0, "items": 0,
                "compile_count": self.compile_count, "skipped": "pod",
            }
        users = np.asarray(user_idx, np.int32).reshape(-1)
        rows = np.asarray(user_rows, np.float32).reshape(len(users), -1)
        keep = users < self.n_users
        users, rows = users[keep], rows[keep]
        if len(users):
            u_dev = jnp.asarray(users)
            if self.factor_dtype == "int8":
                q, scale = _quantize.quantize_factors(rows, "int8")
                new_U = self._U.at[u_dev].set(jnp.asarray(q))
                new_Us = self._Uscale.at[u_dev].set(jnp.asarray(scale))
            else:
                new_U = self._U.at[u_dev].set(
                    jnp.asarray(rows).astype(self._U.dtype)
                )
                new_Us = self._Uscale
            with self._lock:
                self._U = new_U
                self._Uscale = new_Us
        n_items = self._apply_item_rows(item_idx, item_rows)
        with self._lock:
            self._rebuild_static_args()
        return {
            "users": int(len(users)), "items": int(n_items),
            "compile_count": self.compile_count,
        }

    def _apply_item_rows(self, item_idx, item_rows) -> int:
        if item_idx is None:
            return 0
        import jax.numpy as jnp

        idx = np.asarray(item_idx, np.int64).reshape(-1)
        if len(idx) == 0:
            return 0
        rows = np.asarray(item_rows, np.float32).reshape(len(idx), -1)
        keep = idx < self.n_items
        idx, rows = idx[keep], rows[keep]
        slots = self._layout_slots()
        if slots is not None:
            present = np.array([int(g) in slots for g in idx], bool)
            rows = rows[present]
            idx = np.array(
                [slots[int(g)] for g in idx[present]], np.int64
            )
        if len(idx) == 0:
            return 0
        i_dev = jnp.asarray(idx)
        if self.factor_dtype == "int8":
            q, scale = _quantize.quantize_factors(rows, "int8")
            new_V = self._V.at[i_dev].set(jnp.asarray(q))
            new_Vs = self._Vscale.at[i_dev].set(jnp.asarray(scale))
        else:
            new_V = self._V.at[i_dev].set(
                jnp.asarray(rows).astype(self._V.dtype)
            )
            new_Vs = self._Vscale
        with self._lock:
            self._V = new_V
            self._Vscale = new_Vs
        return len(idx)

    def _rebuild_static_args(self) -> None:
        """Re-point the AOT programs' captured operands after a patch.

        Same tuple orders as the three ``_init_*_placement`` builders —
        shapes and dtypes are identical by construction, so the compiled
        executables accept the new buffers without relowering.
        """
        int8 = self.factor_dtype == "int8"
        if self.sharding == "sharded":
            if int8:
                self._static_args = (
                    self._U, self._V, self._Uscale, self._Vscale,
                    self._shard_gid, self._item_pad_mask,
                )
            else:
                self._static_args = (
                    self._U, self._V, self._shard_gid, self._item_pad_mask,
                )
        elif self.retrieval == "ivf":
            if int8:
                self._static_args = (
                    self._U, self._V, self._Uscale, self._Vscale,
                    self._C, self._ivf_gid, self._item_pad_mask,
                )
            else:
                self._static_args = (
                    self._U, self._V, self._C, self._ivf_gid,
                    self._item_pad_mask,
                )
        else:
            if int8:
                self._static_args = (
                    self._U, self._V, self._Uscale, self._Vscale,
                    self._item_pad_mask,
                )
            else:
                self._static_args = (self._U, self._V, self._item_pad_mask)

    def _lower_args(self, b: int) -> tuple:
        return (*self._static_args, self._put_repl(np.zeros(b, np.int32)))

    def _describe(self, b: int) -> tuple:
        """What a replicated rung's program closes over, for the program
        store's key, and the arguments it is lowered on (replicated over a
        mesh of several devices the store leaves the rung alone too:
        ``program_store.lowered_on``)."""
        statics = {
            "scorer": "BucketedScorer", "variant": self.retrieval, "rung": b,
            "k": self.k, "backend": self.backend,
            "factor_dtype": self.factor_dtype,
        }
        if self.retrieval == "ivf":
            statics.update(local_k=self._local_k, probes=self._probes[b],
                           cap_pad=self._ivf_layout.cap_pad)
        return statics, self._lower_args(b)

    def _lower(self, b: int):
        """The bucket-b program traced and lowered, ahead of time."""
        if self.sharding == "sharded":
            return self._lower_sharded(b)
        if self.retrieval == "ivf":
            return self._lower_ivf(b)
        k = self.k
        be = self.backend
        # the fused kernel also returns its merge counters: a third output
        # that rides the leaderboard's readback (stats(): merge_passes)
        ws = be == "fused"

        if self.factor_dtype == "int8":

            def fn(U, V, u_scale, v_scale, item_pad_mask, u_idx):
                return gather_score_topk(
                    U, V, u_idx, k, item_mask=item_pad_mask,
                    u_scale=u_scale, v_scale=v_scale, backend=be,
                    with_stats=ws,
                )

        else:

            def fn(U, V, item_pad_mask, u_idx):
                return gather_score_topk(
                    U, V, u_idx, k, item_mask=item_pad_mask, backend=be,
                    with_stats=ws,
                )

        return jax.jit(fn).lower(*self._lower_args(b))

    def _lower_ivf(self, b: int):
        """The bucket-b IVF probe → scan → merge program, lowered.

        One program per rung, same ladder/warmup contract as the other
        placements.  The batch's dequantized query rows score against the
        centroids; the rung's probe budget ``P_b`` of clusters is picked
        by ``lax.top_k`` over the row-wise MAX of centroid scores (at
        b=1 this is exactly per-query nprobe selection — the publish
        gate's measurement; at larger rungs the shared budget scales as
        ``nprobe·b`` so per-query amortized scan stays ≈ nprobe/nlist).
        A ``lax.scan`` over the probe ids dynamic-slices each cluster's
        contiguous block out of the layout arrays and runs the EXISTING
        ``gather_score_topk`` over it — per-probe leaderboards carry
        global ids, and ``merge_topk``'s (value desc, id asc) order makes
        the result bit-identical to the exact path when every cluster is
        probed.  Only the probe blocks are ever touched: the scan cost
        per dispatch is ``P_b·cap_pad`` rows instead of the full catalog.
        """
        import jax.numpy as jnp

        k = self.k
        lk = self._local_k
        be = self.backend
        cap = self._ivf_layout.cap_pad
        P_b = self._probes[b]

        if self.factor_dtype == "int8":

            def fn(U, V, u_scale, v_scale, C, gid, pad_mask, u_idx):
                q = U[u_idx].astype(jnp.float32) * u_scale[u_idx]
                agg = jnp.max(q @ C.T, axis=0)  # (nlist,)
                _, probes = jax.lax.top_k(agg, P_b)

                def step(carry, p):
                    s = p * cap
                    Vb = jax.lax.dynamic_slice_in_dim(V, s, cap, 0)
                    vsb = jax.lax.dynamic_slice_in_dim(v_scale, s, cap, 0)
                    gb = jax.lax.dynamic_slice_in_dim(gid, s, cap, 0)
                    mb = jax.lax.dynamic_slice_in_dim(pad_mask, s, cap, 0)
                    vals, idx = gather_score_topk(
                        U, Vb, u_idx, lk, item_mask=mb,
                        u_scale=u_scale, v_scale=vsb, backend=be,
                    )
                    return carry, (vals, jnp.take(gb, idx))

                _, (pv, pg) = jax.lax.scan(step, None, probes)
                cand_v = jnp.swapaxes(pv, 0, 1).reshape(b, P_b * lk)
                cand_g = jnp.swapaxes(pg, 0, 1).reshape(b, P_b * lk)
                return merge_topk(cand_v, cand_g, k)

        else:

            def fn(U, V, C, gid, pad_mask, u_idx):
                q = U[u_idx].astype(jnp.float32)
                agg = jnp.max(q @ C.T, axis=0)  # (nlist,)
                _, probes = jax.lax.top_k(agg, P_b)

                def step(carry, p):
                    s = p * cap
                    Vb = jax.lax.dynamic_slice_in_dim(V, s, cap, 0)
                    gb = jax.lax.dynamic_slice_in_dim(gid, s, cap, 0)
                    mb = jax.lax.dynamic_slice_in_dim(pad_mask, s, cap, 0)
                    vals, idx = gather_score_topk(
                        U, Vb, u_idx, lk, item_mask=mb, backend=be
                    )
                    return carry, (vals, jnp.take(gb, idx))

                _, (pv, pg) = jax.lax.scan(step, None, probes)
                cand_v = jnp.swapaxes(pv, 0, 1).reshape(b, P_b * lk)
                cand_g = jnp.swapaxes(pg, 0, 1).reshape(b, P_b * lk)
                return merge_topk(cand_v, cand_g, k)

        return jax.jit(fn).lower(*self._lower_args(b))

    def _lower_sharded(self, b: int):
        """The bucket-b fan-out → local top-k → merge program, lowered.

        One program per rung, same ladder and warmup contract as the
        replicated path.  Inside ``shard_map`` each device runs the
        existing ``gather_score_topk`` over ONLY its local item block and
        maps local winners to global ids; the shard-stacked
        (S, B, local_k) leaderboards leave the shard region sharded, and
        the transpose+merge outside forces the partitioner to emit one
        small leaderboard all-gather (S·B·local_k·8 bytes) — never the
        (B, n_items) score matrix.  ``merge_topk``'s (value desc, id asc)
        order makes the result bit-identical to the replicated reference.

        Pod layouts (``plan.host_groups > 1``) run the merge INSIDE the
        shard region instead: :func:`two_tier_merge_topk` gathers the G
        on-host leaderboards over the ``data`` axis, merges, then gathers
        only the H per-host ``(B, k)`` leaderboards over the ``host``
        axis — the flat ``(S, B, local_k)`` collective above never forms,
        and the cross-host wire carries ``H·B·k·8`` bytes per dispatch
        (docs/perf_roofline.md).  Same two-key sort both tiers, so the
        answers stay bit-identical.
        """
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        k = self.k
        lk = self._local_k
        be = self.backend
        S = self.plan.n_shards
        mesh = self._shard_ctx.mesh
        pod = self._pod
        shard_dim = (HOST_AXIS, DATA_AXIS) if pod else DATA_AXIS

        if self.factor_dtype == "int8":

            def local(U, Vl, u_scale, vs_l, gidl, maskl, u_idx):
                vals, idx = gather_score_topk(
                    U, Vl, u_idx, lk, item_mask=maskl,
                    u_scale=u_scale, v_scale=vs_l, backend=be,
                )
                gids = jnp.take(gidl, idx)
                if pod:
                    return two_tier_merge_topk(
                        vals, gids, k,
                        group_axis=DATA_AXIS, host_axis=HOST_AXIS,
                    )
                return vals[None], gids[None]

            in_specs = (
                P(), P(shard_dim, None), P(), P(shard_dim, None),
                P(shard_dim), P(shard_dim), P(),
            )
        else:

            def local(U, Vl, gidl, maskl, u_idx):
                vals, idx = gather_score_topk(
                    U, Vl, u_idx, lk, item_mask=maskl, backend=be
                )
                gids = jnp.take(gidl, idx)
                if pod:
                    return two_tier_merge_topk(
                        vals, gids, k,
                        group_axis=DATA_AXIS, host_axis=HOST_AXIS,
                    )
                return vals[None], gids[None]

            in_specs = (
                P(), P(shard_dim, None), P(shard_dim), P(shard_dim), P(),
            )
        # shard_map's vma checker stays on only where it can follow the
        # program: a pallas_call is opaque to it (out_shapes carry no vma,
        # and the interpret-mode kernel trips it inside its own scan), and
        # it types all_gather's result as still varying, so it cannot see
        # that the pod merge's gathers over BOTH axes leave every device
        # with the same (B, k) — the replicated out_specs would be refused
        check_vma = be != "fused" and not pod
        if pod:
            # the two-tier merge already replicated the final (B, k)
            out_specs = (P(), P())

            def fn(*args):
                return shard_map(
                    local, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=check_vma,
                )(*args)

        else:
            out_specs = (
                P(DATA_AXIS, None, None), P(DATA_AXIS, None, None),
            )

            def fn(*args):
                lv, lg = shard_map(
                    local, mesh=mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=check_vma,
                )(*args)
                # (S, B, lk) → (B, S·lk) candidate rows; the global
                # reshape is what pulls the leaderboards across the mesh
                cand_v = jnp.swapaxes(lv, 0, 1).reshape(b, S * lk)
                cand_g = jnp.swapaxes(lg, 0, 1).reshape(b, S * lk)
                return merge_topk(cand_v, cand_g, k)

        return jax.jit(fn).lower(*self._lower_args(b))

    def _annotate_cost(self, b: int, compiled) -> None:
        """Record bucket-b per-dispatch FLOPs/bytes on the accountant.

        Prefers the compiler's own numbers for the ACTUAL optimized HLO;
        falls back to the analytic score model when cost_analysis
        declines (some backends return nothing useful).  Fused buckets
        always use the analytic fused model: the Pallas call is opaque to
        XLA's cost analysis, which would report the custom-call as ~free
        and make MFU read as zero forever.  IVF rungs always use the
        analytic model: the probe scan's Pallas calls are opaque to XLA
        cost analysis, and the analytic scanned-rows number (P_b·cap_pad,
        not the full catalog) IS the story.  Called from ``__init__`` only.
        """
        rank = self._U.shape[1]
        if self.retrieval == "ivf":
            scanned = self._probes[b] * self._ivf_layout.cap_pad
            if self.backend == "fused":
                a_flops, a_bytes = _devprof.fused_score_cost(
                    b, scanned, rank, self._local_k, self.factor_dtype
                )
                source = "analytic-fused"
            else:
                a_flops, a_bytes = _devprof.score_cost(
                    b, scanned, rank, dtype=self.factor_dtype
                )
                source = "analytic"
            self.devprof.set_cost(b, a_flops, a_bytes, source=source)
            self._cost_bytes[b] = a_bytes
            return
        if self.backend == "fused":
            a_flops, a_bytes = _devprof.fused_score_cost(
                b, self._n_items_pad, rank, self.k, self.factor_dtype
            )
            self.devprof.set_cost(
                b, a_flops, a_bytes, source="analytic-fused"
            )
            self._cost_bytes[b] = a_bytes
            return
        flops = nbytes = None
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0] if ca else {}
            ca = ca or {}
            flops = ca.get("flops")
            nbytes = ca.get("bytes accessed")
        except Exception:  # pragma: no cover - backend-dependent
            pass
        if flops and nbytes:
            self.devprof.set_cost(b, flops, nbytes, source="xla")
            self._cost_bytes[b] = float(nbytes)
        else:
            a_flops, a_bytes = _devprof.score_cost(
                b, self._n_items_pad, rank, dtype=self.factor_dtype
            )
            self.devprof.set_cost(b, a_flops, a_bytes, source="analytic")
            self._cost_bytes[b] = a_bytes

    def score_topk(
        self, user_indices: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """Top-``k`` (indices, values) for every user in ``user_indices``.

        Batches larger than the top rung are served in top-rung chunks, so
        any size works without growing the compile cache.  ``k`` beyond the
        compiled width raises ValueError — callers route that to their
        exact path instead of silently truncating.
        """
        if k > self.k:
            raise ValueError(f"k={k} exceeds compiled top-k width {self.k}")
        return self._device_topk(np.asarray(user_indices, np.int32), k)

    def _device_topk(
        self, users: np.ndarray, k: int
    ) -> tuple[np.ndarray, np.ndarray]:
        """``score_topk`` past its checks: ``users`` in top-rung chunks,
        each padded to its rung (benchmark/tests/ alter answers here)."""
        top = self.buckets[-1]
        idx_parts, val_parts = [], []
        for s in range(0, len(users), top):
            chunk = users[s : s + top]
            b = bucket_for(len(chunk), self.buckets)
            padded = np.zeros(b, np.int32)
            padded[: len(chunk)] = chunk
            # more: rows past the top rung are still to launch
            (val_h, idx_h, *merge), t0, t1 = self._rungs.run(
                b, functools.partial(self._call_args, padded),
                more=s + top < len(users))
            # launched behind a program in flight, this one sat queued
            # until that one returned: the accountant is charged the run
            # and its readback less what an earlier dispatch's wait covered
            with self._lock:
                wall = t1 - max(t0, self._last_return)
                self._last_return = t1
            self.devprof.record(b, wall)
            with _tracing.stage("d2h"):
                # the readback's residue on the host: the rows asked for
                # (padded tail rows are real top-k rows for user 0)
                idx_rows = idx_h[: len(chunk), :k]
                val_rows = val_h[: len(chunk), :k]
            with self._lock:
                self.queries += len(chunk)
                self.padded_rows += b - len(chunk)
                if merge:
                    passes, blocks = map(int, merge[0])
                    self.merge_passes += passes
                    self.merge_blocks += blocks
                    disp = _tracing.active_dispatch()
                    if disp is not None:
                        disp.merge_passes += passes
                if self._shard_acct is not None:
                    self._shard_acct.note(
                        idx_rows, b, wall,
                        self._cost_bytes.get(b, 0.0),
                    )
                if self.retrieval == "ivf":
                    self._ivf_dispatches += 1
                    self._ivf_probed_blocks += self._probes[b]
                    self._ivf_scanned_rows += (
                        self._probes[b] * self._ivf_layout.cap_pad
                    )
                    self._ivf_dispatch_rows += b
            idx_parts.append(idx_rows)
            val_parts.append(val_rows)
        return np.concatenate(idx_parts), np.concatenate(val_parts)

    def stats(self) -> dict:
        """Counters for ``GET /`` stats and bench artifacts.

        ``compile_count`` only moves at construction (warmup); a nonzero
        delta across serving traffic IS a recompile and fails the bench's
        zero-recompile check.
        """
        with self._lock:
            rungs = self._rungs.stats()
            top = self.buckets[-1]
            costs = self.devprof.costs()
            top_cost = costs.get(top) or {}
            flops = top_cost.get("flops")
            nbytes = top_cost.get("bytes")
            kernel = {
                "backend": self.backend,
                "factor_dtype": self.factor_dtype,
                "resident_factor_bytes": self.resident_factor_bytes,
                # the sweep's tile per compiled rung, from the function
                # the kernel itself calls (per probe block under IVF, per
                # shard when sharded)
                "block_items": _score_kernel.tile_report(
                    self.buckets, self._V.shape[1], self._V.dtype,
                    self._n_items_pad,
                ) if self.backend == "fused" else None,
                "warmup_executions": rungs.pop("warmup_executions"),
                # top-rung arithmetic intensity: the roofline position the
                # docs derive (docs/perf_roofline.md)
                "intensity_flops_per_byte": (
                    round(flops / nbytes, 3) if flops and nbytes else None
                ),
            }
            dev = self.devprof.snapshot()
            sharding = None
            if self._shard_acct is not None:
                sharding = self._shard_acct.snapshot(
                    (dev or {}).get("busy_fraction"),
                    self.resident_shard_bytes,
                )
            retrieval = None
            if self.retrieval == "ivf":
                index = self.ivf_index
                # scanned fraction: item rows the probe scans streamed /
                # rows the exact path would have streamed for the same
                # dispatches.  Per DISPATCH, not per row — one matmul
                # over the probe blocks serves every row in the rung,
                # exactly as one exact full scan would, so this is the
                # honest HBM-bytes ratio between the two paths.
                denom = self._ivf_dispatches * self._exact_items_pad
                retrieval = {
                    "backend": "ivf",
                    "nlist": index.nlist,
                    "nprobe": self._nprobe,
                    "min_probes": self._min_probes,
                    "cap_pad": self._ivf_layout.cap_pad,
                    "probes_per_rung": {
                        str(b): p for b, p in self._probes.items()
                    },
                    "dispatches": self._ivf_dispatches,
                    "dispatch_rows": self._ivf_dispatch_rows,
                    "probed_blocks": self._ivf_probed_blocks,
                    "scanned_rows": self._ivf_scanned_rows,
                    "scanned_fraction": round(
                        self._ivf_scanned_rows / denom, 6
                    )
                    if denom
                    else None,
                    "resident_extra_bytes": self._ivf_extra_bytes,
                    "recall_at_publish": index.recall_at_publish,
                    "fingerprint": index.fingerprint,
                }
            pod = None
            if self._pod:
                pod = {
                    "host_groups": self.plan.host_groups,
                    "shards_per_group": self.plan.shards_per_group,
                    "process_index": jax.process_index(),
                    "process_count": jax.process_count(),
                    "spans_processes": self._pod_spans,
                    "fingerprint": self.plan.fingerprint,
                    "cross_host_merge_bytes": (sharding or {}).get(
                        "pod_merge_bytes", 0.0
                    ),
                    "cross_host_merge_seconds": (sharding or {}).get(
                        "pod_merge_seconds", 0.0
                    ),
                    "dispatches": (sharding or {}).get("pod_dispatches", 0),
                }
            return {
                "buckets": list(self.buckets),
                "top_k": self.k,
                "serving_backend": self.sharding,
                "sharding": sharding,
                "pod": pod,
                "retrieval_backend": self.retrieval,
                "retrieval": retrieval,
                "kernel": kernel,
                **rungs,
                "queries": self.queries,
                "padded_rows": self.padded_rows,
                "merge_passes": self.merge_passes,
                "merge_blocks": self.merge_blocks,
                "row_occupancy": round(
                    self.queries / (self.queries + self.padded_rows), 4
                )
                if self.queries
                else None,
                "devprof": dev,
            }
