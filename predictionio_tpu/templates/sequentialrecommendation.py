"""Sequential-recommendation template: next-item prediction over histories.

A beyond-parity model family (the reference has no sequence models): user
event histories train a causal-transformer recommender
(:mod:`predictionio_tpu.models.sequential`); at query time the user's RECENT
history is read live from the event store (same pattern as the e-commerce
template's serving-time lookups) so recommendations track events newer than
the model.

Six algorithms share the template and its one history seam
(:class:`EventStoreHistory` unless the model or the algorithm carries
another provider): ``sasrec``, the small trained transformer, served one
query at a time from the host; and five packed sequence families at
published widths that serve through ``deploy --batching`` — the batcher's
rows are packed into one dispatch of a resident, ahead-of-time compiled
device program (:mod:`predictionio_tpu.serving.seqpath`, ONE scorer class
for all): ``latentmoe`` (:class:`LatentMoEAlgorithm`), a latent-attention
sparse-expert stack (:mod:`predictionio_tpu.models.latent_moe`),
``gdnhybrid`` (:class:`GDNHybridAlgorithm`), gated-delta-rule
linear-attention layers interleaved with full-attention layers
(:mod:`predictionio_tpu.models.gdn_hybrid`), ``windowmoe``
(:class:`WindowMoEAlgorithm`), window and global grouped-query attention
over sparse experts of which a model may hold a slice
(:mod:`predictionio_tpu.models.window_moe`), ``ssmparallel``
(:class:`SSMParallelAlgorithm`), a state-space mixer and grouped-query
attention side by side in every layer under muP multipliers
(:mod:`predictionio_tpu.models.ssm_parallel`), and ``ssmmoe``
(:class:`SSMMoEAlgorithm`), state-space layers with an attention layer
among them, every layer followed by softmax-routed small experts (of which
a model may hold a slice) and a shared expert, head tied to the embedding
(:mod:`predictionio_tpu.models.ssm_moe`).  What a packed family needs of
an algorithm is :class:`PackedSequenceAlgorithm`'s; a family adds its
model module's name.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from typing import Optional

import numpy as np

from predictionio_tpu.core import (
    Algorithm,
    DataSource,
    Engine,
    EngineFactory,
    FirstServing,
    IdentityPreparator,
    Params,
)
from predictionio_tpu.core.controller import SanityCheck
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.store import LEventStore
from predictionio_tpu.models.sequential import (
    SASRecConfig,
    SASRecModel,
    train_sasrec,
)

logger = logging.getLogger(__name__)


@dataclasses.dataclass
class Query:
    user: str
    num: int = 10


@dataclasses.dataclass
class ItemScore:
    item: str
    score: float


@dataclasses.dataclass
class PredictedResult:
    itemScores: list[ItemScore]


@dataclasses.dataclass
class TrainingData(SanityCheck):
    interactions: Interactions
    # where serving reads histories from, if not the event store: any object
    # with EventStoreHistory's two methods (a DataSource that holds the
    # histories in memory hands them over here)
    histories: Optional[object] = None

    def sanity_check(self):
        if len(self.interactions) == 0:
            raise ValueError("No interaction events found; check appName.")


@dataclasses.dataclass
class SeqDataSourceParams(Params):
    appName: str = "default"
    eventNames: tuple = ("view", "buy", "rate")


class SequentialDataSource(DataSource):
    params_cls = SeqDataSourceParams

    def read_training(self, ctx) -> TrainingData:
        from predictionio_tpu.parallel.ingest import template_interactions

        # single-host: plain columnar read; multi-host launch: 1/N
        # entity-keyed sharded read. SASRec consumes per-user rows only,
        # so the sharded read skips the target-keyed pass (the global item
        # table derives exactly from the user pass).
        return TrainingData(
            interactions=template_interactions(
                self.params.appName,
                entity_type="user",
                event_names=list(self.params.eventNames),
                target_entity_type="item",
                item_pass=False,
            )
        )


class EventStoreHistory:
    """The default history provider: a live serving-time read of the user's
    most recent events, oldest→newest.  Anything with these two methods can
    stand in for it (``TrainingData.histories``)."""

    def __init__(self, app_name: str, event_names):
        self.app_name, self.event_names = app_name, list(event_names)

    def recent_items(self, user: str, limit: int) -> list[str]:
        try:
            events = LEventStore.find_by_entity(
                self.app_name,
                entity_type="user",
                entity_id=user,
                event_names=self.event_names,
                target_entity_type="item",
                limit=limit,
                latest=True,
            )
        except Exception:
            logger.exception("history lookup failed for %s", user)
            return []
        return [
            e.target_entity_id for e in reversed(events) if e.target_entity_id
        ]

    def recent_indices(self, user: str, limit: int, item_map) -> np.ndarray:
        """The same history as item indices of ``item_map`` (items the
        model does not know are dropped)."""
        idx = item_map.to_index_array(self.recent_items(user, limit))
        return idx[idx >= 0].astype(np.int32)


class _ServesHistories:
    """The one seam both algorithms read histories through: the model's own
    provider if it carries one, else the event store."""

    def _histories(self, model=None):
        return getattr(model, "histories", None) or EventStoreHistory(
            self.params.appName, self.params.eventNames)


@dataclasses.dataclass
class SASRecParams(Params):
    appName: str = "default"
    eventNames: tuple = ("view", "buy", "rate")
    dModel: int = 32
    numLayers: int = 2
    numHeads: int = 2
    maxLen: int = 32
    epochs: int = 50
    batchSize: int = 128
    lr: float = 0.005
    seed: int = 0
    # mixture-of-experts FFN; experts shard over the mesh `model` axis (EP)
    numExperts: int = 0
    expertCapacity: float = 1.25
    moeAuxWeight: float = 0.01
    # shard the time dimension over the mesh `model` axis (ring attention)
    seqParallel: bool = False
    # mid-training checkpoint/resume (reference knob: setCheckpointInterval)
    checkpointDir: Optional[str] = None
    checkpointInterval: int = 10


class SASRecAlgorithm(_ServesHistories, Algorithm):
    params_cls = SASRecParams

    def train(self, ctx, pd: TrainingData) -> SASRecModel:
        p = self.params
        return train_sasrec(
            ctx,
            pd.interactions,
            SASRecConfig(
                d_model=p.dModel,
                n_layers=p.numLayers,
                n_heads=p.numHeads,
                max_len=p.maxLen,
                epochs=p.epochs,
                batch_size=p.batchSize,
                lr=p.lr,
                seed=p.seed,
                n_experts=p.numExperts,
                expert_capacity=p.expertCapacity,
                moe_aux_weight=p.moeAuxWeight,
                seq_parallel=p.seqParallel,
                checkpoint_dir=p.checkpointDir,
                checkpoint_interval=p.checkpointInterval,
            ),
        )

    def _history(self, user: str, limit: int) -> list[str]:
        return self._histories().recent_items(user, limit)

    def predict(self, model: SASRecModel, query: Query) -> PredictedResult:
        history = self._history(query.user, model.config.max_len)
        items, scores = model.recommend(history, query.num)
        return PredictedResult(
            itemScores=[
                ItemScore(i, float(s)) for i, s in zip(items, scores)
            ]
        )


@dataclasses.dataclass
class PackedSequenceParams(Params):
    appName: str = "default"
    eventNames: tuple = ("view", "buy", "rate")
    # the model's shape under the keys of its published config.json (the
    # family's Config.from_hf); vocab_size is the catalog
    modelConfig: Optional[dict] = None
    maxLen: int = 2048
    seed: int = 0
    # the serving program: token counts compiled ahead, rows and leaderboard
    # width of one dispatch (serving/seqpath.py's defaults when None)
    tokenLadder: Optional[tuple] = None
    maxRows: Optional[int] = None
    maxK: Optional[int] = None
    # "auto" pickles the weights; "retrain" re-makes them at deploy
    persistMode: str = "auto"


LatentMoEParams = PackedSequenceParams


class PackedSequenceAlgorithm(_ServesHistories, Algorithm):
    """A packed sequence family on the batched serving path: everything
    but the model.  ``family`` names the module that has it (``Config``
    with ``from_hf`` and ``param_count``, ``Model``, ``init_params``).
    There is no trainer for these families yet: ``train`` returns SEEDED,
    untrained weights, and only for a model small enough to be a test
    fixture; it refuses a published width rather than hand back noise under
    a real model's name (the benchmark's families override it knowingly)."""

    params_cls = PackedSequenceParams
    family: str
    # the largest untrained model `train` hands out
    FIXTURE_PARAMS = 5_000_000

    def __init__(self, params=None):
        super().__init__(params)
        self._scorers: dict = {}
        self._scorer_lock = threading.Lock()

    def _family(self):
        import importlib

        return importlib.import_module(self.family)

    def _config(self, n_items: int):
        hf = dict(self.params.modelConfig or {})
        hf.setdefault("vocab_size", n_items)
        if hf["vocab_size"] < n_items:
            raise ValueError(
                f"{n_items} items do not fit a vocabulary of "
                f"{hf['vocab_size']}")
        return self._family().Config.from_hf(hf, max_len=self.params.maxLen)

    def _seeded_model(self, pd: TrainingData):
        family = self._family()
        cfg = self._config(pd.interactions.n_items)
        return family.Model(
            config=cfg, params=family.init_params(cfg, self.params.seed),
            item_map=pd.interactions.item_map, histories=pd.histories)

    def train(self, ctx, pd: TrainingData):
        cfg = self._config(pd.interactions.n_items)
        if cfg.param_count() > self.FIXTURE_PARAMS:
            raise NotImplementedError(
                f"no trainer for the {self.family} family yet: a model of "
                f"{cfg.param_count():,} parameters would be served untrained")
        return self._seeded_model(pd)

    def make_serializable_model(self, model):
        if self.params.persistMode == "retrain":
            from predictionio_tpu.core.persistence import RETRAIN

            return RETRAIN
        import jax

        return dataclasses.replace(model, params=jax.device_get(model.params))

    def _scorer(self, model):
        with self._scorer_lock:
            scorer = self._scorers.get(id(model))
            if scorer is None:
                from predictionio_tpu.serving import seqpath

                p = self.params
                scorer = seqpath.PackedSequenceScorer(
                    model.config, model.params,
                    max_k=p.maxK or seqpath.MAX_K,
                    ladder=p.tokenLadder or seqpath.TOKEN_LADDER,
                    max_rows=p.maxRows or seqpath.MAX_ROWS)
                self._scorers = {id(model): scorer}  # one generation resident
            return scorer

    @property
    def batch_row_ladder(self) -> tuple:
        """Where the batcher may cut a batch: anywhere.  The device programs
        are compiled per TOKEN count and take any number of rows up to
        ``maxRows``, so no row is carried over to reach a rung."""
        from predictionio_tpu.serving import seqpath

        return tuple(range(1, (self.params.maxRows or seqpath.MAX_ROWS) + 1))

    def warmup(self, model) -> None:
        """Deploy/reload-time: make the weights resident and compile and run
        every rung of the token ladder (QueryServer calls this for batching
        deployments), so no request compiles."""
        self._scorer(model)

    def serving_stats(self, model) -> Optional[dict]:
        scorer = self._scorers.get(id(model))
        return scorer.stats() if scorer is not None else None

    def batch_predict(self, model, queries):
        """The batcher's rows as ONE packed dispatch (more only when they
        exceed the top rung); a user with no history gets no items."""
        provider = self._histories(model)
        rows, hists, out = [], [], []
        for i, q in queries:
            h = provider.recent_indices(
                q.user, model.config.max_len, model.item_map)
            if len(h):
                rows.append((i, q))
                hists.append(h)
            else:
                out.append((i, PredictedResult(itemScores=[])))
        if rows:
            scorer = self._scorer(model)
            idx, vals = scorer.score_topk(
                hists, min(scorer.k, max(q.num for _, q in rows)))
            inv = model.item_map.inverse
            for row, (i, q) in enumerate(rows):
                out.append((i, PredictedResult(itemScores=[
                    ItemScore(item=inv[int(j)], score=float(s))
                    for j, s in zip(idx[row][:q.num], vals[row][:q.num])
                ])))
        return out

    def predict(self, model, query: Query) -> PredictedResult:
        return self.batch_predict(model, [(0, query)])[0][1]


class LatentMoEAlgorithm(PackedSequenceAlgorithm):
    """The latent-attention sparse-expert recommender (``latentmoe``)."""

    family = "predictionio_tpu.models.latent_moe"


class GDNHybridAlgorithm(PackedSequenceAlgorithm):
    """The gated-delta-rule / full-attention hybrid (``gdnhybrid``)."""

    family = "predictionio_tpu.models.gdn_hybrid"


class WindowMoEAlgorithm(PackedSequenceAlgorithm):
    """The window/global-attention sparse-expert recommender
    (``windowmoe``)."""

    family = "predictionio_tpu.models.window_moe"


class SSMParallelAlgorithm(PackedSequenceAlgorithm):
    """The parallel state-space / attention recommender
    (``ssmparallel``)."""

    family = "predictionio_tpu.models.ssm_parallel"


class SSMMoEAlgorithm(PackedSequenceAlgorithm):
    """The state-space / attention recommender with routed experts behind
    every layer (``ssmmoe``)."""

    family = "predictionio_tpu.models.ssm_moe"


class SequentialRecommendationEngine(EngineFactory):
    @classmethod
    def apply(cls) -> Engine:
        return Engine(
            data_source_cls=SequentialDataSource,
            preparator_cls=IdentityPreparator,
            algorithm_cls_map={
                "sasrec": SASRecAlgorithm,
                "latentmoe": LatentMoEAlgorithm,
                "gdnhybrid": GDNHybridAlgorithm,
                "windowmoe": WindowMoEAlgorithm,
                "ssmparallel": SSMParallelAlgorithm,
                "ssmmoe": SSMMoEAlgorithm,
            },
            serving_cls=FirstServing,
            query_cls=Query,
        )
