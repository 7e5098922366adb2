"""ALS through the recommendation template's normal path, without the
event store.

``core.workflow.run_train`` -> sealed instance -> ``QueryServer(batching=
True)`` (``prepare_deploy``, AOT warm-up of every rung) -> ``POST
/queries.json``, with the template's own Preparator, Serving and Query and
two classes that live here:

* ``SeededDataSource`` hands over the id maps of the configured width in
  memory (writing events costs 29 us each on the chip host);
* ``SeededFactorsALS`` is the template's ``ALSAlgorithm`` whose ``train``
  returns an ``ALSModel`` with factors drawn from the seed (``N(0,
  1/rank)``, as ``train_als`` initialises them) instead of iterating.

Predict, warm-up, the fast path, the batcher and the HTTP front are the
program's, untouched.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import urllib.request

import numpy as np

from pio_bench import reference, seeded

from predictionio_tpu.core import DataSource, Engine, Params
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
from predictionio_tpu.models.als import ALSModel
from predictionio_tpu.templates import recommendation as template

_MAPS: dict = {}
_MODELS: dict = {}


def id_maps(n_users: int, n_items: int):
    """String ids <-> row indices, made once per process.  Where both sides
    have the same count they are one node set (a link graph) and share one
    map."""
    key = (n_users, n_items)
    if key not in _MAPS:
        def one(prefix, n):
            names = [f"{prefix}{i}" for i in range(n)]
            return BiMap(dict(zip(names, range(n))), dict(enumerate(names)))

        if n_users == n_items:
            _MAPS[key] = (one("n", n_users),) * 2
        else:
            _MAPS[key] = (one("u", n_users), one("i", n_items))
    return _MAPS[key]


@dataclasses.dataclass
class SeededDataSourceParams(Params):
    users: int = 0
    items: int = 0


class SeededDataSource(DataSource):
    params_cls = SeededDataSourceParams

    def read_training(self, ctx):
        user_map, item_map = id_maps(self.params.users, self.params.items)
        # one rating, so that the template's sanity check has a row to see;
        # the serving cells' algorithm does not iterate over it
        return template.TrainingData(Interactions(
            user=np.zeros(1, np.int32), item=np.zeros(1, np.int32),
            rating=np.ones(1, np.float32), t=np.zeros(1, np.float64),
            user_map=user_map, item_map=item_map))


class SeededFactorsALS(template.ALSAlgorithm):
    """``train`` returns seeded factors of the configured width.  With
    ``persistMode: retrain`` (the template's Unit-model mode) deploy calls
    it again and gets the same object back."""

    def train(self, ctx, pd) -> ALSModel:
        inter = pd.interactions
        p = self.params
        key = (inter.n_users, inter.n_items, p.rank, p.seed)
        if key not in _MODELS:
            _MODELS.clear()  # one model's factors at a time on the host
            _MODELS[key] = ALSModel(
                user_factors=seeded.make_factors(
                    p.seed, seeded.STREAM_USER_FACTORS, inter.n_users, p.rank),
                item_factors=seeded.make_factors(
                    p.seed, seeded.STREAM_ITEM_FACTORS, inter.n_items, p.rank),
                user_map=inter.user_map, item_map=inter.item_map,
                config=self._config())
        return _MODELS[key]


def engine() -> Engine:
    return Engine(
        data_source_cls=SeededDataSource,
        preparator_cls=template.ExcludeItemsPreparator,
        algorithm_cls_map={"als": SeededFactorsALS},
        serving_cls=template.FileFilterServing,
        query_cls=template.Query,
    )


def _get(url: str):
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read().decode())


class Deployment:
    """One configuration deployed behind ``/queries.json`` in this process."""

    def __init__(self, cfg: dict, seed: int, workdir: str, ctx):
        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.data.storage.registry import Storage
        from predictionio_tpu.serving.query_server import QueryServer

        self.cfg, self.seed = cfg, seed
        t0 = time.perf_counter()
        os.environ["PIO_FS_BASEDIR"] = os.path.join(workdir, "pio_store")
        storage = Storage(env={
            "PIO_STORAGE_SOURCES_META_TYPE": "sqlite",
            "PIO_STORAGE_SOURCES_META_PATH": os.path.join(workdir, "meta.db"),
            "PIO_STORAGE_SOURCES_MODELS_TYPE": "localfs",
            "PIO_STORAGE_SOURCES_MODELS_PATH": os.path.join(workdir, "models"),
            "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "META",
            "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MODELS",
        })
        eng = engine()
        variant = {
            "engineFactory": __name__ + ".engine",
            "datasource": {"params": {
                "users": cfg["users"], "items": cfg["items"]}},
            "algorithms": [{"name": "als", "params": {
                "rank": cfg["rank"], "numIterations": 0, "lambda": 0.01,
                "seed": seed, "persistMode": "retrain"}}],
        }
        self.instance_id = run_train(
            eng, eng.params_from_variant(variant),
            engine_factory=variant["engineFactory"], storage=storage, ctx=ctx,
            engine_id=cfg["name"], engine_version="1",
            engine_variant="default")
        t1 = time.perf_counter()
        # batching deployments keep the program's defaults: max_batch 64,
        # window 2.0 ms, rungs 1/8/16/32/64, no deadline, 256 in flight
        self.qs = QueryServer(
            eng, storage=storage, ctx=ctx, engine_id=cfg["name"],
            engine_version="1", engine_variant="default", batching=True)
        self.port = self.qs.start("127.0.0.1", 0)
        self.base = f"http://127.0.0.1:{self.port}"
        (self.model,) = _MODELS.values()
        self.seconds = {"run_train": t1 - t0,
                        "deploy_and_warm": time.perf_counter() - t1}

    # -- what the harness reads ------------------------------------------
    def root(self) -> dict:
        return _get(self.base + "/")

    def readyz(self) -> dict:
        return _get(self.base + "/readyz")

    def traces(self) -> list:
        return _get(self.base + "/trace/recent.json")

    def counters(self) -> dict:
        """The batcher's and the fast path's counts, flat."""
        root = self.root()
        fp = (root.get("fastpath") or [{}])[0]
        out = {"batcher." + k: v for k, v in (root.get("batching") or {}).items()}
        out.update({"fastpath." + k: v for k, v in fp.items()
                    if isinstance(v, (int, float, dict))})
        out["resilience"] = root["resilience"]["counters"]
        return out

    def user_name(self, index: int) -> str:
        return self.model.user_map.inverse[int(index)]

    def scorer(self):
        d = self.qs._deployed
        return d.algorithms[0]._scorer(d.models[0]).enable_fastpath()

    def stop(self) -> None:
        self.qs.stop()


def ready_problems(ready: dict, instance_id: str) -> list:
    return [msg for bad, msg in (
        (ready.get("fastpathWarm") is not True, "fastpathWarm is not true"),
        (ready.get("engineInstanceId") != instance_id,
         "the served generation is not the published one"),
        (ready.get("reloadDegraded"), "reloadDegraded"),
    ) if bad]


def audit(dep: Deployment, records: list, sample: int) -> dict:
    """Judge what the window's answers SAY.  Every successful answer is
    checked structurally; a seeded sample of them (the longest among them)
    and one direct call per rung are checked against float64.  Requests
    that failed to arrive, and ``degraded`` answers, are not judged here:
    the harness counts them in ``failed``."""
    cfg, model = dep.cfg, dep.model
    item_map = model.item_map
    ok_recs, structural = [], []
    for rec in records:
        if rec["status"] != 200 or rec.get("degraded"):
            continue
        scores = rec["answer"].get("itemScores")
        if not isinstance(scores, list) or len(scores) != rec["num"]:
            structural.append(f"request {rec['i']}: {rec['num']} asked, "
                              f"{str(rec['answer'])[:120]}")
            continue
        try:
            idx = [item_map[s["item"]] for s in scores]
            vals = [float(s["score"]) for s in scores]
        except (KeyError, TypeError, ValueError) as e:
            structural.append(f"request {rec['i']}: {type(e).__name__} {e}")
            continue
        if len(set(idx)) != len(idx):
            structural.append(f"request {rec['i']}: an item twice")
        elif any(b > a for a, b in zip(vals, vals[1:])):
            structural.append(f"request {rec['i']}: scores increase")
        else:
            ok_recs.append((rec, idx, vals))
    gen = seeded.rng(dep.seed, seeded.STREAM_AUDIT)
    if len(ok_recs) > sample:
        longest = max(range(len(ok_recs)), key=lambda j: ok_recs[j][0]["num"])
        pick = set(gen.choice(len(ok_recs), sample, replace=False).tolist())
        pick.add(longest)
        chosen = [ok_recs[j] for j in sorted(pick)]
    else:
        chosen = ok_recs
    users = [rec["user"] for rec, _, _ in chosen]
    idx = [i for _, i, _ in chosen]
    vals = [v for _, _, v in chosen]
    want = [rec["num"] for rec, _, _ in chosen]
    n_served = len(users)
    # each compiled rung directly, outside the window: which rungs the
    # traffic happened to hit cannot change the verdict
    fp = dep.scorer()
    compiles_before = fp.compile_count
    rgen = seeded.rng(dep.seed, seeded.STREAM_RUNGS)
    rung_rows = {}
    for b in cfg["rungs"]:
        u = rgen.choice(cfg["users"], b, replace=False)
        i_b, v_b = fp.score_topk(u, cfg["max_k"])
        rung_rows[b] = (len(users), len(users) + b)
        users += u.tolist()
        idx += [r for r in np.asarray(i_b)]
        vals += [r for r in np.asarray(v_b)]
        want += [cfg["max_k"]] * b
    res = reference.check_topk(
        model.user_factors, model.item_factors, users, idx, vals, want,
        cfg["guarantees"]["score_tolerance"])
    res.update(
        answers_checked_structurally=len(ok_recs) + len(structural),
        structural_failures=structural[:5], n_structural_failures=len(structural),
        served_rows=n_served, rung_rows=sum(cfg["rungs"]),
        rung_compiles=fp.compile_count - compiles_before)
    res["ok"] = bool(res["ok"] and not structural and n_served > 0
                     and res["rung_compiles"] == 0)
    return res
