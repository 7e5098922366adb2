"""The latent-attention sparse-expert recommender through the sequence
template's normal path, without the event store.

``core.workflow.run_train`` -> sealed instance -> ``QueryServer(batching=
True)`` (``prepare_deploy``, weights resident, every rung of the token ladder
compiled and run) -> ``POST /queries.json``, with the template's own Query,
Serving and Preparator slot and two classes that live here:

* ``SeededSequenceDataSource`` hands over the item map and every user's
  history in memory (``seeded_seq.Histories``, one CSR array made from the
  seed) through the template's history seam;
* ``SeededLatentMoE`` is the template's ``LatentMoEAlgorithm`` whose
  ``train`` returns seeded weights of the configured widths, made on the
  device (the template's own ``train`` refuses a published width: it has no
  trainer).

Predict, warm-up, the packed scorer, the batcher and the HTTP front are the
program's, untouched.  On a CPU rehearsal the widths come from the
configuration's ``rehearsal`` block.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
import urllib.request

import numpy as np

from pio_bench import reference, reference_seq, seeded, seeded_seq

from predictionio_tpu.core import DataSource, Engine, FirstServing, \
    IdentityPreparator, Params
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
# the parent of this family's first PR has no such module: the cell then
# fails here, at once
from predictionio_tpu.models import latent_moe  # noqa: F401
from predictionio_tpu.templates import sequentialrecommendation as template

_STATE: dict = {}

STREAM_SHAPES = 21

# the keys of the published config.json that shape the model
MODEL_KEYS = (
    "attention_bias", "first_k_dense_replace", "hidden_act", "hidden_size",
    "intermediate_size", "kv_lora_rank", "moe_intermediate_size",
    "moe_layer_freq", "n_group", "n_routed_experts", "n_shared_experts",
    "norm_topk_prob", "num_attention_heads", "num_experts_per_tok",
    "num_hidden_layers", "q_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "rms_norm_eps", "rope_interleave", "rope_scaling",
    "rope_theta", "routed_scaling_factor", "scoring_func",
    "tie_word_embeddings", "topk_group", "topk_method", "v_head_dim",
)


def item_map(n_items: int) -> BiMap:
    names = [f"i{j}" for j in range(n_items)]
    return BiMap(dict(zip(names, range(n_items))), dict(enumerate(names)))


@dataclasses.dataclass
class SeededSequenceDataSourceParams(Params):
    users: int = 0
    items: int = 0
    seed: int = 0
    history: dict = dataclasses.field(default_factory=dict)


class SeededSequenceDataSource(DataSource):
    params_cls = SeededSequenceDataSourceParams

    def read_training(self, ctx):
        p = self.params
        key = (p.users, p.items, p.seed)
        if _STATE.get("data_key") != key:
            _STATE["data_key"] = key
            _STATE["histories"] = seeded_seq.make_histories(
                p.seed, p.users, p.items, p.history)
            _STATE["item_map"] = item_map(p.items)
        # one event, so that the template's sanity check has a row to see
        return template.TrainingData(
            interactions=Interactions(
                user=np.zeros(1, np.int32), item=np.zeros(1, np.int32),
                rating=np.ones(1, np.float32), t=np.zeros(1, np.float64),
                user_map=BiMap({"u0": 0}), item_map=_STATE["item_map"]),
            histories=_STATE["histories"])


class SeededLatentMoE(template.LatentMoEAlgorithm):
    """``train`` returns seeded weights of the configured widths.  With
    ``persistMode: retrain`` deploy calls it again and gets the same object
    back: the 11 GB are made once."""

    def train(self, ctx, pd):
        key = (json.dumps(self.params.modelConfig, sort_keys=True),
               self.params.seed, pd.interactions.n_items)
        if _STATE.get("model_key") != key:
            _STATE["model"] = None  # one model's weights at a time
            _STATE["model_key"] = key
            _STATE["model"] = self._seeded_model(pd)
        return _STATE["model"]


def engine() -> Engine:
    return Engine(
        data_source_cls=SeededSequenceDataSource,
        preparator_cls=IdentityPreparator,
        algorithm_cls_map={"latentmoe": SeededLatentMoE},
        serving_cls=FirstServing,
        query_cls=template.Query,
    )


def _get(url: str):
    with urllib.request.urlopen(url, timeout=60) as r:
        return json.loads(r.read().decode())


def model_config(cfg: dict) -> dict:
    """The published keys of the configuration file (what
    ``LatentMoEConfig.from_hf`` reads and checks), at the rehearsal's widths
    off the chip."""
    import jax

    hf = {k: cfg[k] for k in MODEL_KEYS}
    if jax.devices()[0].platform != "tpu":
        hf.update(cfg["rehearsal"]["model"])
    hf["vocab_size"] = cfg["items"]
    return hf


class Deployment:
    """One configuration deployed behind ``/queries.json`` in this process."""

    def __init__(self, cfg: dict, seed: int, workdir: str, ctx):
        import jax

        from predictionio_tpu.core.workflow import run_train
        from predictionio_tpu.data.storage.registry import Storage
        from predictionio_tpu.serving.query_server import QueryServer

        self.cfg, self.seed = cfg, seed
        self.on_chip = jax.devices()[0].platform == "tpu"
        self.hf = model_config(cfg)
        serving = dict(cfg["serving"])
        if not self.on_chip:
            serving.update(cfg["rehearsal"]["serving"])
        self.max_len = serving["max_len"]
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
        history = dict(cfg["history"], max=min(cfg["history"]["max"],
                                               self.max_len))
        eng = engine()
        variant = {
            "engineFactory": __name__ + ".engine",
            "datasource": {"params": {
                "users": cfg["users"], "items": cfg["items"], "seed": seed,
                "history": history}},
            "algorithms": [{"name": "latentmoe", "params": {
                "modelConfig": self.hf, "maxLen": self.max_len, "seed": seed,
                "tokenLadder": serving["token_ladder"],
                "maxRows": serving["max_rows"], "maxK": cfg["max_k"],
                "persistMode": "retrain"}}],
        }
        self.instance_id = run_train(
            eng, eng.params_from_variant(variant),
            engine_factory=variant["engineFactory"], storage=storage, ctx=ctx,
            engine_id=cfg["name"], engine_version="1",
            engine_variant="default")
        t1 = time.perf_counter()
        # the program's defaults (max_batch 64, window 2.0 ms, no deadline)
        # but for the admission gate, which the configuration sizes to ride
        # out a dispatch that stands still (its max_inflight_why)
        self.qs = QueryServer(
            eng, storage=storage, ctx=ctx, engine_id=cfg["name"],
            engine_version="1", engine_variant="default", batching=True,
            max_inflight=cfg["serving"]["max_inflight"])
        self.port = self.qs.start("127.0.0.1", 0)
        self.base = f"http://127.0.0.1:{self.port}"
        self.model = _STATE["model"]
        self.histories = _STATE["histories"]
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
        """The batcher's and the packed scorer's counts, flat."""
        root = self.root()
        fp = (root.get("fastpath") or [{}])[0]
        out = {"batcher." + k: v for k, v in (root.get("batching") or {}).items()}
        out.update({"fastpath." + k: v for k, v in fp.items()
                    if isinstance(v, (int, float, dict))})
        out["resilience"] = root["resilience"]["counters"]
        return out

    def user_name(self, index: int) -> str:
        return f"u{int(index)}"

    def scorer(self):
        d = self.qs._deployed
        return d.algorithms[0]._scorer(d.models[0])

    def stop(self) -> None:
        self.qs.stop()


def ready_problems(ready: dict, instance_id: str) -> list:
    return [msg for bad, msg in (
        (ready.get("fastpathWarm") is not True, "fastpathWarm is not true"),
        (ready.get("engineInstanceId") != instance_id,
         "the served generation is not the published one"),
        (ready.get("reloadDegraded"), "reloadDegraded"),
    ) if bad]


def structural_check(records: list, n_items: int):
    """Every successful answer: the asked number of distinct known items,
    scores that never increase.  Returns the clean (record, item indices,
    scores) triples and the failures."""
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
            idx = [int(s["item"][1:]) for s in scores]
            vals = [float(s["score"]) for s in scores]
            if any(s["item"][:1] != "i" or not 0 <= j < n_items
                   for s, j in zip(scores, idx)):
                raise ValueError("an item outside the catalog")
        except (KeyError, TypeError, ValueError) as e:
            structural.append(f"request {rec['i']}: {type(e).__name__} {e}")
            continue
        if len(set(idx)) != len(idx):
            structural.append(f"request {rec['i']}: an item twice")
        elif any(b > a for a, b in zip(vals, vals[1:])):
            structural.append(f"request {rec['i']}: scores increase")
        else:
            ok_recs.append((rec, idx, vals))
    return ok_recs, structural


def shape_batches(dep: Deployment, scorer) -> dict:
    """For each compiled token count, seeded users whose histories fill
    more than half of it (so the dispatch lands on that rung)."""
    gen = seeded.rng(dep.seed, STREAM_SHAPES)
    out, lower = {}, 0
    for t in scorer.ladder:
        rows, n_tok = [], 0
        while len(rows) < scorer.max_rows:
            h = dep.histories.of(int(gen.integers(dep.cfg["users"])),
                                 dep.max_len)
            if n_tok + len(h) > t:
                if n_tok > lower:
                    break
                continue
            rows.append(h)
            n_tok += len(h)
        out[t], lower = rows, t
    return out


def direct_rows(scorer, batches: list) -> list:
    """Run each batch of histories as ONE direct dispatch of the compiled
    program; one dict per row with what the program made of it."""
    rows = []
    for hists in batches:
        out = scorer.forward(hists)
        b = out["batch"]
        for r, h in enumerate(hists):
            hi = int(b["last_idx"][r])
            lo = int(b["seg_start"][hi])
            rows.append({
                "history": h, "picks": out["picks"][:, lo:hi + 1],
                "h_last": np.asarray(out["h_last"][r], np.float32),
                "idx": out["indices"][r], "vals": out["values"][r],
                "rung": len(b["tokens"])})
    return rows


def trunk_sample(alone: list, shaped: list, per_shape: int) -> list:
    """The rows the trunk is compared on: every one-by-one row, and of each
    compiled shape's packed dispatch the first ``per_shape`` rows (the
    program computes a dispatch's rows alike; the reference costs a second
    a row)."""
    seen, rows = {}, list(alone)
    for r in shaped:
        if seen.setdefault(r["rung"], 0) < per_shape:
            seen[r["rung"]] += 1
            rows.append(r)
    return rows


def audit(dep: Deployment, records: list, sample: int) -> dict:
    """Judge what the window's answers SAY, and what the compiled programs
    compute.  (a) every successful answer structurally.  A seeded sample of
    them (the longest history among them), re-run one by one through the
    compiled programs, plus one direct packed dispatch per compiled shape,
    give rows with the program's ``h_last``, picks and top-k; on those rows
    (b) the head: the program's own scores against float64 ``h_last . E``
    (``reference.check_topk``: score, best unreturned, order) and (c) the
    trunk: ``h_last`` and the picks against the plain reference
    (``reference_seq.compare_trunk``).  (d) ties the window to those rows:
    each sampled SERVED answer against float64 scores of the re-run
    ``h_last`` — served and re-run are the same program on the same
    history, packed with other rows or not, so they differ by bf16 rounding
    and by the routing near-ties it flips.  Requests that failed to arrive,
    and ``degraded`` answers, are not judged here: the harness counts them
    in ``failed``."""
    cfg, g = dep.cfg, dep.cfg["guarantees"]
    say = lambda msg: print(f"[audit] {msg}", flush=True)
    ok_recs, structural = structural_check(records, cfg["items"])
    gen = seeded.rng(dep.seed, seeded.STREAM_AUDIT)
    hist_of = lambda rec: dep.histories.of(rec["user"], dep.max_len)
    if len(ok_recs) > sample:
        longest = max(range(len(ok_recs)),
                      key=lambda j: len(hist_of(ok_recs[j][0])))
        pick = set(gen.choice(len(ok_recs), sample, replace=False).tolist())
        pick.add(longest)
        chosen = [ok_recs[j] for j in sorted(pick)]
    else:
        chosen = ok_recs
    scorer = dep.scorer()
    compiles_before = scorer.compile_count
    t0 = time.perf_counter()
    rerun = direct_rows(scorer, [[hist_of(rec)] for rec, _, _ in chosen])
    shaped = direct_rows(scorer, list(shape_batches(dep, scorer).values()))
    t_direct = time.perf_counter() - t0
    head = np.asarray(dep.model.params["head"][:cfg["items"]],
                      dtype=np.float32)
    rows = rerun + shaped
    U = np.stack([r["h_last"] for r in rows])
    vmax = reference.max_row_norm(head)
    k = cfg["max_k"]
    res = reference.check_topk(
        U, head, np.arange(len(rows)), [r["idx"] for r in rows],
        [r["vals"] for r in rows], [k] * len(rows), g["score_tolerance"],
        vmax=vmax)
    # (d) the served answers against their own re-run
    served = reference.check_topk(
        U, head, np.arange(len(chosen)), [i for _, i, _ in chosen],
        [v for _, _, v in chosen], [rec["num"] for rec, _, _ in chosen],
        g["served_tolerance"], vmax=vmax) if chosen else None
    # (c) the trunk
    trunk_rows = trunk_sample(rerun, shaped, g["trunk_rows_per_shape"])
    t0 = time.perf_counter()
    trunk = reference_seq.compare_trunk(dep.hf, dep.model.params, trunk_rows)
    t_trunk = time.perf_counter() - t0
    say(f"check trunk h_last_rel_err = {trunk['h_last_rel_err']:.6g} "
        f"(limit {g['trunk_tolerance']:g}) over {trunk['rows']} rows "
        f"({len(rerun)} served re-run + {trunk['rows'] - len(rerun)} of "
        f"{len(shaped)} direct rows of {len(scorer.ladder)} shapes)")
    say(f"check trunk route_violation = {trunk['route_violation']:.6g} "
        f"(limit {g['route_tolerance']:g}); reported, not judged: "
        f"{trunk['flipped_decisions']} of {trunk['decisions']} routing "
        f"decisions differ from the reference's own, in "
        f"{trunk['rows_with_a_flip']} rows, at the last position in "
        f"{trunk['rows_with_a_flip_at_the_last_position']}")
    trunk_ok = (trunk["h_last_rel_err"] <= g["trunk_tolerance"]
                and trunk["route_violation"] <= g["route_tolerance"])
    served_ok = True
    if served is not None:
        for name in ("score", "beat", "order"):
            say(f"check served {name}_over_tol = "
                f"{served[name + '_over_tol']:.6g} (limit 1; tolerance "
                f"{g['served_tolerance']:g}*|u|*max|v|) over "
                f"{len(chosen)} served answers against their re-run")
        served_ok = served["ok"]
    res["seconds"].update(direct_calls=t_direct, trunk_reference=t_trunk)
    res.update(
        answers_checked_structurally=len(ok_recs) + len(structural),
        structural_failures=structural[:5],
        n_structural_failures=len(structural),
        served_rows=len(rerun), rung_rows=len(shaped),
        rung_compiles=scorer.compile_count - compiles_before,
        trunk=trunk, served=served and {
            k2: served[k2] for k2 in ("score_over_tol", "beat_over_tol",
                                      "order_over_tol", "n_structural")})
    res["ok"] = bool(res["ok"] and not structural and len(chosen) > 0
                     and res["rung_compiles"] == 0 and trunk_ok and served_ok)
    return res
