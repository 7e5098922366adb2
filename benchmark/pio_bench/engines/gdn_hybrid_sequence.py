"""The gated-delta-rule / full-attention hybrid recommender through the
sequence template's normal path, without the event store.

``core.workflow.run_train`` -> sealed instance -> ``QueryServer(batching=
True)`` (``prepare_deploy``, weights resident, every rung of the token ladder
compiled and run) -> ``POST /queries.json``, as
``engines/latent_moe_sequence.py`` does for the other packed family, whose
harness-side pieces this module imports (the structural check, the seeded
batches per compiled shape, the deployment's readers).  What lives here:

* ``FixedLengthSequenceDataSource`` hands over the item map and every
  user's history in memory through the template's history seam.  History
  LENGTHS are a fixed function of the user index (``fixed_lengths``: the
  configuration's lognormal law at stratified quantiles, a constant of the
  file), so that every seed's popular users carry the same work; the item
  ids in them are drawn from ``--seed``;
* ``SeededGDNHybrid`` is the template's ``GDNHybridAlgorithm`` whose
  ``train`` returns seeded weights of the configured widths, made on the
  device (the template's own refuses a published width: no trainer);
* ``audit``, this family's ``correct``: head, trunk and served, no routing.

Predict, warm-up, the packed scorer, the batcher and the HTTP front are the
program's, untouched.  On a CPU rehearsal the widths come from the
configuration's ``rehearsal`` block.
"""

from __future__ import annotations

import json
import os
import statistics
import time

import numpy as np

from pio_bench import reference, reference_gdn, seeded, seeded_seq
from pio_bench.engines import latent_moe_sequence as _seq
from pio_bench.engines.latent_moe_sequence import (  # noqa: F401  (harness)
    _get, ready_problems, shape_batches, structural_check, trunk_sample,
)

from predictionio_tpu.core import Engine, FirstServing, IdentityPreparator
from predictionio_tpu.data.batch import Interactions
from predictionio_tpu.data.bimap import BiMap
# the parent of this family's first PR has no such module: the cell then
# fails here, at once
from predictionio_tpu.models import gdn_hybrid  # noqa: F401
from predictionio_tpu.templates import sequentialrecommendation as template

_STATE: dict = {}

# the keys of the published config.json that shape the model
MODEL_KEYS = (
    "attention_bias", "hidden_act", "hidden_size", "intermediate_size",
    "layer_types", "linear_allow_neg_eigval", "linear_conv_kernel_dim",
    "linear_key_head_dim", "linear_num_key_heads", "linear_num_value_heads",
    "linear_value_head_dim", "num_attention_heads", "num_hidden_layers",
    "num_key_value_heads", "rms_norm_eps", "rope_parameters",
    "tie_word_embeddings",
)


def fixed_lengths(users: int, spec: dict) -> np.ndarray:
    """History length of every user index, the same in every run: the
    lognormal law's quantile at ``(r + 1/2) / 2^bits``, ``r`` the user
    index with its ``bits`` bits REVERSED (a van der Corput sequence).  Any
    run of consecutive indices then holds an even spread of the law — the
    schedule's popular users are such a run, at an offset the seed picks —
    so what they carry does not depend on the seed.  The rest of a window's
    draws are too sparse for that: their lengths vary as independent draws
    from the law would."""
    bits = max(1, int(users - 1).bit_length())
    idx = np.arange(users, dtype=np.uint64)
    rev = np.zeros(users, np.uint64)
    for b in range(bits):
        rev |= ((idx >> np.uint64(b)) & np.uint64(1)) << np.uint64(
            bits - 1 - b)
    inv = statistics.NormalDist().inv_cdf
    # 2^bits quantiles at most: computed once per distinct value
    z = np.array([inv((r + 0.5) / 2 ** bits) for r in range(2 ** bits)])
    lengths = np.exp(np.log(spec["median"]) + spec["sigma"] * z[rev])
    return np.clip(np.rint(lengths), spec["min"], spec["max"]).astype(np.int64)


def make_histories(seed: int, users: int, items: int, spec: dict):
    """Fixed lengths, seeded item ids (uniform over the catalog)."""
    indptr = np.zeros(users + 1, np.int64)
    np.cumsum(fixed_lengths(users, spec), out=indptr[1:])
    ids = seeded.rng(seed, seeded_seq.STREAM_HISTORY_ITEMS).integers(
        0, items, int(indptr[-1]), dtype=np.int32)
    return seeded_seq.Histories(indptr, ids)


class FixedLengthSequenceDataSource(_seq.SeededSequenceDataSource):
    def read_training(self, ctx):
        p = self.params
        key = (p.users, p.items, p.seed)
        if _STATE.get("data_key") != key:
            _STATE["data_key"] = key
            _STATE["histories"] = make_histories(
                p.seed, p.users, p.items, p.history)
            _STATE["item_map"] = _seq.item_map(p.items)
        # one event, so that the template's sanity check has a row to see
        return template.TrainingData(
            interactions=Interactions(
                user=np.zeros(1, np.int32), item=np.zeros(1, np.int32),
                rating=np.ones(1, np.float32), t=np.zeros(1, np.float64),
                user_map=BiMap({"u0": 0}), item_map=_STATE["item_map"]),
            histories=_STATE["histories"])


class SeededGDNHybrid(template.GDNHybridAlgorithm):
    """``train`` returns seeded weights of the configured widths.  With
    ``persistMode: retrain`` deploy calls it again and gets the same object
    back: the 8 GB are made once."""

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
        data_source_cls=FixedLengthSequenceDataSource,
        preparator_cls=IdentityPreparator,
        algorithm_cls_map={"gdnhybrid": SeededGDNHybrid},
        serving_cls=FirstServing,
        query_cls=template.Query,
    )


def model_config(cfg: dict) -> dict:
    """The published keys of the configuration file (what
    ``GDNHybridConfig.from_hf`` reads and checks), at the rehearsal's widths
    off the chip."""
    import jax

    hf = {k: cfg[k] for k in MODEL_KEYS}
    if jax.devices()[0].platform != "tpu":
        hf.update(cfg["rehearsal"]["model"])
    # the file holds the published pattern whole: the cut is its first layers
    hf["layer_types"] = list(hf["layer_types"][:hf["num_hidden_layers"]])
    hf["vocab_size"] = cfg["items"]
    return hf


class Deployment(_seq.Deployment):
    """One configuration deployed behind ``/queries.json`` in this process
    (the readers — ``root``, ``readyz``, ``traces``, ``counters``,
    ``scorer`` — are the other packed family's)."""

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
            "algorithms": [{"name": "gdnhybrid", "params": {
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
        # the program's defaults but for the admission gate, which the
        # configuration sizes (its max_inflight_why)
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


def direct_rows(scorer, batches: list) -> list:
    """Run each batch of histories as ONE direct dispatch of the compiled
    program; one dict per row with what the program made of it."""
    rows = []
    for hists in batches:
        out = scorer.forward(hists)
        for r, h in enumerate(hists):
            rows.append({
                "history": h,
                "h_last": np.asarray(out["h_last"][r], np.float32),
                "idx": out["indices"][r], "vals": out["values"][r],
                "rung": len(out["batch"]["tokens"])})
    return rows


def audit(dep: Deployment, records: list, sample: int) -> dict:
    """Judge what the window's answers SAY, and what the compiled programs
    compute.  (a) every successful answer structurally.  A seeded sample of
    them (the longest history among them), re-run one by one through the
    compiled programs, plus one direct packed dispatch per compiled shape,
    give rows with the program's ``h_last`` and top-k; on those rows (b)
    the head: the program's own scores against float64 ``h_last . E``
    (``reference.check_topk``: score, best unreturned, order) and, on
    ``trunk_rows_alone`` of the re-run rows and ``trunk_rows_per_shape`` of
    each shape's, (c) the trunk: ``h_last`` against the plain f32 reference
    (``reference_gdn.compare_trunk``).  (d) ties the window to those rows:
    each sampled SERVED answer against float64 scores of the re-run
    ``h_last`` — served and re-run are the same program on the same
    history, packed with other rows or not; nothing is routed, so they
    differ by rounding alone.  Requests that failed to arrive, and
    ``degraded`` answers, are not judged here: the harness counts them in
    ``failed``."""
    cfg, g = dep.cfg, dict(dep.cfg["guarantees"])
    if not dep.on_chip:  # the rehearsal's widths round more coarsely
        g.update(cfg["rehearsal"]["guarantees"])
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
    # (c) the trunk: the reference costs 0.3-3 s a row at these widths, so
    # of the re-run rows a seeded subset (the longest history among them)
    longest = max(range(len(rerun)), key=lambda j: len(rerun[j]["history"]),
                  default=0)
    few = sorted({longest, *gen.choice(
        len(rerun), min(len(rerun), g["trunk_rows_alone"]),
        replace=False).tolist()}) if rerun else []
    trunk_rows = trunk_sample([rerun[j] for j in few], shaped,
                              g["trunk_rows_per_shape"])
    t0 = time.perf_counter()
    trunk = reference_gdn.compare_trunk(dep.hf, dep.model.params, trunk_rows)
    t_trunk = time.perf_counter() - t0
    say(f"check trunk h_last_rel_err = {trunk['h_last_rel_err']:.6g} "
        f"(limit {g['trunk_tolerance']:g}; mean "
        f"{trunk['h_last_rel_err_mean']:.6g}) over {trunk['rows']} rows "
        f"({len(few)} of {len(rerun)} served re-run + "
        f"{trunk['rows'] - len(few)} of {len(shaped)} direct rows of "
        f"{len(scorer.ladder)} shapes); the "
        f"worst row has {trunk['worst_row_tokens']} events")
    trunk_ok = trunk["h_last_rel_err"] <= g["trunk_tolerance"]
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
