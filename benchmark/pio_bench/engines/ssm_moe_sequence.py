"""The state-space / attention recommender with routed experts behind every
layer (one rank's share of an expert-parallel deployment) through the
sequence template's normal path, without the event store.

``core.workflow.run_train`` -> sealed instance -> ``QueryServer(batching=
True)`` (``prepare_deploy``, weights resident, every rung of the token ladder
compiled and run) -> ``POST /queries.json``, as the other four packed
families' engines do, whose harness-side pieces this module imports: the
structural check, the seeded batches per compiled shape and the deployment's
readers from ``engines/latent_moe_sequence.py``, the history lengths fixed
per user index from ``engines/gdn_hybrid_sequence.py``, the direct rows (with
the routing picks), the sample of served answers with the longest histories
and the trunk's three judged numbers from ``engines/window_moe_sequence.py``.
What lives here:

* ``SeededSSMMoE`` is the template's ``SSMMoEAlgorithm`` whose ``train``
  returns seeded weights of the configured widths, made on the device (the
  template's own refuses a published width: no trainer);
* ``model_config``: the configuration file's keys as the family's
  ``Config.from_hf`` reads them — the layers of this stage out of the
  published ``layer_types``, the router's published width beside the
  experts held here and the first of them (the rank's);
* ``audit``, this family's ``correct``: head, trunk with forced routing,
  route, served.

Predict, warm-up, the packed scorer, the batcher and the HTTP front are the
program's, untouched.  On a CPU rehearsal the widths come from the
configuration's ``rehearsal`` block.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from pio_bench import reference, reference_smoe, seeded
from pio_bench.engines import gdn_hybrid_sequence as _fixed
from pio_bench.engines import latent_moe_sequence as _seq
from pio_bench.engines.latent_moe_sequence import (  # noqa: F401  (harness)
    ready_problems, shape_batches, structural_check, trunk_sample,
)
from pio_bench.engines.window_moe_sequence import (  # noqa: F401  (harness)
    direct_rows, sample_served, trunk_problems,
)

from predictionio_tpu.core import Engine, FirstServing, IdentityPreparator
# the parent of this family's first PR has no such module: the cell then
# fails here, at once
from predictionio_tpu.models import ssm_moe  # noqa: F401
from predictionio_tpu.templates import sequentialrecommendation as template

_STATE = _fixed._STATE  # the data source's histories live there

# the keys of the published config.json that shape the model
MODEL_KEYS = (
    "attention_bias", "attention_multiplier", "embedding_multiplier",
    "hidden_act", "hidden_size", "intermediate_size", "logits_scaling",
    "mamba_chunk_size", "mamba_conv_bias", "mamba_d_conv", "mamba_d_head",
    "mamba_d_state", "mamba_expand", "mamba_n_groups", "mamba_n_heads",
    "mamba_proj_bias", "normalization_function", "num_attention_heads",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads",
    "num_local_experts", "position_embedding_type", "residual_multiplier",
    "rms_norm_eps", "rope_scaling", "shared_intermediate_size",
    "tie_word_embeddings",
)
# histories beyond this many events are sampled on purpose (the two top
# rungs, each filled by ONE row)
LONG = 4096


class SeededSSMMoE(template.SSMMoEAlgorithm):
    """``train`` returns seeded weights of the configured widths.  With
    ``persistMode: retrain`` deploy calls it again and gets the same object
    back: the 9.9 GB are made once."""

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
        data_source_cls=_fixed.FixedLengthSequenceDataSource,
        preparator_cls=IdentityPreparator,
        algorithm_cls_map={"ssmmoe": SeededSSMMoE},
        serving_cls=FirstServing,
        query_cls=template.Query,
    )


def model_config(cfg: dict) -> dict:
    """What ``SSMMoEConfig.from_hf`` reads and checks, from the
    configuration file: its published keys (at the rehearsal's widths off
    the chip); ``layer_types`` cut to this stage's layers (the file holds
    the published pattern whole); the file's ``num_local_experts`` is what
    is HELD here, the router keeps the published count, and the first held
    expert is the rank's."""
    import jax

    hf = {k: cfg[k] for k in MODEL_KEYS}
    router = cfg["published"]["num_local_experts"]
    if jax.devices()[0].platform != "tpu":
        hf.update(cfg["rehearsal"]["model"])
        router = cfg["rehearsal"]["router_experts"]
    stage = cfg["stage"]
    hf["layer_types"] = list(cfg["layer_types"][
        stage["first_layer"]:stage["first_layer"] + hf["num_hidden_layers"]])
    held = hf["num_local_experts"]
    hf.update(num_local_experts=router, num_experts_held=held,
              first_expert_held=stage["expert_rank"] * held,
              vocab_size=cfg["items"])
    return hf


class Deployment(_seq.Deployment):
    """One configuration deployed behind ``/queries.json`` in this process
    (the readers — ``root``, ``readyz``, ``traces``, ``counters``,
    ``scorer`` — are the other packed families')."""

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
        if not self.on_chip:
            history.update(cfg["rehearsal"].get("history", {}))
        eng = engine()
        variant = {
            "engineFactory": __name__ + ".engine",
            "datasource": {"params": {
                "users": cfg["users"], "items": cfg["items"], "seed": seed,
                "history": history}},
            "algorithms": [{"name": "ssmmoe", "params": {
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


def audit(dep: Deployment, records: list, sample: int) -> dict:
    """Judge what the window's answers SAY, and what the compiled programs
    compute.  (a) every successful answer structurally.  A seeded sample of
    them (the longest history among them, and the three longest beyond 4,096
    events), re-run one by one through the compiled programs, plus one
    direct packed dispatch per compiled shape, give rows with the program's
    ``h_last``, residual stream, picks and top-k; on those rows (b) the
    head: the program's own scores against float64 ``h_last . E``, ``E`` the
    tied table and ``h_last`` holding ``1 / logits_scaling``
    (``reference.check_topk``: score, best unreturned, order) and, on
    ``trunk_rows_alone`` of the re-run rows (the longest and the longest
    beyond 4,096 among them) and ``trunk_rows_per_shape`` of each shape's,
    (c) the trunk: the f32 residual stream at the last position and
    ``h_last`` against the plain f32 reference given the same held experts,
    routing forced to the program's picks, and how admissible those picks
    are (``reference_smoe.compare_trunk``: ``added_rel_err``,
    ``h_last_rel_err``, ``route_violation``).  (d) ties the window to those
    rows: each sampled SERVED answer against float64 scores of the re-run
    ``h_last`` — served and re-run are the same program on the same
    history, packed with other rows or not, so they differ by bf16 rounding
    and by the routing near-ties it flips.  Requests that failed to arrive,
    and ``degraded`` answers, are not judged here: the harness counts them
    in ``failed``."""
    cfg, g = dep.cfg, dict(dep.cfg["guarantees"])
    if not dep.on_chip:  # the rehearsal's widths round more coarsely
        g.update(cfg["rehearsal"]["guarantees"])
    say = lambda msg: print(f"[audit] {msg}", flush=True)
    ok_recs, structural = structural_check(records, cfg["items"])
    gen = seeded.rng(dep.seed, seeded.STREAM_AUDIT)
    hist_of = lambda rec: dep.histories.of(rec["user"], dep.max_len)
    long_over = LONG if dep.on_chip else dep.max_len // 2
    chosen = sample_served(ok_recs, hist_of, sample, gen, long_over)
    scorer = dep.scorer()
    compiles_before = scorer.compile_count
    t0 = time.perf_counter()
    rerun = direct_rows(scorer, [[hist_of(rec)] for rec, _, _ in chosen])
    shaped = direct_rows(scorer, list(shape_batches(dep, scorer).values()))
    t_direct = time.perf_counter() - t0
    # the tied table: the embedding's rows are the head's
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
    # (c) the trunk: the reference walks a history token by token, so of
    # the re-run rows the longest, the longest beyond 4,096 and a seeded few
    by_length = sorted(range(len(rerun)),
                       key=lambda j: -len(rerun[j]["history"]))
    long_rows = [j for j in by_length[:g["trunk_rows_long"] + 1]
                 if j == by_length[0] or len(rerun[j]["history"]) > long_over]
    few = sorted({*long_rows, *gen.choice(
        len(rerun), min(len(rerun), g["trunk_rows_alone"]),
        replace=False).tolist()}) if rerun else []
    trunk_rows = trunk_sample([rerun[j] for j in few], shaped,
                              g["trunk_rows_per_shape"])
    t0 = time.perf_counter()
    trunk = reference_smoe.compare_trunk(dep.hf, dep.model.params, trunk_rows)
    t_trunk = time.perf_counter() - t0
    n_long = sum(len(r["history"]) > long_over for r in trunk_rows)
    say(f"check trunk added_rel_err = {trunk['added_rel_err']:.6g} "
        f"(limit {g['trunk_tolerance']:g}; the error of the residual stream "
        f"over what the layers added to it) over {trunk['rows']} rows "
        f"({len(few)} of {len(rerun)} served re-run + "
        f"{trunk['rows'] - len(few)} of {len(shaped)} direct rows of "
        f"{len(scorer.ladder)} shapes; {n_long} longer than {long_over} "
        f"events, {sum(len(r['history']) for r in trunk_rows)} events in "
        f"all); the worst row has {trunk['worst_row_tokens']} events")
    say(f"check trunk h_last_rel_err = {trunk['h_last_rel_err']:.6g} "
        f"(limit {g['h_last_tolerance']:g})")
    say(f"check trunk route_violation = {trunk['route_violation']:.6g} "
        f"(limit {g['route_tolerance']:g}); reported, not judged: "
        f"{trunk['flipped_decisions']} of {trunk['decisions']} routing "
        f"decisions differ from the reference's own, in "
        f"{trunk['rows_with_a_flip']} rows, at the last position in "
        f"{trunk['rows_with_a_flip_at_the_last_position']}")
    trunk_ok = not trunk_problems(trunk, g)
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
