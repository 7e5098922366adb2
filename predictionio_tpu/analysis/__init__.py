"""``pio analyze``: whole-repo static analysis for TPU-serving hazards.

The reference platform gated every build on scalastyle; this package is
the TPU-native equivalent — one rule engine, one suppression mechanism
(``# pio: ignore[rule-id]``), one baseline file — aimed at the failure
modes that actually bite a JAX serving stack:

* ``hotpath``  — host-sync forcers, traced branching/loops, jit or
  ``block_until_ready`` in the request path;
* ``races``    — unguarded shared state reachable from ≥2 thread entry
  points (batcher worker, flush/WAL threads, HTTP handlers, signal
  handlers);
* ``knobs``    — the ``PIO_*`` registry vs ``docs/operations.md``:
  undocumented, dead, and default-drifted knobs;
* ``metrics``  — the ``pio_*`` families vs the ``docs/observability.md``
  catalog: undocumented/dead/type-mismatched series, label cardinality;
* ``blocking`` — sleeps/fsyncs/JSON/network calls in the batcher
  dispatch loop and fastpath scoring;
* ``hygiene``  — the original lint gates (unused imports, parse health,
  ad-hoc counters/caches) migrated into the framework.

The interprocedural engine (:mod:`callgraph`: whole-repo call graph +
per-function lock summaries over the same ``RepoIndex`` parse cache)
powers three more:

* ``lockorder``  — global lock-order graph; cycles across call chains
  are reported as potential AB/BA deadlocks with witness chains;
* ``deadline``   — the ``X-Request-Deadline`` contract verified along
  call-graph reachability from request entry points;
* ``collective`` — shard_map/mesh axis consistency, pallas_call
  index_map arity, and host-sync taint extended one call deep.

Entry points: ``pio analyze`` in the CLI, :func:`run` for tests.  Findings
at severity ``error`` gate tier-1 via ``tests/test_analysis.py``.
"""

from predictionio_tpu.analysis.core import (
    ANALYZER_RULES,
    ANALYZERS,
    BASELINE_NAME,
    Finding,
    Module,
    RepoIndex,
    Report,
    RULES,
    load_baseline,
    run,
    write_baseline,
)
from predictionio_tpu.analysis.core import (
    prune_baseline,
    stale_baseline_keys,
    to_sarif,
)
from predictionio_tpu.analysis import callgraph
from predictionio_tpu.analysis import (  # registers the analyzers
    blocking,
    collective,
    deadline,
    hotpath,
    hygiene,
    knobs,
    lockorder,
    metrics_contract,
    races,
)

__all__ = [
    "ANALYZER_RULES",
    "ANALYZERS",
    "BASELINE_NAME",
    "Finding",
    "Module",
    "RepoIndex",
    "Report",
    "RULES",
    "blocking",
    "callgraph",
    "collective",
    "deadline",
    "hotpath",
    "hygiene",
    "knobs",
    "load_baseline",
    "lockorder",
    "metrics_contract",
    "prune_baseline",
    "races",
    "run",
    "stale_baseline_keys",
    "to_sarif",
    "write_baseline",
]
