"""Device mesh + sharding: the compute fabric replacing SparkContext.

Where every reference workflow entry point builds a ``SparkContext``
(``core/.../workflow/WorkflowContext.scala``) and distributes work as RDD
partitions over executors, the TPU-native equivalent is a
:class:`jax.sharding.Mesh` over the chips of a slice (or several slices), with
XLA collectives over ICI/DCN doing what Spark shuffle did (SURVEY.md §2.7).

:class:`MeshContext` is the ``sc`` of this framework: it is handed to every
DataSource/Preparator/Algorithm and carries the mesh plus placement helpers.
Axis conventions:

* ``data``  — batch/entity dimension (users, queries, events): data parallelism
* ``model`` — feature/factor dimension: tensor-style model parallelism

Multi-host note: on a pod slice each host runs this same program
(``jax.distributed``-initialized); ``make_mesh`` uses all global devices so
shardings lay collectives onto ICI first (mesh axes ordered devices-major).
"""

from __future__ import annotations

import dataclasses
import logging
import math
import os
from typing import Any, Mapping, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)

DATA_AXIS = "data"
MODEL_AXIS = "model"
# pod-scale serving: the cross-host dimension of a 2-D (host, data) mesh.
# jax.devices() enumerates process-major, so host-axis rows coincide with
# process boundaries and a collective over HOST_AXIS is genuinely DCN
# traffic on a multi-process pod (see MeshContext.pod_submesh).
HOST_AXIS = "host"

# Every shard_map / pcast user in the package imports these two names from
# here, so the sharding vocabulary has one home.
shard_map = jax.shard_map


def pcast_varying(x, axis_name):
    """Mark ``x`` varying over ``axis_name`` for shard_map's vma checker."""
    return jax.lax.pcast(x, axis_name, to="varying")


# -- persistent compile cache -----------------------------------------------
# Deploy AOT-compiles every bucket rung, each fleet child does it again, and
# a chip run starts cold; a persistent cache turns all but the first of
# those into disk reads.  The directory is part of the cache key, so it must
# not move: one fixed, git-ignored path inside the checkout — never a
# temporary name, a pid or a timestamp.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_compile_cache",
)


def configure_compile_cache() -> Optional[str]:
    """Place JAX's persistent compilation cache; returns the directory this
    code chose, or None when the operator placed it.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
    this function names no directory at all.  Unset: the cache goes to
    :data:`COMPILE_CACHE_DIR`.  Idempotent; called by ``cli.main`` and
    :meth:`MeshContext.create`, i.e. before anything compiles, so fleet
    children, ``pio train`` and ``chip_smoke.py`` share one cache.
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def pad_to_multiple(n: int, m: int) -> int:
    """Smallest multiple of m that is >= max(n, 1) — static-shape padding."""
    return max(1, math.ceil(max(n, 1) / m)) * m


def make_mesh(
    axes: Optional[Mapping[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh. Default: 1-D ``data`` axis over all visible devices.

    ``axes={"data": -1, "model": 2}`` lets one axis be inferred (-1) from the
    device count, mirroring how Spark infers partition counts.
    """
    devs = list(devices if devices is not None else jax.devices())
    n = len(devs)
    if axes is None:
        axes = {DATA_AXIS: n}
    axes = dict(axes)
    known = 1
    infer_key = None
    for k, v in axes.items():
        if v == -1:
            if infer_key is not None:
                raise ValueError("only one mesh axis may be -1")
            infer_key = k
        else:
            known *= v
    if infer_key is not None:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        axes[infer_key] = n // known
    total = math.prod(axes.values())
    if total != n:
        raise ValueError(f"mesh axes {axes} need {total} devices, have {n}")
    dev_array = np.array(devs).reshape(tuple(axes.values()))
    return Mesh(dev_array, tuple(axes.keys()))


def misaligned_pod_row(
    devices: Sequence[Any], host_groups: int
) -> Optional[int]:
    """First host row whose devices span more than one process, else None.

    The pod alignment precondition (:meth:`MeshContext.pod_submesh`):
    folding ``devices`` row-major into ``host_groups`` rows, every row
    must be process-pure for the two-tier merge's on-host tier to stay
    off DCN.  ``len(devices)`` must be divisible by ``host_groups``.
    """
    per_row = len(devices) // host_groups
    for g in range(host_groups):
        row = devices[g * per_row:(g + 1) * per_row]
        if len({d.process_index for d in row}) > 1:
            return g
    return None


@dataclasses.dataclass
class MeshContext:
    """The compute context handed through the DASE pipeline (replaces ``sc``).

    Parity role: the ``sc: SparkContext`` parameter threaded through
    ``BaseDataSource.readTrainingBase`` / ``BaseAlgorithm.trainBase``
    (``core/.../core/BaseAlgorithm.scala:69``); here it carries the device
    mesh and placement helpers instead of an RDD factory.
    """

    mesh: Mesh
    conf: dict = dataclasses.field(default_factory=dict)

    @staticmethod
    def create(
        conf: Optional[dict] = None,
        axes: Optional[Mapping[str, int]] = None,
        devices: Optional[Sequence[jax.Device]] = None,
    ) -> "MeshContext":
        configure_compile_cache()
        conf = dict(conf or {})
        if axes is None and "mesh_axes" in conf:
            axes = {k: int(v) for k, v in conf["mesh_axes"].items()}
        return MeshContext(mesh=make_mesh(axes=axes, devices=devices), conf=conf)

    # -- placement helpers -------------------------------------------------
    @property
    def n_devices(self) -> int:
        return self.mesh.size

    def axis_size(self, axis: str) -> int:
        return self.mesh.shape.get(axis, 1)

    def sharding(self, *spec: Any) -> NamedSharding:
        """NamedSharding from a PartitionSpec-style tuple."""
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def submesh(self, n_devices: int, axis: str = DATA_AXIS) -> "MeshContext":
        """A context over the first ``n_devices`` devices, one ``axis``.

        Sharded serving places a ShardingPlan of S shards on an S-device
        1-D mesh; when the plan is narrower than the full mesh this carves
        the prefix (devices-major order keeps the slice ICI-contiguous).
        ``n_devices == mesh.size`` with a matching 1-D mesh returns self.
        """
        if n_devices == self.mesh.size and self.mesh.axis_names == (axis,):
            return self
        if n_devices > self.mesh.size:
            raise ValueError(
                f"submesh of {n_devices} devices from a {self.mesh.size}-"
                "device mesh"
            )
        devs = list(self.mesh.devices.flat)[:n_devices]
        return MeshContext(
            mesh=make_mesh(axes={axis: n_devices}, devices=devs),
            conf=dict(self.conf),
        )

    def pod_submesh(self, n_shards: int, host_groups: int) -> "MeshContext":
        """A 2-D ``(host, data)`` context over the first ``n_shards`` devices.

        The pod-scale serving layout: ``host_groups`` rows of
        ``n_shards // host_groups`` devices each.  The prefix carve keeps
        ``jax.devices()``'s process-major order, so each host row is
        ICI-local exactly when every row's devices live in one process —
        the on-host tier of the two-tier leaderboard merge then never
        touches DCN, and only the tiny ``(H, B, k)`` host-axis gather
        crosses processes.  That alignment is a correctness precondition,
        not a hint: a row straddling a process boundary would silently
        turn the "on-host" tier into DCN traffic and break the contiguous
        ``group_of_shard`` ↔ process mapping the router keys ownership
        on, so a misaligned carve is rejected here (callers fall back to
        the flat single-tier merge).
        """
        if host_groups < 1 or n_shards % host_groups:
            raise ValueError(
                f"host_groups={host_groups} must divide n_shards={n_shards}"
            )
        if n_shards > self.mesh.size:
            raise ValueError(
                f"pod submesh of {n_shards} devices from a "
                f"{self.mesh.size}-device mesh"
            )
        devs = list(self.mesh.devices.flat)[:n_shards]
        bad = misaligned_pod_row(devs, host_groups)
        if bad is not None:
            per_row = n_shards // host_groups
            raise ValueError(
                f"pod host row {bad} spans processes: {host_groups} host "
                f"groups of {per_row} shards do not align with the "
                "per-process device layout, so the on-host merge tier "
                "would cross DCN and group ownership would disagree with "
                "device placement — pick host_groups so each row's "
                "devices share one process"
            )
        return MeshContext(
            mesh=make_mesh(
                axes={HOST_AXIS: host_groups,
                      DATA_AXIS: n_shards // host_groups},
                devices=devs,
            ),
            conf=dict(self.conf),
        )

    @property
    def spans_processes(self) -> bool:
        """True when some mesh device belongs to another process — plain
        ``device_put``/``device_get`` then can't touch the whole array and
        placement must go through :meth:`place` / ``addressable_data``."""
        me = jax.process_index()
        return any(d.process_index != me for d in self.mesh.devices.flat)

    def place(self, x, *spec: Any):
        """Place a host array under ``spec``, multi-process safe.

        Single-process meshes take the ordinary ``device_put``.  When the
        mesh spans processes, every process holds the SAME full host copy
        (the SPMD serving contract) and ``make_array_from_callback`` hands
        each process exactly its addressable shards of the global array.
        """
        arr = np.asarray(x)
        sharding = self.sharding(*spec)
        if not self.spans_processes:
            import jax.numpy as jnp

            return jax.device_put(jnp.asarray(arr), sharding)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    def shard_rows(self, x, axis: str = DATA_AXIS):
        """Place array with dim 0 sharded over ``axis`` (pads to divisible)."""
        import jax.numpy as jnp

        size = self.axis_size(axis)
        n = x.shape[0]
        padded = pad_to_multiple(n, size)
        if padded != n:
            pad_width = [(0, padded - n)] + [(0, 0)] * (x.ndim - 1)
            x = np.pad(np.asarray(x), pad_width)
        spec = (axis,) + (None,) * (x.ndim - 1)
        return jax.device_put(jnp.asarray(x), self.sharding(*spec))

    def replicate(self, x):
        import jax.numpy as jnp

        return jax.device_put(jnp.asarray(x), self.replicated())

    def to_host(self, tree):
        """Device pytree → host numpy pytree (for persistence)."""
        return jax.tree.map(device_get_global, tree)


def device_get_global(x) -> np.ndarray:
    """Device→host that works when the array spans multiple PROCESSES.

    Single-process: a plain ``device_get``.  Multi-host SPMD: a sharded
    array's remote shards are non-addressable, so every process
    all-gathers the global value (``process_allgather`` — rides the same
    collective fabric as training).  Every process returns the full array.
    """
    if jax.process_count() > 1 and hasattr(x, "sharding"):
        from jax.experimental import multihost_utils

        if not getattr(x.sharding, "is_fully_addressable", True):
            return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(jax.device_get(x))


def default_context(conf: Optional[dict] = None) -> MeshContext:
    """The workflow-level factory (parity: WorkflowContext SparkContext)."""
    return MeshContext.create(conf=conf)
