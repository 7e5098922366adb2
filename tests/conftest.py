"""Test bootstrap: force a virtual 8-device CPU platform BEFORE jax imports.

This is the TPU-build analogue of the reference's Spark ``local[N]`` masters
(SURVEY.md §4): multi-chip sharding logic runs over a
``jax.sharding.Mesh`` of 8 virtual CPU devices, real TPU not required.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the CPU whatever is preset
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests compile thousands of small programs; keep them — and every CLI
# subprocess they spawn, hence the environment variable — out of the
# persistent compile cache that MeshContext.create() places in the checkout.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture()
def mem_env(tmp_path):
    """Fake PIO_STORAGE_* env pointing all repositories at the memory driver.

    Parity role: StorageMockContext.scala:21-58 (mocked env + in-memory H2).
    """
    import uuid

    from predictionio_tpu.data.storage import memory

    name = "T" + uuid.uuid4().hex[:8].upper()
    env = {
        f"PIO_STORAGE_SOURCES_{name}_TYPE": "memory",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": name,
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": name,
        "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": name,
    }
    yield env
    memory.reset_store(name)


@pytest.fixture()
def storage(mem_env):
    from predictionio_tpu.data.storage.registry import Storage

    return Storage(env=mem_env)


# XLA:CPU rounds one rank contraction differently at different matrix
# widths (FMA grouping follows the width), so a full-width scan and a scan
# over narrower item blocks agree only to a few f32 ulps here.  Measured
# over the shapes of the tests that use this (tests/test_ivf.py
# TestBitIdentity, tests/test_pod_serving.py): f32 <= 2, bf16 <= 1, int8
# <= 3 ulps, every index equal (PR 29; PR 21 saw the same <= 3).  The bound
# is that measurement plus one ulp.  The MXU contraction does not depend on
# the width, so on a TPU the same comparison is exact.
CPU_WIDTH_MAX_ULP = 4


def _assert_same_topk(idx_a, val_a, idx_b, val_b, what=""):
    idx_a, idx_b = np.asarray(idx_a), np.asarray(idx_b)
    val_a = np.asarray(val_a, np.float32)
    val_b = np.asarray(val_b, np.float32)
    if jax.default_backend() == "tpu":
        assert np.array_equal(idx_a, idx_b), f"indices differ {what}"
        assert np.array_equal(val_a, val_b), f"values differ {what}"
        return
    np.testing.assert_array_max_ulp(val_a, val_b, maxulp=CPU_WIDTH_MAX_ULP)
    # two winners may trade places only where their scores are that close
    for r, j in zip(*np.nonzero(idx_a != idx_b)):
        near = [
            val_a[r, n] for n in (j - 1, j + 1) if 0 <= n < val_a.shape[1]
        ]
        here = val_a[r, j]
        gap = min(
            abs(here - v) / np.spacing(max(abs(here), abs(v))) for v in near
        )
        assert gap <= CPU_WIDTH_MAX_ULP, (
            f"indices differ {what} at row {r} rank {j}: "
            f"{idx_a[r, j]} vs {idx_b[r, j]}, nearest score {gap} ulps away"
        )


@pytest.fixture()
def assert_same_topk():
    """Two top-k answers whose scans contract at DIFFERENT widths: equal on
    a TPU; on XLA:CPU values within ``CPU_WIDTH_MAX_ULP`` and indices equal
    except between scores that close.  Same-width comparisons do not use
    this: they stay ``np.array_equal``."""
    return _assert_same_topk
