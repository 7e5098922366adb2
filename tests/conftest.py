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
