"""The fused score kernel through the real Mosaic / XLA:TPU compiler, at
the benchmark's width, for a v5e that is described and not attached.

Interpret mode (every other test of the kernel) cannot see what Mosaic
refuses: the merge's ``while_loop`` with a vector→scalar condition, the
SMEM counter output, a zero-width slice at k = 1, and — since the tile is
sized from the shapes (ISSUE 30) — more VMEM than a kernel gets or a
relayout it has no lowering for at the geometry ``tile_geometry`` picks.
Nothing runs here —
a compile that passes says nothing about results or times.  The topology
is described inside a fixture, never at import (one process at a time
may load libtpu; see the on-chip-measurement guide), and this is the only
test file that loads it.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from predictionio_tpu.ops import score_kernel

N_ITEMS, RANK = 5_700_096, 128  # als-wgde-d128, padded to BLOCK_I
HEAD_ITEMS, HEAD_RANK = 129_536, 2_048  # joyai-llm-flash-l5's padded head


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, dtype, batch, k, with_stats, *, n_items=N_ITEMS,
             rank=RANK, mask_row=False):
    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    table = shape((n_items, rank), dtype)
    # the lane row a placement builds once, or a bool mask converted here
    mask = (shape((1, n_items), jnp.int32) if mask_row
            else shape((n_items,), jnp.bool_))
    args = [table, table, shape((batch,), jnp.int32), mask]
    if dtype == jnp.int8:
        scale = shape((n_items, 1), jnp.float32)

        def fn(U, V, u_idx, mask, us, vs):
            return score_kernel.fused_gather_score_topk(
                U, V, u_idx, k, mask, u_scale=us, v_scale=vs,
                interpret=False, with_stats=with_stats)

        args += [scale, scale]
    else:

        def fn(U, V, u_idx, mask):
            return score_kernel.fused_gather_score_topk(
                U, V, u_idx, k, mask, interpret=False,
                with_stats=with_stats)

    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("batch", (1, 8, 64))
@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16, jnp.int8),
                         ids=("f32", "bf16", "int8"))
def test_serving_program_compiles_with_counters(one_chip, dtype, batch):
    # the replicated fast path's program, at the tile the rule picks for
    # this rung (f32: 8 x 4,096 at rungs 1 and 8, 64 x 2,048 at rung 64)
    compiled = _compile(one_chip, dtype, batch, 100, with_stats=True,
                        mask_row=True)
    assert "tpu_custom_call" in compiled.as_text()
    assert len(compiled.out_info) == 3
    assert compiled.out_info[0].shape == (batch, 100)


def test_sequence_head_compiles_at_its_tile(one_chip):
    assert score_kernel.tile_geometry(
        64, HEAD_RANK, jnp.bfloat16, HEAD_ITEMS) == (64, 512)
    compiled = _compile(one_chip, jnp.bfloat16, 64, 100, with_stats=True,
                        n_items=HEAD_ITEMS, rank=HEAD_RANK, mask_row=True)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", (1, 2, 100))
def test_compiles_without_counters_at_any_k(one_chip, k):
    compiled = _compile(one_chip, jnp.float32, 16, k, with_stats=False)
    assert len(compiled.out_info) == 2
