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


# -- the hybrid sequence family's kernels at olmo-hybrid-7b-l16's widths ------

GDN_HEADS, GDN_DK, GDN_DV, ATTN_D = 30, 96, 192, 128
HYBRID_HEAD_ITEMS, HYBRID_RANK = 100_352, 3_840


def _gdn_shapes(one_chip, t):
    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    bf, f32 = jnp.bfloat16, jnp.float32
    return shape, [shape((GDN_HEADS, t, GDN_DK), bf),
                   shape((GDN_HEADS, t, GDN_DK), bf),
                   shape((GDN_HEADS, t, GDN_DV), bf),
                   shape((GDN_HEADS, t), f32), shape((GDN_HEADS, t), f32),
                   shape((t,), jnp.int32)]


@pytest.mark.parametrize("t", (256, 8192))
def test_gated_delta_scan_compiles_at_the_ladders_ends(one_chip, t):
    # what interpret mode cannot refuse: a (1, 1) value spread over both
    # axes, an f32 product at HIGHEST inside the kernel, a (64, 8) block
    from predictionio_tpu.ops import gated_delta

    # and of the split scan: two heads side by side on the lanes (a concat
    # and a slice at lane 64), block indices clamped by a prefetched count
    shape, args = _gdn_shapes(one_chip, t)
    compiled = jax.jit(lambda *a, n: gated_delta.gdn_scan(
        *a, n_real=n, interpret=False)).lower(
            *args, n=shape((), jnp.int32)).compile()
    text = compiled.as_text()
    # the pre-pass and the step, each under a name that holds "gdn_scan"
    assert text.count("tpu_custom_call") >= 2
    assert "pio.gdn_scan_prep" in text and "%pio.gdn_scan." in text
    assert compiled.out_info.shape == (GDN_HEADS, t, GDN_DV)


def test_gated_delta_scan_compiles_with_the_state_carry(one_chip):
    from predictionio_tpu.ops import gated_delta

    shape, args = _gdn_shapes(one_chip, 2048)
    rows = 64
    args += [shape((rows, GDN_HEADS, GDN_DK, GDN_DV), jnp.float32),
             shape((rows,), jnp.int32), shape((rows,), jnp.int32)]
    compiled = jax.jit(lambda q, k, v, g, b, s, h0, rs, rl:
                       gated_delta.gdn_scan(
                           q, k, v, g, b, s, h0=h0, row_start=rs, row_last=rl,
                           output_final_state=True, interpret=False)
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info[1].shape == (rows, GDN_HEADS, GDN_DK, GDN_DV)


def test_packed_attention_compiles_at_the_top_rung(one_chip):
    from predictionio_tpu.ops.flash_attention import packed_causal_attention

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    x = shape((GDN_HEADS, 8192, ATTN_D), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, s: packed_causal_attention(
        q, k, v, s, interpret=False)).lower(
            x, x, x, shape((8192,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_hybrid_head_compiles_at_its_tile(one_chip):
    # rank 3,840 over 100,352 rows: the widest head the score kernel sweeps
    compiled = _compile(one_chip, jnp.bfloat16, 64, 100, with_stats=True,
                        n_items=score_kernel.pad_block_items(
                            HYBRID_HEAD_ITEMS), rank=HYBRID_RANK,
                        mask_row=True)
    assert "tpu_custom_call" in compiled.as_text()


# -- the parallel state-space / attention family's kernels at
# falcon-h1-34b-l6's widths ---------------------------------------------------

SSD_HEADS, SSD_GROUPS, SSD_P, SSD_N = 32, 2, 128, 256
SSD_HEAD_ITEMS, SSD_RANK = 261_120, 5_120


def _ssd_shapes(one_chip, t):
    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    bf, f32 = jnp.bfloat16, jnp.float32
    return shape, [shape((t, SSD_HEADS * SSD_P), bf),
                   shape((t, SSD_GROUPS * SSD_N), bf),
                   shape((t, SSD_GROUPS * SSD_N), bf),
                   shape((t, SSD_HEADS), f32), shape((SSD_HEADS,), f32),
                   shape((SSD_HEADS,), f32), shape((t,), jnp.int32)]


@pytest.mark.parametrize("t", (256, 8192))
def test_state_space_scan_compiles_at_the_ladders_ends(one_chip, t):
    # what interpret mode cannot refuse: sixteen heads side by side on the
    # lanes (slices and a concat at multiples of 128), a product contracted
    # over both operands' FIRST axis, a 2 MB f32 state in VMEM, block indices
    # clamped by a prefetched count
    from predictionio_tpu.ops import ssd_scan

    shape, args = _ssd_shapes(one_chip, t)
    compiled = jax.jit(lambda *a, n: ssd_scan.ssd_scan(
        *a, n_groups=SSD_GROUPS, n_real=n, interpret=False)).lower(
            *args, n=shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1 and "%pio.ssd_scan." in text
    assert compiled.out_info.shape == (t, SSD_HEADS * SSD_P)


def test_state_space_scan_compiles_with_the_state_carry(one_chip):
    from predictionio_tpu.ops import ssd_scan

    shape, args = _ssd_shapes(one_chip, 2048)
    rows = 16
    args += [shape((rows, SSD_HEADS, SSD_P, SSD_N), jnp.float32),
             shape((rows,), jnp.int32), shape((rows,), jnp.int32)]
    compiled = jax.jit(lambda x, b, c, dt, a, d, s, h0, rs, rl:
                       ssd_scan.ssd_scan(
                           x, b, c, dt, a, d, s, n_groups=SSD_GROUPS, h0=h0,
                           row_start=rs, row_last=rl,
                           output_final_state=True, interpret=False)
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info[1].shape == (rows, SSD_HEADS, SSD_P, SSD_N)


def test_grouped_attention_compiles_at_twenty_over_four_heads(one_chip):
    from predictionio_tpu.ops.flash_attention import packed_grouped_attention

    def shape(s, dt):
        return jax.ShapeDtypeStruct(s, dt, sharding=one_chip)

    q = shape((20, 8192, 128), jnp.bfloat16)
    kv = shape((4, 8192, 128), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, s: packed_grouped_attention(
        q, k, v, s, interpret=False)).lower(
            q, kv, kv, shape((8192,), jnp.int32)).compile()
    assert "%pio.global_attention." in compiled.as_text()


def test_widest_head_compiles_at_the_tile_the_rule_picks(one_chip):
    # rank 5,120 over 261,120 rows, 2.67 GB a sweep: `tile_geometry` is a
    # function of shapes and takes it unedited
    assert score_kernel.pad_block_items(SSD_HEAD_ITEMS) == SSD_HEAD_ITEMS
    rows, block = score_kernel.tile_geometry(
        64, SSD_RANK, jnp.bfloat16, SSD_HEAD_ITEMS)
    assert rows == 64 and SSD_HEAD_ITEMS % block == 0
    compiled = _compile(one_chip, jnp.bfloat16, 64, 100, with_stats=True,
                        n_items=SSD_HEAD_ITEMS, rank=SSD_RANK, mask_row=True)
    assert "tpu_custom_call" in compiled.as_text()
