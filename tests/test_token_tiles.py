"""``ops/token_tiles``: a position-wise segment run only over the token
tiles that hold a real token.  On the CPU at a tile of 8 or 16 tokens (the
program's is 512; the helper takes it as a plain argument)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from predictionio_tpu.ops import token_tiles as tt

T, TILE, D = 64, 8, 12


def _inputs(seed=0):
    r = np.random.default_rng(seed)
    # no row is zero, so a row of zeros in a result is a row never computed
    x = jnp.asarray(r.normal(size=(T, D)) + 3.0, jnp.float32)
    heads = jnp.asarray(r.normal(size=(3, T, 4)) + 3.0, jnp.float32)
    pos = jnp.arange(T, dtype=jnp.int32)
    w = jnp.asarray(r.normal(size=(D, 5)), jnp.float32)
    return x, heads, pos, w


def _segment(w):
    """Position-wise, with a weight it closes over, the token axis of one
    input and one output lying second, and a pytree out."""
    def fn(x, heads, pos):
        y = jnp.dot(x, w) + pos[:, None].astype(jnp.float32)
        return {"y": y, "h": (heads * 2.0 + x[None, :, :4], x.sum(axis=1))}
    return fn


AXES = dict(in_axes=(0, 1, 0), out_axes=(1, 0, 0))  # leaves: h[0], h[1], y


def _check(got, want, n_real):
    """Equal to ``fn`` on the real prefix, zero past the last real tile, and
    computed (not zero) up to there: the loop ran ``dense_tiles`` tiles."""
    ran = TILE * tt.dense_tiles(T, n_real, TILE)
    for (g, w, ax) in ((got["y"], want["y"], 0), (got["h"][0], want["h"][0], 1),
                       (got["h"][1], want["h"][1], 0)):
        g, w = np.moveaxis(np.asarray(g), ax, 0), np.moveaxis(
            np.asarray(w), ax, 0)
        np.testing.assert_allclose(g[:n_real], w[:n_real], rtol=1e-6)
        np.testing.assert_allclose(g[:ran], w[:ran], rtol=1e-6)
        assert not g[ran:].any()
        assert np.abs(g[:ran].reshape(ran, -1)).sum(axis=1).min() > 0


@pytest.mark.parametrize("n_real", [1, TILE - 1, TILE, TILE + 1, T - 1, T])
def test_under_jit_equals_fn_on_the_real_prefix_and_zero_beyond(n_real):
    x, heads, pos, w = _inputs()
    fn = _segment(w)
    run = jax.jit(lambda n, *xs: tt.over_real_tiles(
        fn, n, *xs, tile=TILE, **AXES))
    _check(run(jnp.int32(n_real), x, heads, pos), fn(x, heads, pos), n_real)


@pytest.mark.parametrize("n_real", [1, TILE - 1, TILE, TILE + 1, T - 1, T])
def test_inside_a_scan_over_depth_with_the_layers_own_weight(n_real):
    """Inside ``lax.scan``'s body, closing over that layer's slice of a
    stacked weight and over ``n_real`` from outside the scan: it computes
    the same there (what it COSTS there is another matter: the scan hands
    the loop its slice as a copy, ``PERF.md`` section 6, PR 42)."""
    x, heads, pos, _ = _inputs(1)
    ws = jnp.asarray(np.random.default_rng(2).normal(size=(3, D, 5)),
                     jnp.float32)

    @jax.jit
    def run(n, x, heads, pos, ws):
        def layer(carry, w):
            out = tt.over_real_tiles(_segment(w), n, x, heads, pos,
                                     tile=TILE, **AXES)
            return carry + out["y"].sum(), out
        return jax.lax.scan(layer, 0.0, ws)[1]

    got = run(jnp.int32(n_real), x, heads, pos, ws)
    for layer in range(3):
        _check(jax.tree.map(lambda a: a[layer], got),
               _segment(ws[layer])(x, heads, pos), n_real)


@pytest.mark.parametrize("t", [TILE, 2 * TILE, 3 * TILE])
def test_any_whole_number_of_tiles_runs_under_the_loop(t):
    """Which rungs run in tiles is the caller's to say (``window_moe.
    runs_in_tiles``): the helper loops over whatever it is handed, one tile
    too."""
    x, heads, pos, w = _inputs()
    x, heads, pos = x[:t], heads[:, :t], pos[:t]
    fn = _segment(w)
    text = str(jax.make_jaxpr(lambda n, *xs: tt.over_real_tiles(
        fn, n, *xs, tile=TILE, **AXES))(jnp.int32(3), x, heads, pos))
    assert "while" in text
    got = tt.over_real_tiles(fn, jnp.int32(3), x, heads, pos, tile=TILE,
                             **AXES)
    want = fn(x, heads, pos)
    np.testing.assert_allclose(got["y"][:3], want["y"][:3], rtol=1e-6)
    assert not np.asarray(got["y"][TILE:]).any()


@pytest.mark.parametrize("t", [TILE - 1, 2 * TILE + 3])
def test_tiles_that_do_not_divide_the_axis_are_refused(t):
    x, heads, pos, w = _inputs()
    with pytest.raises(ValueError, match="do not divide"):
        tt.over_real_tiles(_segment(w), jnp.int32(3), x[:t], heads[:, :t],
                           pos[:t], tile=TILE, **AXES)
    with pytest.raises(ValueError, match="do not divide"):
        tt.dense_tiles(t, 3, TILE)


@pytest.mark.parametrize("t_pad, n_real, tiles", [
    (512, 1, 1), (512, 512, 1), (1024, 512, 1), (1024, 513, 2),
    (2048, 1, 1), (2048, 1024, 2), (2048, 1025, 3), (2048, 2048, 4),
    (8192, 4097, 9), (16384, 8193, 17), (16384, 16384, 32),
])
def test_dense_tiles_is_the_trip_count_at_the_programs_tile(
        t_pad, n_real, tiles):
    assert tt.DENSE_TILE == 512
    assert tt.dense_tiles(t_pad, n_real) == tiles
    assert int(jax.jit(lambda n: tt.dense_tiles(t_pad, n) + 0)(
        jnp.int32(n_real))) == tiles


def test_the_loop_holds_the_segments_intermediates_at_a_tiles_size():
    """What the loop is for: inside it ``fn``'s intermediates are (tile, F),
    and no (T, F) array of the segment's inner width exists anywhere."""
    x = jnp.ones((T, D))
    w1, w2 = jnp.ones((D, 40)), jnp.ones((40, D))
    fn = lambda x: jnp.dot(jax.nn.silu(jnp.dot(x, w1)), w2)
    text = str(jax.make_jaxpr(lambda n, x: tt.over_real_tiles(
        fn, n, x, tile=TILE))(jnp.int32(9), x))
    assert f"f32[{TILE},40]" in text and f"f32[{T},40]" not in text
    assert "while" in text


def test_real_tiles_binds_a_dispatch_and_axes_must_match():
    x, heads, pos, w = _inputs()
    fn = _segment(w)
    got = jax.jit(lambda n, *xs: tt.real_tiles(n, TILE)(fn, *xs, **AXES))(
        jnp.int32(20), x, heads, pos)
    _check(got, fn(x, heads, pos), 20)
    with pytest.raises(ValueError, match="axes"):
        tt.over_real_tiles(fn, jnp.int32(3), x, heads, pos, tile=TILE,
                           in_axes=(0, 1))
