"""What every packed family that runs its dense sublayers in token tiles
(``ops/token_tiles``) must keep, stated once; each family's test file calls
these with its own module, configuration and weights.  Tile 16 on a rung of
128 tokens: the program's own tile is 512 (at which a rung of 128 runs
whole), and ``forward_flat`` takes a test's as a plain argument."""

import jax
import jax.numpy as jnp
import numpy as np

from predictionio_tpu.ops import token_tiles as tt

T, TILE, ROWS, K = 128, 16, 8, 10
# one long row (its last tile partly padding, full, and one event long), rows
# packed with the last tile partly padding, and a rung filled to its end
LENS = [(100,), (113,), (128,), (37, 1, 50), (5, 20, 40), (64, 64)]
# a tile's product sums in another order than the whole rung's (XLA:CPU
# groups by width): f32 rounding, read 2e-6 to 1e-5 at these sizes
TOL = 5e-5


def programs(family, cfg):
    """The 128-token program run whole and in tiles of 16."""
    def build(**kw):
        return jax.jit(lambda P, flat: family.forward_flat(
            cfg, P, flat, T, K, score_backend="reference", **kw))
    return build(), build(dense_tile=TILE)


def histories(cfg, seed, lens):
    r = np.random.default_rng(seed)
    return [r.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]


def check_real_rows(family, cfg, P, lens, progs, exact=()):
    """In tiles, every real row's ``values``, ``indices``, ``h_last`` (and
    ``x_last`` where the family returns it) are the whole rung's; the
    outputs named in ``exact`` are equal to the bit."""
    hists = histories(cfg, 21, lens)
    flat = jnp.asarray(family.flatten(family.pack(hists, T, ROWS)))
    whole, tiled = (run(P, flat) for run in progs)
    n = len(hists)
    for name in ("values", "h_last", "x_last"):
        if name in whole:
            want = np.asarray(whole[name][:n], np.float64)
            np.testing.assert_allclose(
                np.asarray(tiled[name][:n], np.float64), want,
                atol=TOL * np.abs(want).max(), err_msg=name)
    np.testing.assert_array_equal(tiled["indices"][:n], whole["indices"][:n])
    for name in exact:
        np.testing.assert_array_equal(tiled[name], whole[name], err_msg=name)
    return whole, tiled


def check_trip_count(x, n_tok):
    """The residual stream a trunk returns: computed (no row is zero) on the
    ``dense_tiles`` tiles that hold a real token, zeros beyond — the trip
    count of the last segment's loop, read off its result."""
    x = np.asarray(x)
    ran = TILE * tt.dense_tiles(T, n_tok, TILE)
    assert np.isfinite(x).all()
    assert np.abs(x[:ran]).sum(axis=1).min() > 0 and not x[ran:].any()
