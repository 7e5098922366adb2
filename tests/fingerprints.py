"""Fingerprints of two packed sequence programs' jaxprs, to pin "this rung's
program is the parent's, jaxpr for jaxpr" without keeping the parent's
modules here line for line: ``sha256(str(make_jaxpr(forward_flat)))`` at the
family's test configuration (``test_<family>.CFG``) on shapes alone.

``PARENT`` holds the readings of commit c907cf6 (PR 41), the parent of the
PR that made the window family's dense sublayers run in token tiles from
2,048 tokens on (PR 42): that family's lower rungs, and the family whose
helpers (``_mm``, ``_swiglu``, ``rms_norm``) it shares and must not move.  To read a
tree's own: ``JAX_PLATFORMS=cpu python tests/fingerprints.py`` from its
root.  A deliberate change to a family's low-rung program re-reads its rows
and says so in its PR; the text holds no address, so the reading repeats
from process to process."""

import hashlib
import importlib

import jax
import jax.numpy as jnp

K = 10
RUNGS = {"window_moe": (256, 512, 1024),  # runs in tiles from 2,048 on
         "latent_moe": (256, 512, 1024, 2048)}
PARENT = {
    "window_moe.256": "ab6c7c88821039e3", "window_moe.512": "bba1fcaacd37cdea",
    "window_moe.1024": "58596a14e43c0616",
    "latent_moe.256": "965798f795dfda16", "latent_moe.512": "272381f2eb07c00a",
    "latent_moe.1024": "c321c74534306657",
    "latent_moe.2048": "8d422c2fcb1ed55a",
}


def program_jaxpr(family: str, cfg, t: int, **kw) -> str:
    """The text of the ``t``-token serving program's jaxpr."""
    fam = importlib.import_module("predictionio_tpu.models." + family)
    P = {name: jax.ShapeDtypeStruct(shape, dtype)
         for name, (shape, dtype) in fam.param_shapes(cfg).items()}
    flat = jax.ShapeDtypeStruct((4 * t + 8,), jnp.int32)
    return str(jax.make_jaxpr(lambda P, flat: fam.forward_flat(
        cfg, P, flat, t, K, score_backend="reference", **kw))(P, flat))


def fingerprint(family: str, cfg, t: int) -> str:
    return hashlib.sha256(
        program_jaxpr(family, cfg, t).encode()).hexdigest()[:16]


if __name__ == "__main__":
    import os
    import sys

    sys.path[:0] = [os.getcwd(), os.path.join(os.getcwd(), "tests")]
    for family, rungs in RUNGS.items():
        cfg = importlib.import_module("test_" + family).CFG
        for t in rungs:
            print(f'"{family}.{t}": "{fingerprint(family, cfg, t)}",')
