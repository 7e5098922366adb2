"""Fingerprints of two packed sequence programs' jaxprs, to pin "this rung's
program is the parent's, jaxpr for jaxpr" without keeping the parent's
modules here line for line: ``sha256(str(make_jaxpr(forward_flat)))`` at the
family's test configuration (``test_<family>.CFG``) on shapes alone.

``PARENT`` holds the readings of PR 48's tree, RE-READ for its deliberate
change: both families' layers now call their equal residual branches as ONE
jitted function a rung (``latent_moe.SharedBranches``), so each text holds
the branches' bodies once (``let _attention = {...}``) and a ``pjit`` a
call where it held every layer's equations in line.  The equations
themselves are those of commit c907cf6 (PR 41), whose readings stood here
until then — ab6c7c88821039e3, bba1fcaacd37cdea, 58596a14e43c0616 for
``window_moe`` and 965798f795dfda16, 272381f2eb07c00a, c321c74534306657,
8d422c2fcb1ed55a for ``latent_moe`` — and what pins THAT is the bit-for-bit
comparison with the unshared composition in each family's test file
(``shared_branch_cases.check_bits``).  The rows: the window family's rungs
below 2,048 tokens (from there on it runs in token tiles, PR 42), and the
family whose helpers (``_mm``, ``_swiglu``, ``rms_norm``) it shares and
must not move.  To read a tree's own: ``JAX_PLATFORMS=cpu python
tests/fingerprints.py`` from its root.  A deliberate change to a family's
low-rung program re-reads its rows and says so in its PR; the text holds no
address, so the reading repeats from process to process."""

import hashlib
import importlib

import jax
import jax.numpy as jnp

K = 10
RUNGS = {"window_moe": (256, 512, 1024),  # runs in tiles from 2,048 on
         "latent_moe": (256, 512, 1024, 2048)}
PARENT = {
    "window_moe.256": "0c2a0c7e66be638a", "window_moe.512": "514cf5939110efb3",
    "window_moe.1024": "6c4b55947fd511b7",
    "latent_moe.256": "9739bd424798035d", "latent_moe.512": "cdf023500eef0cfe",
    "latent_moe.1024": "77ed83275456bebb",
    "latent_moe.2048": "baf09b986e2380c9",
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
