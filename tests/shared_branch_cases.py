"""What a packed family whose depth is a Python loop must keep of
``latent_moe.SharedBranches``, stated once; each family's test file calls
:func:`check` with its own module, configuration and counts.

The plain reference of a program is the same program with every shared
function replaced by the body it wraps (:func:`unshared`): the trunk's loop
then composes the equations layer by layer, each branch and each kernel
traced and lowered for itself, as the loop did before there was sharing."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

ROWS, K = 8, 10


def unshared(monkeypatch, family):
    """``family``'s shared functions replaced by their bodies."""
    shared = {name: fn.__wrapped__ for name, fn in vars(family).items()
              if callable(fn) and hasattr(fn, "__wrapped__")}
    assert shared
    for name, body in shared.items():
        monkeypatch.setattr(family, name, body)


def _program(family, cfg, t, **kw):
    return jax.jit(lambda P, flat: family.forward_flat(
        cfg, P, flat, t, K, score_backend="reference", **kw))


def _shapes(family, cfg, t):
    return ({n: jax.ShapeDtypeStruct(s, d)
             for n, (s, d) in family.param_shapes(cfg).items()},
            jax.ShapeDtypeStruct((4 * t + ROWS,), jnp.int32))


def _fresh(cfg):
    """``cfg`` under an eps no other test uses: a static argument of every
    shared function, so nothing traced before in this process is found."""
    return dataclasses.replace(cfg, rms_norm_eps=cfg.rms_norm_eps * 1.03125)


def check(what, monkeypatch, family, cfg, P, t, **kw):
    """One case of a family's parametrised test: ``counts``, ``bits`` or
    ``lowered`` at the ``t``-token program."""
    if what == "bits":
        return _bits(monkeypatch, family, cfg, P, t, **kw)
    return {"counts": _counts, "lowered": _lowered}[what](
        monkeypatch, family, cfg, t, **kw)


def _counts(monkeypatch, family, cfg, t, distinct, calls, **kw):
    """Tracing the program runs ``distinct`` bodies for ``calls`` calls by
    the layers; a second trace of it runs none; the family's
    ``DispatchCounters`` report both."""
    cfg, shared = _fresh(cfg), family._shared
    for want in (distinct, 0):
        was = (shared.traces, shared.calls)
        _program(family, cfg, t, **kw).trace(*_shapes(family, cfg, t))
        assert (shared.traces - was[0], shared.calls - was[1]) == (want, calls)
    st = family.DispatchCounters(cfg).stats()
    assert (st["branch_traces"], st["branch_calls"]) == (
        shared.traces, shared.calls)


def _bits(monkeypatch, family, cfg, P, t, lens=(5, 20, 17, 3, 12), **kw):
    """Every output of the program equals, to the bit, that of the same
    equations composed without sharing."""
    r = np.random.default_rng(t)
    hists = [r.integers(0, cfg.vocab_size, n).astype(np.int32) for n in lens]
    flat = jnp.asarray(family.flatten(family.pack(hists, t, ROWS)))
    got = _program(family, cfg, t, **kw)(P, flat)
    with monkeypatch.context() as m:
        unshared(m, family)
        was = family._shared.stats()
        want = _program(family, cfg, t, **kw)(P, flat)
        assert family._shared.stats() == was  # nothing shared ran
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(
            np.asarray(got[name]), np.asarray(want[name]), err_msg=name)


def _lowered(monkeypatch, family, cfg, t, kernels, unshared_kernels):
    """Lowered for the TPU (no chip is needed to lower), the program holds
    one ``tpu_custom_call`` a kernel and distinct signature: ``kernels`` of
    them, where the layers' own come to ``unshared_kernels``."""
    def custom_calls():
        text = _program(family, _fresh(cfg), t, interpret=False).trace(
            *_shapes(family, cfg, t)).lower(
                lowering_platforms=("tpu",)).as_text()
        return text.count("stablehlo.custom_call @tpu_custom_call")

    assert custom_calls() == kernels
    with monkeypatch.context() as m:
        unshared(m, family)
        assert custom_calls() == unshared_kernels
