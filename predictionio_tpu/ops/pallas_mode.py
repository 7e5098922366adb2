"""How the Pallas kernels of this process were run: compiled or interpreted.

Every kernel entry point takes ``interpret: Optional[bool]`` and derives the
default from the platform — Mosaic on a TPU, the interpreter anywhere else,
which is how the CPU tests run the identical kernel body.  That default is
resolved HERE, once, and each resolution is counted at trace time, so a run
can state as a fact that nothing on its path was interpreted
(``chip_smoke.py`` asserts it) instead of inferring it from the platform.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax

_lock = threading.Lock()
_traces: dict[str, dict[str, int]] = {}


def resolve(kernel: str, interpret: Optional[bool]) -> bool:
    """The ``interpret=`` value for one trace of ``kernel``: the caller's
    explicit choice, else platform-derived.  Counts the outcome."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    mode = "interpreted" if interpret else "mosaic"
    with _lock:
        by_mode = _traces.setdefault(kernel, {"mosaic": 0, "interpreted": 0})
        by_mode[mode] += 1
    return interpret


def traces() -> dict[str, dict[str, int]]:
    """``{kernel: {"mosaic": n, "interpreted": m}}`` — traces, not calls: a
    jitted program traces its kernel once however often it then runs."""
    with _lock:
        return {k: dict(v) for k, v in _traces.items()}
