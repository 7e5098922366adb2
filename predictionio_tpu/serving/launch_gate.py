"""One program queued behind the one running, and only where both fit.

The micro-batcher launches a run's program while the previous run's is
still on the device (``serving/batching.py``, launch-ahead), so a scorer's
``score_topk`` is entered by two threads and two of its compiled programs
can be enqueued at once.  XLA reserves a program's temporaries and outputs
when it is ENQUEUED, not when it starts, so the pair must fit the device's
memory together: a sequence model's top rung takes gigabytes beside a
resident model that already fills most of the chip.  Which two programs
meet is known only here, in the scorer — the rung of a packed dispatch is
decided by its tokens — so the scorer's launch passes through this gate: a
launch that would not fit beside the one in flight waits for that one's
return, exactly as it did before there was a launch-ahead, and is counted.

No setting: the sizes are the compiler's (``compiled.memory_analysis()``)
and the limit is the device's (``device.memory_stats()``).

Beside it, what else of launch-ahead only a scorer can know:
:func:`measure_lag`, how long after a program's END the host hears of it.
"""

from __future__ import annotations

import contextlib
import statistics
import threading
import time


def program_bytes(compiled) -> int:
    """What enqueueing ``compiled`` reserves on the device beyond its
    arguments: temporaries and outputs, by the compiler's own count (0
    where the backend gives none)."""
    try:
        m = compiled.memory_analysis()
    except Exception:  # pragma: no cover - backend-dependent
        return 0
    return int(getattr(m, "temp_size_in_bytes", 0) or 0) + int(
        getattr(m, "output_size_in_bytes", 0) or 0)


def measure_lag(launch, wait, reps: int = 5) -> float:
    """Seconds between a program's end on the device and its waiter's
    return on the host: the runtime's hop from the jitted call to the
    device's queue, the notice of the end, the outputs' landing, the
    thread's wake-up.  The batcher aims the NEXT program's enqueue at the
    END of the one in flight, but sees only returns; and no pair of its own
    readings gives the difference where a rung's programs differ with their
    rows.  So a scorer measures it once, at warm-up, on an idle device,
    with ONE program and input twice in a row: ``launch()`` enqueues it
    (readback requested as a dispatch does) and returns what ``wait``
    blocks on.  The first, on a free device, returns hop + program + lag
    after its enqueue; the second, queued behind it, a program after the
    first.  The median of ``reps`` (a pause may stretch either term)."""
    lags = []
    for _ in range(reps):
        first = launch()
        t_enqueued = time.perf_counter()
        second = launch()
        wait(first)
        t_first = time.perf_counter()
        wait(second)
        lags.append((t_first - t_enqueued) - (time.perf_counter() - t_first))
    return max(0.0, statistics.median(lags))


class LaunchGate:
    """Around a scorer's launch and its wait: ``with gate.flight(rung)``.

    ``need`` maps a rung to :func:`program_bytes` of its program.  A
    launch finds either nothing of this scorer in flight (the common case:
    one comparison) or one program, and then asks the device what is free
    NOW.  That reading may or may not hold the temporaries of the program
    in flight already, so they are counted again: the rule errs towards
    waiting, which costs the few milliseconds of a turnaround behind a
    program large enough to matter, one that runs for hundreds.
    """

    def __init__(self, device, need: dict):
        self._device = device
        self._need = need
        self._cv = threading.Condition()
        self._flying: list = []  # rungs launched and not yet returned
        self.held = 0  # launches that waited for the one in flight

    def _fits(self, rung) -> bool:
        stats = self._device.memory_stats()
        if not stats or "bytes_limit" not in stats:
            return True  # a backend that names no limit (the CPU)
        free = stats["bytes_limit"] - stats.get("bytes_in_use", 0)
        return self._need[rung] + sum(
            self._need[r] for r in self._flying) <= free

    @contextlib.contextmanager
    def flight(self, rung):
        with self._cv:
            if self._flying and not self._fits(rung):
                self.held += 1
                while self._flying:
                    self._cv.wait()
            self._flying.append(rung)
        try:
            yield
        finally:
            with self._cv:
                self._flying.remove(rung)
                self._cv.notify_all()
