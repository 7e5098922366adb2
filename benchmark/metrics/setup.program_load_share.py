"""Rungs the scorer's set-up took from the program store over the rungs it
made ready (`programs_loaded` / `compile_count` of either scorer's
`stats()`, as the counters stood when the window opened), %: 100 = every
rung's executable was loaded, none traced or lowered; 0 = every rung was
compiled (the run that built the store, or a process in which the store does
not engage).  A program without the counter (the parent of ISSUE 49) gives
nothing to read."""


def read(ctx):
    before = ctx["counters_before"]
    loaded, ready = (before.get("fastpath.programs_loaded"),
                     before.get("fastpath.compile_count"))
    if loaded is None or not ready:
        return None
    return 100.0 * loaded / ready
