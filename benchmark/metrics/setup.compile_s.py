"""Wall seconds of the scorer's ladder of compiles — every rung's program
traced, lowered and compiled by the backend or read from the persistent
compile cache, one after another (`RungPrograms.compile_s`, `compile_s` of
either scorer's `stats()`) — as the counters stood when the window opened:
the set-up is over by then.  A program without the timer (the parent of
ISSUE 48) gives nothing to read."""


def read(ctx):
    return ctx["counters_before"].get("fastpath.compile_s")
