"""Residual-branch bodies the sequence programs' set-up TRACED over the
branch calls their layers made (`branch_traces` / `branch_calls` of the
family's `DispatchCounters`, summed over the ladder, as the counters stood
when the window opened), %: 100 = every layer's branch was traced and
lowered for itself; 30 = a rung's ten calls ran three bodies.  A program
without the counters (the parent of ISSUE 48, or a family whose depth is a
scan: its body is traced once anyway) gives nothing to read."""


def read(ctx):
    before = ctx["counters_before"]
    traces, calls = (before.get("fastpath.branch_traces"),
                     before.get("fastpath.branch_calls"))
    if traces is None or not calls:
        return None
    return 100.0 * traces / calls
