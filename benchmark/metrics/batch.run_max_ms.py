"""The longest single run of the window, ms: `run_ms_max` is a maximum since
the server started, so it is read only when the run that set it
(`run_ms_max_seq`) came after the dispatches counted before the window."""


def read(ctx):
    after, before = ctx["counters_after"], ctx["counters_before"]
    seq = after.get("batcher.run_ms_max_seq")
    if seq is None or seq <= before.get("batcher.batches", 0):
        return None
    return after["batcher.run_ms_max"]
