"""Median of `meta.handback_ms` over the answered traced requests that
carry it (the queued ones): the batcher set the request's event -> its
handler thread runs again, ms.  On a request it lies in `other`."""
from pio_bench import hostjoin
from pio_bench.readers import pct


def read(ctx):
    return pct(list(hostjoin.meta_values(ctx, "handback_ms").values()), 50)
