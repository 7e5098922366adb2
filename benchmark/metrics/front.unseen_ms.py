"""Median over answered requests of the client's (done − sent) less the
server's `wallMs` less `meta.parse_ms`, joined on `X-Request-Id: bench-<i>`,
ms: the sockets, the handler thread's wake-up and the generator's own
reading, which no server span covers."""
from pio_bench import hostjoin
from pio_bench.readers import pct


def read(ctx):
    return pct(hostjoin.unseen_ms(ctx), 50)
