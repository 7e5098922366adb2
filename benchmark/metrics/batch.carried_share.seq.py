"""`batch.carried_share` in the sequence family's cells, which report no
`serve.p95_ms` (PERF.md section 2): the same reading, declared as moving
`serve.p50_ms`."""
from pio_bench.readers import load_reader

read = load_reader("batch.carried_share")
