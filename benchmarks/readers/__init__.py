"""Per-layer metric readers: `read(obs, **args) -> float | None`.

`obs` is what one run observed (benchmarks/run.py): `window_metrics`
(the program's counters and histogram counts, window end minus window
start), `report` (the feeder's due and sent instants), `latency_ms` (the
client's per-frame latencies), `events_in_window`, `trace` (the reduced
profiler trace of a traced run, or None), `peaks`, `flops_per_event`,
`bytes_per_event`, `chips`. A reader that finds nothing to read returns
None, never 0.
"""
