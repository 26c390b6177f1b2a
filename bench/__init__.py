"""On-chip benchmark: one entry point (`bench/run.py`) over cells named in
the repository's `BENCHMARK.json`. Everything that defines the yardstick
lives here: the traffic generator, the peaks table, the trace reduction,
the work counts and the plain references."""
