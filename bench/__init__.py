"""On-chip benchmark of the DFRC fit and the online server (BENCHMARK.json)."""
