"""The on-chip benchmark of the served event path (BENCHMARK.json)."""
