"""The benchmark of record (BENCHMARK.json): harness, data and yardstick."""
