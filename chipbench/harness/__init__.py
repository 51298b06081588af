"""The benchmark harness: runs one cell of BENCHMARK.json on the chip."""
