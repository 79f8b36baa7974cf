"""The benchmark of the Quegel engine on a TPU (see PERF.md and BENCHMARK.json)."""
