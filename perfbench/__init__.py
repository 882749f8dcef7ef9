"""perfbench: the benchmark of paddle_tpu. See PERF.md and BENCHMARK.json."""
