"""The benchmark: harness, yardstick and data. See benchmarks/README.md."""
