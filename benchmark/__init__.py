"""The benchmark: BENCHMARK.json's command, its yardsticks and its data
files. See README.md in this directory."""
