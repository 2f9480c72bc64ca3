"""The on-chip benchmark of the library: `python3 bench/run.py`.

See `harness.py` for how a cell is found from `BENCHMARK.json`, and
PERF.md at the root for what each cell and metric measures."""
