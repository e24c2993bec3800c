"""The benchmark of tpu-llmlb: `python3 benchmark/run.py --workload <cell> ...`.

Everything the yardstick needs lives under this directory (and its tests under
`tests/benchmark/`); `BENCHMARK.json` at the repo root names the cells,
configurations and metrics, and the harness finds each one's file by name.
"""
