"""Chip benchmark of the constellation work-stealing simulator.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json`. Everything that belongs to one
deployment, one traffic mix or one metric is a file of its own, found by
the name in `BENCHMARK.json`: `bench/configs/<config>.json`,
`bench/traffic/<traffic>.json` and `bench/metrics/<metric>.py`.
"""
