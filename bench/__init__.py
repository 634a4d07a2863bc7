"""The chip benchmark of this repository: one command runs one cell once
(`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace
<0|1>`); BENCHMARK.json at the root lists the cells and metrics."""
