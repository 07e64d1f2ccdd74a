"""Chip benchmark of the serving path: one cell is one model configuration
under one traffic mix, as named in ``BENCHMARK.json``.  Run a cell with
``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``."""
