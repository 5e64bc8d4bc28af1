"""Chip benchmark: models served through the engine on a TPU, timed from the
client side.  ``python chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``; the cells are listed in ``BENCHMARK.json``."""
