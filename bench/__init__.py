"""The chip benchmark of the overlay simulator: harness, traffic generator,
plain reference, trace reduction and the data files that define its cells.

Run one cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout; ``BENCHMARK.json``
names the cells.
"""
