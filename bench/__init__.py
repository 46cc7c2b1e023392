"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that defines a cell is data found by name: the configuration
``bench/configs/<config>.json``, the traffic mix ``bench/traffic/<mix>.json``,
the correctness limits ``bench/limits/<cell>.json`` and one reducer per
per-layer metric, ``bench/metrics/<metric>.py``.  The yardstick (inputs from
the seed, the plain reference, flop and byte counts, the peaks table, the
trace reduction) lives here too, apart from the program under test.
"""
