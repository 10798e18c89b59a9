"""chipbench: the repository's benchmark on the chip.

One command runs one cell (a model configuration under a traffic mix) once:

    python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name from ``BENCHMARK.json`` and the data files under
``chipbench/`` (see ``chipbench/README.md``); nothing here lists cells.
"""
