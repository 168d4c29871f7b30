"""The chip benchmark: ``python3 benchmark/run.py --workload <cell> ...``
(see ``harness.py`` for how cells, drivers and metric readers are found)."""
