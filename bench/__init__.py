"""On-chip training benchmark: harness, plain reference, work counts and
trace reduction.  ``python3 bench/run.py --workload <cell> ...`` is the
one command; ``BENCHMARK.json`` at the checkout root names the cells."""
