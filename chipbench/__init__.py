"""The chip benchmark of this repository: ``BENCHMARK.json`` at the root
names its cells; ``chipbench/run.py`` runs one of them once."""
