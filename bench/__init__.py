"""Chip benchmark: one harness driven by ``BENCHMARK.json`` and the data
files beside it. ``python3 bench/run.py --help`` says how to run a cell."""
