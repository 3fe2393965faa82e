"""Chip benchmark of the streaming decentralized learner.

`bench/run.py` is the entry point; `BENCHMARK.json` at the checkout root names
the cells, and each configuration, traffic mix, per-layer metric and limit set
lives in a file of its own under this directory, found by its name."""
