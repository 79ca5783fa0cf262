"""The benchmark: everything `BENCHMARK.json`'s command runs and reads.

`run.py` is the command.  Whatever belongs to one configuration, one
traffic mix or one per-layer metric is a file of its own, found by the
name `BENCHMARK.json` gives it; `README.md` says how to add one.
"""
