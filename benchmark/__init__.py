"""The benchmark: see run.py and PERF.md."""
