"""On-chip serving benchmark: cells named in BENCHMARK.json, run by run.py."""
