"""The benchmark of raft_tpu: cells from BENCHMARK.json, run by run.py."""
