"""The benchmark of the beacon node on the TPU: see run.py and PERF.md."""
