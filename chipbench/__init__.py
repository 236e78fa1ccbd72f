"""chipbench — the chip benchmark of federated_pytorch_test_tpu.

`BENCHMARK.json` at the repo root lists the configurations, traffic
mixes, cells and metrics; each is a file of its own under this
directory, found by name (`spec.py`). `run.py` is the one command.
"""
