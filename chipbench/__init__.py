"""On-chip benchmark of the NTX train step.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on. Cells,
configurations, traffic mixes and per-layer metrics are data: each lives in
a file of its own (``configs/<name>.json``, ``traffic/<name>.json``,
``metrics/<name>.py``, ``limits/<cell>.json``) that the harness finds by
the name ``BENCHMARK.json`` gives it.
"""
