"""On-chip benchmark of TensorCodec's served reads and streaming fit.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json``.  Everything that belongs to one
configuration, traffic mix or per-layer metric lives in a file of its own
(``configs/``, ``traffic/``, ``layer_metrics/``) and is found by name.
"""
