"""On-chip benchmark of the descriptor offload runtime.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the TPU it is started on and prints
one JSON result line.  Everything a cell needs is found by name: its
deployment in ``bench/configs/``, its traffic mix in ``bench/traffic/``,
the adapter of the deployment's system in ``bench/systems/`` and one reader
per metric in ``bench/metrics/``.
"""
