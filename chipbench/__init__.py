"""Chip benchmark of the federated constellation system.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on.  Configurations, traffic mixes, path drivers and metric
readers are files of their own under this directory, found by the names
that ``BENCHMARK.json`` gives them.
"""
