"""On-chip benchmark of IBMB training, bulk scoring and serving.

``python ibmb_bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once. Everything that
belongs to one configuration, workload, mode or per-layer metric is a file
of its own, found by its name: ``configs/<config>.json``,
``workloads/<cell>.json``, ``modes/<mode>.py``, ``metrics/<metric>.py``.
"""
