"""Chip benchmark of the serving path: one cell per run, driven by data.

``python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``; see ``run.py``.
"""
