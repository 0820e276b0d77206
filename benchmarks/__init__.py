"""The chip benchmark of multiverso_tpu (``BENCHMARK.json`` at the repo root).

``python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell.  ``PERF.md`` ("Adding a cell") says which
file holds what; nothing outside this directory belongs to the benchmark.
"""
