"""Chip benchmark for coded random-projection search, ingest and training.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the accelerator
it is started on. Everything a cell needs is found by name: its
configuration in ``configs/``, its traffic mix in ``traffic/`` (read by
the loop the mix names, in ``loops/``), its per-layer metric readers
in ``metrics/`` and its kernels' work counts in ``kernels/``.
"""
