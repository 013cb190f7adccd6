"""On-chip benchmark of sparsified data-parallel training.

``python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once. Everything a cell needs is found
by name: ``configs/<config>.json`` (sizes) with ``configs/<config>.py``
(plain reference, weight init, FLOP count), ``traffic/<mix>.json``,
``metrics/<metric>.py`` and ``limits/<workload>.json``.
"""
