"""The chip benchmark of the fabric simulator.

``python benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the chip it
is started on and prints one JSON result line.  Everything a cell needs
is found by name: its configuration under ``configs/``, its traffic mix
under ``traffic/``, the mix's driver under ``drivers/``, its generators
under ``generators/``, its limits under ``limits/`` and each per-layer
metric's reader under ``metrics/``.
"""
