"""Chip benchmark of the ZIPPER GNN system: one cell per run, driven by data.

``BENCHMARK.json`` at the checkout root names the cells, metrics and
configurations; everything that belongs to one configuration, traffic mix,
measurement loop or metric is a file of its own under ``bench/``, found by
name (:mod:`bench.manifest`).  ``python3 bench/run.py --workload <cell>``
runs one cell once on the chip.
"""
