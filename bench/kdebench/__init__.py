"""Chip benchmark of the Flash-SD-KDE system, driven by ``BENCHMARK.json``.

``bench/run.py`` runs one cell: it finds the cell in ``BENCHMARK.json``,
loads the cell's configuration (``bench/configs/<config>.json``), its
traffic mix (``bench/traffic/<mix>.json``) and its per-layer metric readers
(``bench/layer_metrics/<metric>.py``) by name, and drives the system
through its normal entry points.  Nothing here names a cell: adding one is
adding files and entries.
"""
