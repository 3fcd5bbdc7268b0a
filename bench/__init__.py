"""Chip benchmark of the A-3PO system: one cell per run, driven by data.

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0``
runs one cell of ``BENCHMARK.json`` once and prints its result as the last
line of standard output. Everything a cell needs is found by name:

* ``bench/configs/<config>.json``  the model configuration as it is run;
* ``bench/traffic/<traffic>.json`` the traffic mix (``bench.traffic`` reads it);
* ``bench/cells/<workload>.json``  the cell's driver, sizing and limits;
* ``bench/metrics/<metric>.py``    one reader per per-layer metric;
* ``bench/peaks.json``             published chip peaks, keyed by device kind.
"""
