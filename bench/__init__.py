"""Chip benchmark of the selection system: one data-driven harness.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1``
reads the cell from ``BENCHMARK.json`` and finds its parts by name:
``bench/configs/<config>.json`` (one deployment),
``bench/traffic/<traffic>.json`` (one mix, read by ``bench/lib/traffic.py``),
``bench/metrics/<metric>.py`` (one per-layer reader) and
``bench/drivers/<driver>.py`` (the entry path the config names).
Everything the yardstick needs (data generator, reference, re-score,
peaks, trace reduction, work counts) lives under ``bench/lib``.
"""
