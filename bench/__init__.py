"""Chip benchmark for the MPX training and paged-serving stack.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the chips of this machine.
Everything the harness needs for a cell is found by name under this
directory: ``configs/<config>.json``, ``traffic/<mix>.json``,
``drivers/<kind>.py``, ``layer_metrics/<metric>.py`` and
``limits/<workload>.json``.
"""
