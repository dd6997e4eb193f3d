"""The benchmark the chip judges: BENCHMARK.json names cells, this package runs one.

Everything a later PR adds is data or a small file found by name:
``configs/<config>.json``, ``traffic/<mix>.json`` (+ ``traffic/<kind>.py`` for
a new arrival kind), ``cells/<cell>.json``, ``layer_metrics/<metric>.py``.
"""
