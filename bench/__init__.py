"""Chip benchmark for the DC-ELM learn, stream and serve paths.

One cell, one run: ``python3 -m bench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``. ``BENCHMARK.json`` at the repository root
names the cells; everything a cell uses is found by name under this
directory: ``configs/<config>.json``, ``traffic/<mix>.json`` (whose
``kind`` picks ``drivers/<kind>.py``), ``limits/<cell>.json`` and
``metrics/<metric>.py``.
"""
