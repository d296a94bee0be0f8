"""On-chip benchmark of the leased serving path.

One command runs one cell once (``python chipbench/run.py --workload ...``).
Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the model's sizes and how it is served;
- ``traffic/<mix>.json``: arrival process and length distributions;
- ``limits/<workload>.json``: the correctness limit of one cell and the
  readings it was set from;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``reference/<name>.py``: the plain reference a configuration names.
"""
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
