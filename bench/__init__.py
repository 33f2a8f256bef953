"""On-chip serving benchmark: one entry point (``bench/run.py``) whose cells
(model configuration x traffic mix) are named in ``BENCHMARK.json`` and
resolved from data files under this directory."""
