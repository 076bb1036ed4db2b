"""On-chip benchmark of the OptINC repository: one cell per run, driven by
``BENCHMARK.json`` (see ``bench/run.py``)."""
