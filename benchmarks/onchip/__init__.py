"""On-chip benchmark of the TapOut serving path (see PERF.md).

``run.py`` is the one command; everything it measures is found by name from
``BENCHMARK.json``: configurations under ``configs/``, traffic mixes under
``traffic/``, per-layer metric readers under ``metrics/``.
"""
