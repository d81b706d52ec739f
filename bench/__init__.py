"""On-chip benchmark of ``repro.serving.codesign`` (see BENCHMARK.json, PERF.md).

Everything a cell needs is found by name: ``configs/<config>.json``,
``mixes/<traffic>.json``, ``metrics/<metric>.py``, ``limits/<cell>.json``.
"""
