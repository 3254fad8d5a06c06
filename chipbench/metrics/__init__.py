"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module has ``read(run) -> float | None``.  ``run`` holds the window's
``record`` (host clock and the program's counters), ``setup_s``, the
``trace`` reduction of a traced run (``chipbench.trace.reduce``) or None,
the ``config``, the ``traffic``, the device's ``peaks`` and the number of
``chips``.  A reader that finds nothing to read returns None, and the
metric is left out of the result line.
"""
