"""Per-layer metrics, one reader per file: ``read(ctx)`` returns the
metric's value, or ``None`` where the run left nothing to read.

``ctx`` (built by ``perf/run.py``) holds ``rounds`` and ``window_s`` of the
measured window, ``spans`` (host seconds per harness span), ``rows``
(training rows handed out in the window), ``compiles``,
``memory_peak_bytes``, ``devices``, ``peaks`` (``perf/peaks.py``),
``conf``/``mix`` (the cell's configuration and traffic), ``ref`` (the
configuration's reference module) and, traced, ``trace``
(``perf/devtrace.Reduced``).
"""
