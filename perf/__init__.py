"""The on-chip benchmark of the FedDif system.

``BENCHMARK.json`` at the checkout's root names the cells; each is a
configuration under a traffic mix, and everything else is found by name:

* ``configs/<config>.json``: the configuration's sizes, as run, its rows
  (``data``: images or tokens) and, optionally, smaller sizes for the CPU
  tests (``cpu``); ``configs/<config>_ref.py``: its plain reference
  (trained weights from a seed, the loss in plain ``jax.numpy``, FLOPs per
  training row and, optionally, a frozen tree, ``frozen``);
  ``configs/<config>.py``: the glue to the program under test.
* ``traffic/<mix>.json``: a traffic mix, read by ``traffic/generate.py``.
* ``cells/<workload>.json``: the limits of the numbers that decide
  ``correct``.
* ``metrics/<metric>.py``: one per-layer metric's reader.

``run.py`` runs one cell, ``readings.py`` takes the readings the limits are
set from, ``reference.py`` is the plain reference of a communication round,
``devtrace.py`` reduces a profiler trace, ``flops.py`` and ``peaks.py``
hold the yardstick's counts and the chips' peaks.
"""
