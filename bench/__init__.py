"""The on-chip benchmark of the PolyFit query service.

``bench/run.py`` runs one cell of ``BENCHMARK.json`` (a configuration under
a traffic mix) and prints one JSON result line.  Everything that decides a
number lives here, apart from the program it measures: the data and traffic
generators (``data.py``, ``traffic/``), the plain reference that decides
``correct`` (``reference.py``), the trace reduction (``trace.py``), the
device peaks (``peaks.json``) and one reader per metric (``metrics/``).
"""
