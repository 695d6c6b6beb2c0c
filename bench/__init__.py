"""The chip benchmark of the persistent indexes: one harness, driven by data.

``BENCHMARK.json`` at the root of the checkout names the cells; each cell
is one configuration (``bench/configs/<name>.json``) under one traffic mix
(``bench/traffic/<name>.json``), and each per-layer metric is a small
reader of its own (``bench/metrics/<name>.py``).  ``bench/run.py`` finds
all of them by name, so a new cell, mix or metric is new files and new
entries, never an edit of a file that is there.

What must not move when the program changes lives here too: the key and
traffic samplers (``traffic``), the plain reference (``replay``), the
table of peaks (``peaks``), the bytes each kernel needs by its shapes
(``shapes``) and the reduction of a profiler trace (``xplane``).
"""
