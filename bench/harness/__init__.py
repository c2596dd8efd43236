"""Chip benchmark harness: one cell of ``BENCHMARK.json``, run once.

Everything here is shared by every cell.  What belongs to one cell lives
in files found by name: ``configs/<config>.json`` (the model as run),
``architectures/<architecture>.py`` (named by the configuration: its
sizes, the program's config, the plain reference and the counts from
shapes), ``traffic/<mix>.json`` (the load; its ``kind`` names the module
here that runs it, ``graph.py`` or ``serve.py``), ``limits/<workload>.json``
(the limits of its check) and ``metrics/<metric>.py`` (one reader per
metric).
"""
