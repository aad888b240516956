"""The on-chip benchmark of ml_recipe_tpu: ``python3 perfbench/run.py``.

Everything the yardstick needs lives under this directory: traffic
generation, the plain reference, the peaks table, the FLOP and byte
functions, the reduction from trace and spans to metrics, and the comparison
that decides ``correct``. From the program it takes only the system under
test and its counters. Layout (each found by name from ``BENCHMARK.json``):

- ``configs/<config>.json``   a model configuration as it is run;
- ``traffic/<traffic>.json``  a job or traffic mix: parameters only, read by
                              the one runner its ``runner`` field names;
- ``metrics/<metric>.py``     one per-layer metric: ``read(ctx)`` returns a
                              number, or ``None`` where there is nothing to read;
- ``runners/<runner>.py``     one per kind of job (``train``, ``serve``).
"""
