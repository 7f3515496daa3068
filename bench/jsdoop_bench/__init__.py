"""The chip benchmark of the served training path.

``run.py`` beside this package is the command. The package is general: a
cell names a model configuration (``configs/<name>.json``, built by
``families/<family>.py`` and checked against its plain reference), a traffic
mix (``traffic/<name>.json``), correctness limits (``limits/<cell>.json``)
and metrics (``metrics/<metric>.py``, one reader each). Adding a cell,
configuration, mix or metric adds files and ``BENCHMARK.json`` entries only.
"""
