"""Traffic: mixes (``<mix>.json``, parameters only) and the generator
modules they name (``<generator>.py``, each defining ``Generator``, a
``bench.loadgen.Traffic``)."""
