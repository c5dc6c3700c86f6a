"""Chip benchmark of this repository: one command, ``bench/run.py``, runs
one cell of ``BENCHMARK.json`` on the machine it is started on.

Every configuration (``bench/configs/<name>.json``), traffic mix
(``bench/traffic/<name>.json``) and per-layer metric
(``bench/metrics/<name>.py``) is a file of its own, found by the name that
``BENCHMARK.json`` gives it. A configuration names its architecture
(``"reference"``), whose plain reference (``bench/reference/<name>.py``)
and adapter to the program (``bench/adapters/<name>.py``) are found by
that name; no other module names an architecture. The yardstick lives
here too: traffic generation, the weights made from the seed, the plain
reference and its comparison, the peaks table, the FLOP/byte functions
and the reduction of a profiler trace to metrics. Of the program under
test it imports only the system itself (``repro``), never its metric
arithmetic.
"""
