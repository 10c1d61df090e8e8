"""On-chip benchmark harness: one cell of ``BENCHMARK.json`` run once.

The harness is driven by data.  ``BENCHMARK.json`` names each cell's
configuration file and traffic mix, and each metric; the harness finds
everything else by those names under the benchmark's directory:

* ``configs/<config>.json``   sizes, engine settings, precision, limits;
* ``traffic/<traffic>.json``  one traffic mix for the general generator;
* ``metrics/<metric>.py``     one reader per metric, ``read(run)``;
* ``roofline/<kernel>.py``    least work of one kernel call, from shapes;
* ``roofline/peaks.json``     peaks of each device kind;
* ``reference/<family>.py``   plain float32 reference of a model family.

A configuration names its ``driver`` (``cnn_serve`` or ``lm_continuous``):
the module that stands the system under test up through its public
entry points and drives it with the traffic.
"""
