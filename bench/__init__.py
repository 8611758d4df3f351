"""On-chip benchmark of the trainer: cells, metrics, references and checks.

Everything that belongs to one configuration, traffic mix, per-layer metric
or cell lives in a file of its own under this directory and is found by the
name ``BENCHMARK.json`` gives it:

  configs/<config>.json      sizes as run, source, assumed values
  models/<reference>.py      plain float32 reference and FLOP count of a family
  traffic/<traffic>.json     parameters of a packed-document mix
  metrics/<metric>.py        reader of one per-layer metric from the trace
  limits/<workload>.json     limit of each number ``correct`` compares
  peaks.json                 peak FLOP/s and bytes/s per ``device_kind``

``run.py`` is the command; ``harness.py`` drives one run.
"""
