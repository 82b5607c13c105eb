"""Decode steps per dispatched quantum in the batch cell, from the
program's ``engine.quantum.dispatch`` spans (``bench/lib/spans.py``)."""
from bench.lib.spans import reader, steps_per_quantum

read = reader(steps_per_quantum)
