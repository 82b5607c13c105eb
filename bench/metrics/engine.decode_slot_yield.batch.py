"""Decode slot yield in the batch cell: tokens the decode quanta were
given over the slot-steps they ran, from the program's
``engine.quantum.dispatch`` spans (``bench/lib/spans.py``), in %."""
from bench.lib.spans import reader, slot_yield

read = reader(slot_yield)
