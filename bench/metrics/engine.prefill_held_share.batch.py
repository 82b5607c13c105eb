"""Share of the decode quanta's slot-steps held by a prompt still
prefilling, batch cell, from the program's ``engine.quantum.dispatch``
spans (``bench/lib/spans.py``), in %."""
from bench.lib.spans import prefill_held_share, reader

read = reader(prefill_held_share)
