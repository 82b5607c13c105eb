"""Profiler spans of the serving loop: their names and arguments.

Each span is a ``jax.profiler.TraceAnnotation``.  With no profiler
session open it records nothing and costs under a microsecond or two of
host time; inside a session (``jax.profiler.start_trace`` or
``jax.profiler.trace``) the profiler keeps it on the trace's host plane,
on the same clock as the device's operations, with its keyword arguments
as the event's stats.  Arguments are host values only (Python ints and
strings, numpy scalars), never device arrays: reading one would sync.

Spans nest: a tick holds admission, sense, plan and each engine's calls;
an engine call holds its dispatch, sync and bookkeeping.  A request's
spans share its ``rid``; the ``tick`` of the enclosing ``runtime.tick``
links them to the quanta around them.

Each span, what it covers, and its arguments:

* ``runtime.tick``: one iteration of ``ClusterRuntime.serve``; ``tick``,
  ``active`` (tenants with live slots after admission).
* ``runtime.admit``: the tick's admission loop; ``admitted``, ``pending``
  (requests still queued after it), both summed over tenants.
* ``runtime.sense``: ``_live_demands``, slot occupancy to demands.
* ``runtime.plan``: ``_replan`` of one tenant; ``tenant``.
* ``runtime.counters``: the counter read and proxy re-fit inside it;
  ``tenant``.
* ``runtime.pick``: ``pick_quantum`` for one engine; ``tenant``.
* ``engine.set_level``: ``set_interference_level``; ``switched`` (1 when
  the code version changed, else 0).
* ``engine.admit``: ``admit_request``; ``model``, ``rid``.
* ``engine.prefill_step``: ``prefill_step``; ``model``.
* ``engine.prefill.dispatch``: the chunk program's call; ``model``,
  ``rid``, ``start`` (prompt offset), ``chunk`` (padded size), ``tokens``
  (real prompt tokens), ``finished`` (1 on the prompt's last chunk).
* ``engine.prefill.row_write``: that last chunk's row written into the
  batched cache; ``rid``.
* ``engine.prefill.sync``: the first-token argmax, one host sync; ``rid``;
  for a model with routed experts also the routing counts of the
  prompt's chunks (below).
* ``engine.begin_quantum``: ``begin_quantum``; ``model``.
* ``engine.quantum.dispatch``: the decode program's call; see below.
* ``engine.finish_quantum``: ``finish_quantum``; ``model``.
* ``engine.quantum.sync``: the quantum's one device-to-host transfer; for
  a model with routed experts also the quantum's routing counts.
* ``engine.quantum.bookkeep``: everything after that transfer.
* ``engine.vcache.build``: a trace or compile in ``VersionCache``;
  ``kind``, ``bucket``.

``engine.quantum.dispatch`` carries what the quantum was given:
``model`` (config name), ``slots`` (batch slots), ``rows`` (decodable
rows), ``prefilling`` (slots held by a prompt still prefilling),
``asked`` (the ``k`` the caller asked for), ``steps`` (most tokens one
row can take from the call: the fused quantum's length, a speculative
quantum's per-row cap), ``bucket`` (the executable's K-bucket),
``tokens`` (sum of the rows' budgets at dispatch) and ``kind``
(``decode`` or ``spec``).

Routing counts (``engine.prefill.sync``, ``engine.quantum.sync``; set
when the transfer has brought them back, so the sync itself carries
them): ``moe_assigned`` (the real tokens' picks over all routed
experts, summed over MoE layers), ``moe_held`` (those that land on
experts held on this chip) and ``moe_peak`` (the busiest held expert's
token count, summed over steps and MoE layers); the engine's counters
``moe_assignments`` and ``moe_held_assignments`` sum the first two.

``engine.vcache.build`` covers the lower-and-compile of the AOT decode
quanta (``kind`` ``quantum`` or ``spec``, ``bucket`` K) and the trace of
the lazily jitted programs (``prefill``, ``decode``, ``prefill_chunk``;
``bucket`` their token width), whose compile runs after it, inside the
caller's span.
"""
from __future__ import annotations

import jax

RUNTIME_TICK = "runtime.tick"
RUNTIME_ADMIT = "runtime.admit"
RUNTIME_SENSE = "runtime.sense"
RUNTIME_PLAN = "runtime.plan"
RUNTIME_COUNTERS = "runtime.counters"
RUNTIME_PICK = "runtime.pick"
ENGINE_SET_LEVEL = "engine.set_level"
ENGINE_ADMIT = "engine.admit"
ENGINE_PREFILL_STEP = "engine.prefill_step"
PREFILL_DISPATCH = "engine.prefill.dispatch"
PREFILL_ROW_WRITE = "engine.prefill.row_write"
PREFILL_SYNC = "engine.prefill.sync"
ENGINE_BEGIN_QUANTUM = "engine.begin_quantum"
QUANTUM_DISPATCH = "engine.quantum.dispatch"
ENGINE_FINISH_QUANTUM = "engine.finish_quantum"
QUANTUM_SYNC = "engine.quantum.sync"
QUANTUM_BOOKKEEP = "engine.quantum.bookkeep"
VCACHE_BUILD = "engine.vcache.build"


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A profiler span ``name`` with host-value ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(name, **args)
