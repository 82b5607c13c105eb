"""The program's own spans in a traced run, read with their arguments.

The serving loop emits profiler spans (``repro.serving.spans``) whose
keyword arguments travel in the trace as event stats on the host plane.
``dispatches`` returns the arguments of every ``engine.quantum.dispatch``
span, one dict per decode call; the readers below reduce them:

* slot yield: tokens the quanta were given over the slot-steps they ran,
  100 * sum(tokens) / sum(slots * steps);
* prefill-held share: slot-steps held by a prompt still prefilling,
  100 * sum(prefilling * steps) / sum(slots * steps);
* steps per quantum: sum(steps) / number of dispatches.

A reader gets its spans from the last ``.xplane.pb`` under
``<root>/.bench_trace`` in name order, the trace ``bench/run.py``
reduced (a traced run empties the directory first), and returns
None in an untraced run (no reduction: a trace left there by an earlier
run is never read) or when the trace holds no dispatch span (a program
that does not emit them).
"""
from __future__ import annotations

import functools
import glob
import os

from bench.lib import trace
from bench.lib.spec import ROOT

TRACE_DIR = ROOT / ".bench_trace"
# repro.serving.spans.QUANTUM_DISPATCH, written out: the harness also
# runs against a program that has no such module
QUANTUM_DISPATCH = "engine.quantum.dispatch"


def spans(data, name: str) -> list[dict]:
    """Arguments of every host event ``name`` in a loaded trace."""
    return [dict(ev.stats)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines
            for ev in line.events if ev.name == name]


def dispatches(data) -> list[dict]:
    return spans(data, QUANTUM_DISPATCH)


@functools.lru_cache(maxsize=1)
def _dispatches_of(path: str, mtime: float) -> list[dict]:
    return dispatches(trace.load(path))


def traced_dispatches(ctx) -> list[dict] | None:
    """The traced window's dispatch spans, or None in an untraced run or
    where there are none."""
    if ctx.reduction is None:
        return None
    paths = sorted(glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    return _dispatches_of(paths[-1], os.path.getmtime(paths[-1])) or None


def slot_steps(ds: list[dict]) -> int:
    return sum(int(d["slots"]) * int(d["steps"]) for d in ds)


def slot_yield(ds: list[dict]) -> float:
    return 100.0 * sum(int(d["tokens"]) for d in ds) / slot_steps(ds)


def prefill_held_share(ds: list[dict]) -> float:
    return 100.0 * sum(int(d["prefilling"]) * int(d["steps"])
                       for d in ds) / slot_steps(ds)


def steps_per_quantum(ds: list[dict]) -> float:
    return sum(int(d["steps"]) for d in ds) / len(ds)


def reader(reduce):
    """``read(ctx)`` applying ``reduce`` to the traced dispatch spans."""
    def read(ctx):
        ds = traced_dispatches(ctx)
        return None if ds is None else reduce(ds)
    return read
