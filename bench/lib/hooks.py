"""Stamps, spans and window control, wrapped around the engines' calls.

The harness never reads the runtime's clock or records.  It wraps four
methods on each engine instance (not the class) and takes every
end-to-end timestamp itself, on ``time.perf_counter()``:

* ``admit_request`` waits until the request's due time ``T0 + arrival``
  before admitting it (the runtime jumps its clock to the next arrival
  when idle, which would admit early), gives the request its own output
  length (the runtime's ``Workload`` carries one, the longest) and stamps
  the admission;
* ``prefill_step`` stamps the first token when the prompt's last chunk
  finishes (the first-token argmax syncs inside that call);
* ``finish_quantum`` stamps the tokens each host sync delivered;
* ``begin_quantum`` records what each decode quantum was asked to do.

The prefill and decode wraps also record the work each call did (start
position and real tokens; per-row positions and steps), which the
roofline counts read.  After every call the window controller may stop
the runtime's loop (by setting its ``max_steps`` to 0) and start or stop
the profiler.  In a traced run every wrapped call, and the runtime's
sense/plan/pick phases, runs inside a ``jax.profiler.TraceAnnotation``.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench.lib.stats import Record

REQUIRED = ("admit_request", "prefill_step", "begin_quantum",
            "finish_quantum")


class HookError(Exception):
    """A method the harness must wrap does not exist."""


class Stamps:
    """Everything the window recorded."""

    def __init__(self, t0: float, records: dict[int, Record]):
        self.t0 = t0
        self.records = records            # rid -> Record
        self.prompts: dict[int, np.ndarray] = {}
        self.outputs: dict[int, list[int]] = {}
        self.prefill_calls: list[tuple] = []   # (t, model, start, tokens, last)
        self.decode_calls: list[tuple] = []    # (t, model, rows, steps)


class Window:
    """Installs the wraps on every tenant engine and drives the window's
    edges.  ``stop_at``: stop the runtime loop at the first call
    boundary after it (None: run to completion); ``trace``: (start,
    stop) perf_counter times of the profiler window, or None."""

    def __init__(self, runtime, stamps: Stamps, *, stop_at: float | None,
                 trace: tuple[float, float] | None, trace_dir: str | None):
        self.runtime = runtime
        self.stamps = stamps
        self.stop_at = stop_at
        self.trace = trace
        self.trace_dir = trace_dir
        self.tracing = False
        self.traced = None                 # (start, stop) actually traced
        self.stopped_at = None
        self._undo: list = []
        for t in runtime.tenants:
            for name in REQUIRED:
                if not callable(getattr(t.engine, name, None)):
                    raise HookError(f"{t.name}: engine has no {name}()")
            self._wrap_engine(t.name, t.engine)
        if trace is not None:
            for name, label in (("_live_demands", "runtime.sense"),
                                ("_replan", "runtime.plan")):
                self._wrap(runtime, name, self._span_only(
                    getattr(runtime, name), label))
            import repro.serving.cluster as cluster_mod
            if not callable(getattr(cluster_mod, "pick_quantum", None)):
                raise HookError("repro.serving.cluster has no pick_quantum")
            self._wrap(cluster_mod, "pick_quantum", self._span_only(
                cluster_mod.pick_quantum, "runtime.pick"))

    # -- installation -------------------------------------------------
    def _wrap(self, obj, name: str, fn) -> None:
        had = name in vars(obj)
        self._undo.append((obj, name, vars(obj).get(name), had))
        setattr(obj, name, fn)

    def remove(self) -> None:
        for obj, name, old, had in reversed(self._undo):
            if had:
                setattr(obj, name, old)
            else:
                delattr(obj, name)
        self._undo.clear()

    def _span(self, label: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(label)

    def _span_only(self, fn, label: str):
        def wrapped(*a, **kw):
            with self._span(label):
                return fn(*a, **kw)
        return wrapped

    def _wrap_engine(self, model: str, eng) -> None:
        s = self.stamps
        admit, prefill = eng.admit_request, eng.prefill_step
        begin, finish = eng.begin_quantum, eng.finish_quantum
        prefilled: dict[int, int] = {}

        def admit_request(req, *a, **kw):
            rec = s.records[req.rid]
            wait = rec.due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            req.max_new_tokens = rec.want - 1
            with self._span("engine.admit"):
                ok = admit(req, *a, **kw)
            if ok:
                rec.admitted = time.perf_counter()
                s.prompts[req.rid] = np.array(req.prompt, np.int32)
                s.outputs[req.rid] = req.output
            return ok

        def prefill_step(*a, **kw):
            t = time.perf_counter()
            with self._span("engine.prefill_step"):
                pq = prefill(*a, **kw)
            now = time.perf_counter()
            if pq is not None:
                start = prefilled.get(pq.rid, 0)
                prefilled[pq.rid] = start + pq.tokens
                s.prefill_calls.append((t, model, start, pq.tokens,
                                        bool(pq.finished)))
                if pq.finished:
                    rec = s.records[pq.rid]
                    rec.first = now
                    rec.deliveries.append((now, 1))
            self._edge(now)
            return pq

        def begin_quantum(*a, **kw):
            t = time.perf_counter()
            with self._span("engine.begin_quantum"):
                h = begin(*a, **kw)
            if h is not None:
                rows = [(int(eng.slot_pos[i]), int(h.n_left[i]))
                        for i in h.active]
                s.decode_calls.append((t, model, rows, int(h.steps)))
            return h

        def finish_quantum(handle, *a, **kw):
            live = ([(eng.slot_req[i], len(eng.slot_req[i].output))
                     for i in handle.active] if handle is not None else [])
            with self._span("engine.finish_quantum"):
                done = finish(handle, *a, **kw)
            now = time.perf_counter()
            for req, before in live:
                n = len(req.output) - before
                if n:
                    s.records[req.rid].deliveries.append((now, n))
            self._edge(now)
            return done

        for name, fn in (("admit_request", admit_request),
                         ("prefill_step", prefill_step),
                         ("begin_quantum", begin_quantum),
                         ("finish_quantum", finish_quantum)):
            self._wrap(eng, name, fn)

    # -- window edges -------------------------------------------------
    def _edge(self, now: float) -> None:
        if self.trace is not None:
            start, stop = self.trace
            if not self.tracing and self.traced is None and now >= start:
                self._start_trace()
            elif self.tracing and now >= stop:
                self._stop_trace()
        if self.stop_at is not None and now >= self.stop_at \
                and self.stopped_at is None:
            self.stopped_at = now
            self.runtime.max_steps = 0     # the loop ends at its next check

    @staticmethod
    def _drain_device() -> None:
        """Wait until the device has run everything dispatched so far, so
        the traced window holds exactly the calls made inside it."""
        import jax
        for a in jax.live_arrays():
            a.block_until_ready()

    def _start_trace(self) -> None:
        import jax
        self._drain_device()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # the harness's spans suffice
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self.tracing = True
        self.traced = (time.perf_counter(), None)

    def _stop_trace(self) -> None:
        import jax
        self._drain_device()
        self.traced = (self.traced[0], time.perf_counter())
        self.tracing = False
        jax.profiler.stop_trace()

    def close(self) -> None:
        if self.tracing:
            self._stop_trace()
        self.remove()
