"""Reduction of a profiler trace (``.xplane.pb``) to device numbers.

Read with ``jax.profiler.ProfileData``.  Device planes are those named
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation run and the ``XLA Modules`` line one event per program run,
named after the jitted function (``jit_<name>(<id>)``).  The harness's
host spans (``TraceAnnotation``) are events on the ``/host:CPU`` plane
whose names start with a layer prefix (``engine.``, ``runtime.``).

* busy: the union of operation intervals, averaged over the device
  planes; idle share = 1 - busy / traced window;
* program time: summed durations of the module events of each program
  kind, keyed on the jitted function's name (``PROGRAMS``);
* breakdown: the operations that took most device time (self time, less
  the operations nested in them), summed by ``<jitted function>/<HLO
  instruction>`` over their runs, and the idle
  gaps between operations, summed by the innermost host span that covers
  each gap's midpoint (``host`` outside every span).  Host and device
  events share the profiler's clock.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re

# program kind -> jitted function names (repro.serving.version_cache)
PROGRAMS = {"prefill": ("prefill_chunk",), "decode": ("qfn",)}
SPAN_PREFIXES = ("engine.", "runtime.")
_MODULE = re.compile(r"^jit_([A-Za-z0-9_]+)")


@dataclasses.dataclass
class Reduction:
    busy_s: float                          # mean over device planes
    program_s: dict                        # kind -> device seconds
    program_runs: dict                     # kind -> module events
    ops: list                              # [(name, seconds)] top 10
    idle_gaps: list                        # [(span, seconds)] top 10
    n_devices: int


def kind_of(module_name: str) -> str | None:
    m = _MODULE.match(module_name)
    fn = m.group(1) if m else module_name
    for kind, names in PROGRAMS.items():
        if fn in names:
            return kind
    return None


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _events(line):
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _self_times(events):
    """(name, start, self ns) of each event: its duration less that of the
    events nested in it (a ``while`` op holds its body's ops)."""
    out, stack = [], []          # stack: [index into out, end]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]][2] -= e - s
        out.append([name, s, e - s])
        stack.append((len(out) - 1, e))
    return out


def _op(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _module_of(modules, starts, t: float) -> str:
    """Jitted function name of the program run covering time ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and modules[i][2] >= t:
        m = _MODULE.match(modules[i][0])
        return m.group(1) if m else modules[i][0]
    return "?"


def _label(spans, starts, t: float, depth: int = 8) -> str:
    """Name of the innermost span covering ``t``: the latest-starting of
    the (few) spans that began before it and have not ended."""
    i = bisect.bisect_right(starts, t) - 1
    for j in range(i, max(i - depth, -1), -1):
        if spans[j][2] >= t:
            return spans[j][0]
    return "host"


def load(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        import gzip
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def reduce(data) -> Reduction:
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    if not devices:
        raise ValueError("trace has no /device:TPU: plane")
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIXES)]
    spans.sort(key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    busy = 0.0
    prog_s: dict = collections.defaultdict(float)
    prog_n: dict = collections.defaultdict(int)
    ops: dict = collections.defaultdict(float)
    gaps: dict = collections.defaultdict(float)
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_events = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        modules = sorted(_events(lines["XLA Modules"])
                         if "XLA Modules" in lines else [],
                         key=lambda m: m[1])
        for name, s, e in modules:
            kind = kind_of(name)
            if kind is not None:
                prog_s[kind] += (e - s) * 1e-9
                prog_n[kind] += 1
        mod_starts = [m[1] for m in modules]
        for name, s, self_ns in _self_times(op_events):
            ops[f"{_module_of(modules, mod_starts, s)}/{_op(name)}"] += \
                self_ns * 1e-9
        merged = _union((s, e) for _, s, e in op_events)
        busy += sum(e - s for s, e in merged) * 1e-9
        for (_, a), (b, _) in zip(merged, merged[1:]):
            gaps[_label(spans, starts, 0.5 * (a + b))] += (b - a) * 1e-9
    n = len(devices)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((k, v / n) for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:10]
    return Reduction(busy_s=busy / n, program_s=dict(prog_s),
                     program_runs=dict(prog_n),
                     ops=[[k, v / n] for k, v in top],
                     idle_gaps=[[k, v] for k, v in idle], n_devices=n)
