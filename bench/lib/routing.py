"""MoE routing counts in a traced run, read from the program's sync spans.

A model with routed experts puts its routing counts on the spans of the
host syncs that bring them back (``repro.serving.spans``):
``engine.quantum.sync`` (one decode quantum) and ``engine.prefill.sync``
(the chunks of one prompt) carry ``moe_assigned`` (picks over all routed
experts), ``moe_held`` (picks that land on experts held on this chip) and
``moe_peak`` (the busiest held expert's token count, summed over steps
and MoE layers), each over real tokens only.  The readers below reduce the
spans of the traced window:

* held share: 100 * sum(moe_held) / sum(moe_assigned);
* expert load: sum(moe_peak) / (sum(moe_held) / held experts), the
  busiest held expert over the mean one (1.0 is even);
* whole-step FLOP share: the FLOPs ``bench/counts`` gives the traced
  calls plus the routed FLOPs of sum(moe_held), over the traced window
  times the peak.

Each returns None in an untraced run and where no span carries the counts
(a model without routed experts, or a program that does not emit them).
"""
from __future__ import annotations

import functools
import glob
import os

from bench.lib import spans, trace
from bench.lib.spec import family_module

# repro.serving.spans.QUANTUM_SYNC and PREFILL_SYNC, written out: the
# harness also runs against a program that has no such module
SYNCS = ("engine.quantum.sync", "engine.prefill.sync")


def syncs(data) -> list[dict]:
    """Arguments of the sync spans in a loaded trace that carry routing
    counts."""
    return [d for name in SYNCS for d in spans.spans(data, name)
            if "moe_held" in d]


@functools.lru_cache(maxsize=1)
def _syncs_of(path: str, mtime: float) -> list[dict]:
    return syncs(trace.load(path))


def traced_syncs(ctx) -> list[dict] | None:
    """The traced window's sync spans with routing counts, or None."""
    if ctx.reduction is None:
        return None
    paths = sorted(glob.glob(str(spans.TRACE_DIR / "**" / "*.xplane.pb"),
                             recursive=True))
    if not paths:
        return None
    return _syncs_of(paths[-1], os.path.getmtime(paths[-1])) or None


def _sum(rs: list[dict], key: str) -> int:
    return sum(int(r[key]) for r in rs)


def _moe_spec(ctx) -> dict | None:
    """The configuration entry of the cell's model with routed experts."""
    moe = [s for s in ctx.specs.values() if "router_experts" in s]
    return moe[0] if len(moe) == 1 else None


def held_share(ctx):
    rs = traced_syncs(ctx)
    if rs is None or not _sum(rs, "moe_assigned"):
        return None
    return 100.0 * _sum(rs, "moe_held") / _sum(rs, "moe_assigned")


def expert_load(ctx):
    rs, spec = traced_syncs(ctx), _moe_spec(ctx)
    if rs is None or spec is None or not _sum(rs, "moe_held"):
        return None
    mean = _sum(rs, "moe_held") / int(spec["n_routed_experts"])
    return _sum(rs, "moe_peak") / mean


def step_flop_share(ctx):
    rs, spec = traced_syncs(ctx), _moe_spec(ctx)
    if rs is None or spec is None or ctx.window_s is None:
        return None
    counts = family_module("counts", spec["family"])
    flops = sum(ctx.work(k)[0] for k in ("prefill", "decode")) \
        + counts.routed_flops(spec, _sum(rs, "moe_held"))
    return 100.0 * flops / (ctx.window_s * ctx.peaks["flops_per_s"])
