"""Readers shared by per-layer metrics that split one quantity by the
end-to-end metric it moves (``<name>.serve``, ``<name>.batch``).  Each
``bench/metrics/<metric>.py`` binds one of these as its ``read``; a
reader returns None when there is nothing to read."""
from __future__ import annotations


def tokens_per_sync(ctx):
    """Tokens delivered per device-to-host sync: deltas of the engines'
    ``tokens_decoded`` over ``host_syncs`` across the window."""
    syncs = ctx.delta("host_syncs")
    return ctx.delta("tokens_decoded") / syncs if syncs else None


def traces_in_window(ctx):
    """JAX traces the engines' version caches made during the window (the
    delta of ``VersionCache.traces``); warmup should leave none."""
    return float(ctx.delta("traces"))


def idle_share(ctx):
    """Share of the traced window in which no operation ran on the device:
    1 - (union of the device's operation intervals) / window, in %."""
    if ctx.reduction is None or not ctx.window_s:
        return None
    return 100.0 * (1.0 - ctx.reduction.busy_s / ctx.window_s)


def roofline(kind: str):
    """Share of the roofline reached by the ``kind`` programs ("prefill"
    chunks or "decode" quanta) in the traced window: the least time their
    calls needed (``bench/counts``) over the device time of their program
    runs, in %."""
    def read(ctx):
        work, dev = ctx.work(kind), ctx.program_s(kind)
        if not work or not work[2] or dev is None:
            return None
        return 100.0 * work[1] / dev
    return read


def step_mfu(kind: str):
    """The whole ``kind`` step's share of the chip's peak FLOP/s: the
    FLOPs of the real tokens its calls processed in the traced window over
    the device time of its program runs times the peak, in %."""
    def read(ctx):
        work, dev = ctx.work(kind), ctx.program_s(kind)
        if not work or not work[2] or dev is None:
            return None
        return 100.0 * work[0] / (dev * ctx.peaks["flops_per_s"])
    return read
