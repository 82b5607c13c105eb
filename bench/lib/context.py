"""What a per-layer metric reader gets: the window's stamps, the program's
counters as deltas over the window, and, in a traced run, the trace's
reduction with the work the traced calls did.

A reader (``bench/metrics/<name>.py``) is ``read(ctx) -> float | None``;
it returns None when there is nothing to read, and the harness leaves
that metric out of the result line.
"""
from __future__ import annotations

import dataclasses

from bench.lib.spec import family_module


@dataclasses.dataclass
class Context:
    stamps: object                  # hooks.Stamps
    counters0: dict                 # tenant -> counter -> value, at start
    counters1: dict                 # ... at the end of the window
    end: float                      # perf_counter when serving stopped
    specs: dict                     # tenant -> its config entry
    peaks: dict                     # the device's row of peaks.json
    traced: tuple | None = None     # (start, stop) perf_counter
    reduction: object = None        # trace.Reduction

    def delta(self, key: str) -> int:
        """A program counter's change over the window, all tenants."""
        return sum(self.counters1[t][key] - self.counters0[t][key]
                   for t in self.counters1)

    @property
    def window_s(self) -> float | None:
        return None if self.traced is None else \
            self.traced[1] - self.traced[0]

    def work(self, kind: str) -> tuple[float, float, int] | None:
        """(FLOPs, least seconds, calls) of the ``kind`` ("prefill" or
        "decode") calls made inside the traced window; least seconds sum
        each call's max(FLOPs / peak FLOP/s, bytes / peak bytes/s)."""
        if self.traced is None:
            return None
        start, stop = self.traced
        calls = (self.stamps.prefill_calls if kind == "prefill"
                 else self.stamps.decode_calls)
        flops = least = 0.0
        n = 0
        for t, model, *args in calls:
            if not start <= t <= stop:
                continue
            counts = family_module("counts", self.specs[model]["family"])
            f, b = getattr(counts, kind)(self.specs[model], *args)
            flops += f
            least += max(f / self.peaks["flops_per_s"],
                         b / self.peaks["hbm_bytes_per_s"])
            n += 1
        return flops, least, n

    def program_s(self, kind: str) -> float | None:
        if self.reduction is None:
            return None
        s = self.reduction.program_s.get(kind, 0.0)
        return s if s > 0 else None
