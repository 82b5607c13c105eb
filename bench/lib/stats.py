"""End-to-end metric arithmetic over the harness's own stamps.

All times are ``time.perf_counter()`` seconds.  A request's record holds
its due time, the stamp of its first token and of every later token
delivery (one host sync may deliver several tokens).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Record:
    rid: int
    tenant: str
    due: float                     # window start + arrival offset
    prompt_len: int
    want: int                      # tokens the request asks for (first + new)
    admitted: float | None = None
    first: float | None = None     # first token on the host
    deliveries: list = dataclasses.field(default_factory=list)  # (t, n)

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.deliveries)

    @property
    def finished(self) -> bool:
        return self.tokens >= self.want

    @property
    def last(self) -> float | None:
        return self.deliveries[-1][0] if self.deliveries else None


def percentile(values, q: float) -> float:
    """``q``-th percentile with linear interpolation between order
    statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, float), q))


def ttft(rec: Record, end: float) -> float:
    """Due time to first token; a request without one enters the tail at
    the latency it had reached at ``end``."""
    return (rec.first if rec.first is not None else end) - rec.due


def tpot(rec: Record, end: float) -> float:
    """Mean time per output token after the first:
    ``(t_last - t_first) / (n_tokens - 1)``.  An unfinished request
    counts as if its next token came at ``end``; one without a first
    token counts its whole wait."""
    if rec.first is None:
        return end - rec.due
    if rec.finished:
        return (rec.last - rec.first) / max(rec.tokens - 1, 1)
    return (end - rec.first) / max(rec.tokens, 1)


def tokens_by(records, deadline: float) -> int:
    """Output tokens delivered to the host at or before ``deadline``."""
    return sum(n for r in records for t, n in r.deliveries if t <= deadline)
