"""The one traffic generator: a traffic file's parameters -> requests.

* ``open_loop``: the window is cut into ``round(seconds / interval_s)``
  equal segments.  Each segment's rate multiplier is drawn from the seed
  as ``Gamma(1/b, b)`` (mean 1, variance ``b`` = ``burstiness``; ``b = 0``
  is plain Poisson), the definition of
  ``repro.serving.request.gamma_poisson_workload``; each segment's
  arrival count is Poisson with that mean and its offsets are uniform.
* ``backlog``: ``n_requests`` requests, all due at the window's start.

Request sizes come in groups of ``group`` requests (default: all of
them).  Every group holds the same multiset: each tenant's share of the
group (``popularity``, exact counts by largest remainder), with prompt
lengths spread evenly over ``prompt_len`` = [lo, hi] and output lengths
spread evenly over ``output_len`` = [lo, hi] (or one number), paired and
ordered by a fixed permutation.  So the sizes, and their order, are the
same for every seed, and a window that serves the first groups does the
same work whatever the seed; the seed draws the arrivals, the prompt
tokens and the weights.
"""
from __future__ import annotations

import dataclasses

import numpy as np

ORDER_STREAM = 3        # the fixed permutations of sizes (seed-independent)


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float          # seconds after the window opens
    tenant: str
    prompt_len: int
    output_len: int       # new tokens after the first


def split_seed(seed: int) -> list[int]:
    """A seed of any size as 32-bit words (numpy and JAX both take it)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError("seed must be >= 0")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream] + split_seed(seed))


def tenant_counts(popularity: dict[str, float], n: int) -> dict[str, int]:
    """Exact per-tenant counts summing to ``n`` (largest remainder)."""
    names = list(popularity)
    w = np.array([popularity[k] for k in names], float)
    quota = w / w.sum() * n
    base = np.floor(quota).astype(int)
    order = np.argsort(-(quota - base), kind="stable")
    for i in order[:n - base.sum()]:
        base[i] += 1
    return dict(zip(names, base.tolist()))


def spread_lengths(lo: int, hi: int, n: int) -> list[int]:
    """``n`` lengths spread evenly over [lo, hi] (midpoints of n strata)."""
    span = hi - lo + 1
    return [lo + int((i + 0.5) * span / n) for i in range(n)]


def length_range(value) -> tuple[int, int]:
    """[lo, hi] of a traffic file's length entry (a list or one number)."""
    lo, hi = (value, value) if np.isscalar(value) else value
    return int(lo), int(hi)


def _arrival_times(traffic: dict, seconds: float, seed: int) -> list[float]:
    draw = rng(seed, 1)
    b = float(traffic.get("burstiness", 0.0))
    n_seg = max(1, round(seconds / float(traffic["interval_s"])))
    seg = seconds / n_seg
    times = []
    for j in range(n_seg):
        mult = float(draw.gamma(1.0 / b, b)) if b > 0 else 1.0
        k = int(draw.poisson(float(traffic["rate_per_s"]) * mult * seg))
        times += (j * seg + np.sort(draw.uniform(0.0, seg, k))).tolist()
    return times


def _group(popularity: dict[str, float], n: int, prompt: tuple[int, int],
           output: tuple[int, int], order: np.random.Generator) -> list:
    """(tenant, prompt_len, output_len) of one group of ``n``, in order."""
    block = []
    for t, c in tenant_counts(popularity, n).items():
        outs = spread_lengths(*output, c)
        block += [(t, p, outs[i]) for p, i in
                  zip(spread_lengths(*prompt, c), order.permutation(c))]
    return [block[i] for i in order.permutation(n)]


def generate(traffic: dict, tenants: list[str], seconds: float,
             seed: int) -> list[Request]:
    """Requests of one run, sorted by due time (all 0.0 for a
    backlog)."""
    kind = traffic["kind"]
    if kind == "open_loop":
        times = _arrival_times(traffic, seconds, seed)
    elif kind == "backlog":
        times = [0.0] * int(traffic["n_requests"])
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    popularity = traffic.get("popularity") or {t: 1.0 for t in tenants}
    if set(popularity) != set(tenants):
        raise ValueError(f"traffic popularity names {sorted(popularity)}, "
                         f"the configuration serves {sorted(tenants)}")
    prompt = length_range(traffic["prompt_len"])
    output = length_range(traffic["output_len"])
    group = int(traffic.get("group") or len(times) or 1)
    order = rng(0, ORDER_STREAM)
    sizes = []
    for g in range(0, len(times), group):
        sizes += _group(popularity, min(group, len(times) - g), prompt,
                        output, order)
    return [Request(due_s=t, tenant=s[0], prompt_len=s[1], output_len=s[2])
            for t, s in zip(times, sizes)]
