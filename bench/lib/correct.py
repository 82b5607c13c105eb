"""Whether what the timed path served is right, judged by the reference.

Once the window has closed and the engines are freed, a sample of the
requests the window finished, drawn from the seed and always holding the
longest, is run through the family's plain float32 reference: one causal
forward over each prompt followed by its served tokens.  For each served
token the number read is its gap, the amount by which the reference's
logit for it lies below the reference's best logit at that position (0
when the served token is the reference's own greedy choice; the traffic
is all greedy).  The number compared is the widest gap over the sample,
per model, against the cell's limit for that model (the mean is read
too, for the record).

The control (``control=True``) puts the reference, computed in float8
(``reference.common``), in the program's place: at each position of the
same sequences it reads the gap, in the float32 reference, of the token
the float8 reference puts first.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.spec import family_module
from bench.lib.traffic import length_range, rng


def sample(stamps, model: str, k: int, seed: int) -> list[int]:
    """Up to ``k`` finished requests of ``model`` that have prompts on
    record: the longest (prompt plus served tokens) and the rest drawn
    from the seed.  Without finished ones, the requests with the most
    served tokens stand in."""
    recs = [r for r in stamps.records.values()
            if r.tenant == model and r.rid in stamps.prompts
            and len(stamps.outputs[r.rid]) > 0]
    done = [r for r in recs if r.finished]
    pool = done or sorted(recs, key=lambda r: -len(stamps.outputs[r.rid]))[:k]
    if not pool:
        return []
    size = {r.rid: r.prompt_len + len(stamps.outputs[r.rid]) for r in pool}
    longest = max(size, key=lambda rid: (size[rid], -rid))
    rest = sorted(rid for rid in size if rid != longest)
    pick = rng(seed, 4).permutation(len(rest))[:k - 1]
    return [longest] + sorted(rest[i] for i in pick)


def sequences(stamps, rids: list[int], rows: int, width: int):
    """Token, target and mask arrays (rows, width) for the reference:
    position ``p - 1 + j`` of a request with a ``p``-token prompt predicts
    its ``j``-th served token."""
    toks = np.zeros((rows, width), np.int32)
    tgt = np.zeros((rows, width), np.int32)
    mask = np.zeros((rows, width), bool)
    for i, rid in enumerate(rids):
        p = stamps.prompts[rid]
        out = np.asarray(stamps.outputs[rid], np.int32)
        seq = np.concatenate([p, out[:-1]])[:width]
        toks[i, :len(seq)] = seq
        n = min(len(out), width - len(p) + 1)
        tgt[i, len(p) - 1:len(p) - 1 + n] = out[:n]
        mask[i, len(p) - 1:len(p) - 1 + n] = True
    return toks, tgt, mask


def gaps(family: str, spec: dict, weights: dict, toks, tgt, mask,
         control: bool) -> dict:
    """{"program": {"max", "mean"}} of the served tokens' gaps and, with
    ``control``, {"control": {"max", "mean"}} of the gaps of the float8
    reference's top tokens, both in the float32 reference's logits."""
    ref = family_module("reference", family)
    t, m = jnp.asarray(toks), jnp.asarray(mask)
    logits = ref.logits(spec, weights, t)
    out = {"program": _stats(logits, jnp.asarray(tgt), m)}
    if control:
        top = jnp.argmax(ref.logits(spec, weights, t, quant=True), -1)
        out["control"] = _stats(logits, top.astype(jnp.int32), m)
    return out


def _stats(logits, idx, mask) -> dict:
    widest, mean = _gap(logits, idx, mask)
    return {"max": float(widest), "mean": float(mean)}


@jax.jit
def _gap(logits, idx, mask):
    """Widest and mean ``max(logits) - logits[idx]`` over the masked
    positions."""
    top = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, idx[..., None], -1)[..., 0]
    gap = jnp.where(mask, top - got, 0.0)
    return jnp.max(gap), jnp.sum(gap) / jnp.maximum(jnp.sum(mask), 1)


def seq_width(traffic: dict) -> int:
    """Reference sequence length of a cell: longest prompt plus its
    served tokens, rounded up to 128."""
    n = length_range(traffic["prompt_len"])[1] + \
        length_range(traffic["output_len"])[1]
    return 128 * math.ceil(n / 128)
