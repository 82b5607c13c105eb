"""Operations and bytes that StarCoder2 inference needs, from what each
call really did (not from the shapes the program compiled).

* FLOPs: real tokens only.  Per token and layer, 2 per weight of the
  linear layers plus 4 * H * hd per attended position (Q.K and P.V over
  the causal, windowed context); 2 * D * V for each token whose logits
  are needed (the last token of a prompt, every decoded token; a prefill
  chunk that does not end its prompt needs none).
* Bytes: weights (bf16 matrices, f32 norms and biases) read once per
  prefill call and once per decode step actually needed; the tied
  embedding read whole once per such call or step that needs logits,
  and otherwise only its rows of the call's tokens; KV cache
  (bf16) over the valid positions of active rows only: read the earlier
  positions, write the new ones.
"""
from __future__ import annotations


def _dims(s: dict):
    d, h = int(s["hidden_size"]), int(s["num_attention_heads"])
    hd = d // h
    return (d, h, int(s["num_key_value_heads"]), hd,
            int(s["intermediate_size"]), int(s["vocab_size"]),
            int(s["num_hidden_layers"]),
            int(s.get("sliding_window") or 1 << 30))


def _linear_weights(s: dict) -> int:
    d, h, k, hd, f, _, _, _ = _dims(s)
    return d * h * hd + 2 * d * k * hd + h * hd * d + 2 * d * f


def _weight_bytes(s: dict) -> int:
    d, _, _, _, f, v, layers, _ = _dims(s)
    small = 4 * (4 * d + f + d)             # two LayerNorms, two biases
    return layers * (2 * _linear_weights(s) + small) + 2 * v * d


def _token(s: dict, ctx: int) -> tuple[int, int]:
    """(FLOPs of the layers, KV bytes) for one token seeing ``ctx``
    positions (itself included)."""
    d, h, k, hd, _, _, layers, window = _dims(s)
    seen = min(ctx, window)
    flops = layers * (2 * _linear_weights(s) + 4 * h * hd * seen)
    kv = layers * 2 * k * hd * 2 * seen
    return flops, kv


def prefill(s: dict, start: int, n: int,
            last: bool = True) -> tuple[float, float]:
    """One prefill call: ``n`` real tokens at positions start..start+n-1;
    ``last``: the call ends its prompt, so its logits are needed."""
    d, _, _, _, _, v, _, _ = _dims(s)
    head = 2 * d * v if last else 0   # logits FLOPs; bf16 table bytes
    flops = head
    for p in range(start, start + n):
        f, _ = _token(s, p + 1)
        flops += f
    _, kv = _token(s, start + n)            # the row's valid positions
    byts = _weight_bytes(s) - 2 * v * d + head + kv + 2 * n * d
    return float(flops), float(byts)


def decode(s: dict, rows, steps: int) -> tuple[float, float]:
    """One decode quantum: ``rows`` = [(position, steps taken)], each row
    decoding one token per step from its position; ``steps`` = the most
    any row needed."""
    d, _, _, _, _, v, _, _ = _dims(s)
    flops = byts = 0
    for pos, n in rows:
        for j in range(n):
            f, kv = _token(s, pos + j + 1)
            flops += f + 2 * d * v
            byts += kv + 2 * d
    return float(flops), float(byts + steps * _weight_bytes(s))
