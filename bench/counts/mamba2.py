"""Operations and bytes that Mamba-2 inference needs, from what each call
really did (not from the shapes the program compiled).

* FLOPs: real tokens only.  Per token and layer, 2 per weight of
  ``in_proj`` and ``out_proj``, 2 per conv tap and channel, and 5 per
  state element of the recurrence (decay, input outer product, add;
  output contraction); 2 * D * V for each token whose logits are needed
  (the last token of a prompt, every decoded token; a prefill chunk that
  does not end its prompt needs none).
* Bytes: weights read once per prefill call and once per decode step
  actually needed; the tied embedding whole once per such call or step
  that needs logits, and otherwise only its rows of the call's tokens; the
  recurrent state of each active row (f32 SSM state, bf16 conv window)
  read and written once per prefill call and once per decoded token.
"""
from __future__ import annotations


def _dims(s: dict):
    d = int(s["d_model"])
    di = int(s["expand"]) * d
    n, g, p = int(s["d_state"]), int(s["ngroups"]), int(s["headdim"])
    return (d, di, n, g, di // p, p, int(s["d_conv"]), int(s["vocab_size"]),
            int(s["n_layer"]))


def _per_token_flops(s: dict) -> int:
    d, di, n, g, h, p, w, _, layers = _dims(s)
    conv_ch = di + 2 * g * n
    in_proj = d * (2 * di + 2 * g * n + h)
    return layers * (2 * (in_proj + di * d) + 2 * w * conv_ch
                     + 5 * h * p * n)


def _weight_bytes(s: dict) -> int:
    d, di, n, g, h, p, w, v, layers = _dims(s)
    conv_ch = di + 2 * g * n
    mats = 2 * (d * (2 * di + 2 * g * n + h) + di * d)
    small = 4 * (w * conv_ch + conv_ch + 3 * h + di + d)
    return layers * (mats + small) + 2 * v * d


def _state_bytes(s: dict) -> int:
    """One row's recurrent state, read and written."""
    d, di, n, g, h, p, w, _, layers = _dims(s)
    return 2 * layers * (4 * h * p * n + 2 * (w - 1) * (di + 2 * g * n))


def prefill(s: dict, start: int, n: int,
            last: bool = True) -> tuple[float, float]:
    """One prefill call of ``n`` real tokens; ``last``: the call ends its
    prompt, so its logits are needed."""
    d, _, _, _, _, _, _, v, _ = _dims(s)
    head = 2 * d * v if last else 0   # logits FLOPs; bf16 table bytes
    flops = n * _per_token_flops(s) + head
    byts = (_weight_bytes(s) - 2 * v * d + head + _state_bytes(s)
            + 2 * n * d)
    return float(flops), float(byts)


def decode(s: dict, rows, steps: int) -> tuple[float, float]:
    d, _, _, _, _, _, _, v, _ = _dims(s)
    tokens = sum(n for _, n in rows)
    flops = tokens * (_per_token_flops(s) + 2 * d * v)
    byts = tokens * (_state_bytes(s) + 2 * d) + steps * _weight_bytes(s)
    return float(flops), float(byts)
