"""Operations and bytes that DeepSeek-V2 inference needs on one chip's
share of its routed experts, from what each call really did (not from
the shapes the program compiled).

* FLOPs: real tokens only.  Per token and layer: 2 per weight of the
  attention projections (``wq``, ``w_dkv``, the token's own key and value
  expansion ``w_uk``/``w_uv``, ``wo``), plus ``2 * H * (dn + dr + dv)``
  per attended position (Q.K and P.V over the causal context); layer 0's
  dense SwiGLU; in each MoE layer the router over all routed experts and
  the shared experts.  Routed experts count only the picks that land on
  experts held here, ``2 * 3 * D * f`` each, which the caller passes as
  ``held`` (the program's ``moe_held`` counts); the gate-masked products
  the program computes for experts a token did not pick are not counted.
  ``2 * D * V`` for each token whose logits are needed (the last token of
  a prompt, every decoded token; a prefill chunk that does not end its
  prompt needs none).
* Bytes: weights (bf16 matrices, f32 norms and router) read once per
  prefill call and once per decode step actually needed, the held
  experts' weights among them; the untied output matrix once per call or
  step that needs logits; of the embedding only the rows of the call's
  tokens; the latent cache (bf16, ``r + dr`` a position and layer) over
  the valid positions of active rows only: read the earlier positions,
  write the new ones.
"""
from __future__ import annotations


def _dims(s: dict):
    return (int(s["hidden_size"]), int(s["num_attention_heads"]),
            int(s["qk_nope_head_dim"]), int(s["qk_rope_head_dim"]),
            int(s["v_head_dim"]), int(s["kv_lora_rank"]),
            int(s["vocab_size"]), int(s["num_hidden_layers"]))


def _attn_weights(s: dict) -> int:
    d, h, dn, dr, dv, r, _, _ = _dims(s)
    return d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv) + h * dv * d


def _dense_ffn(s: dict) -> int:
    return 3 * int(s["hidden_size"]) * int(s["intermediate_size"])


def _shared_ffn(s: dict) -> int:
    return 3 * int(s["hidden_size"]) * int(s["n_shared_experts"]) \
        * int(s["moe_intermediate_size"])


def _router(s: dict) -> int:
    return int(s["hidden_size"]) * int(s["router_experts"])


def expert_weights(s: dict) -> int:
    """Weights of one routed expert."""
    return 3 * int(s["hidden_size"]) * int(s["moe_intermediate_size"])


def routed_flops(s: dict, held: int) -> float:
    """FLOPs of ``held`` picks that land on experts held here."""
    return 2.0 * expert_weights(s) * held


def param_count(s: dict, routed_experts: int | None = None) -> int:
    """Parameters with ``routed_experts`` routed experts in each MoE layer
    (default: the ``n_routed_experts`` held here)."""
    d, _, _, _, _, r, v, layers = _dims(s)
    e = int(s["n_routed_experts"]) if routed_experts is None \
        else routed_experts
    norms = 2 * d + r
    moe = _attn_weights(s) + norms + _router(s) + _shared_ffn(s) \
        + e * expert_weights(s)
    return 2 * v * d + d + _attn_weights(s) + norms + _dense_ffn(s) \
        + (layers - 1) * moe


def _weight_bytes(s: dict) -> int:
    """Every weight but the embedding and output matrices, once."""
    d, _, _, _, _, r, _, layers = _dims(s)
    norms = 4 * (2 * d + r)
    moe = 2 * (_attn_weights(s) + _shared_ffn(s)
               + int(s["n_routed_experts"]) * expert_weights(s)) \
        + 4 * _router(s) + norms
    return 2 * (_attn_weights(s) + _dense_ffn(s)) + norms \
        + (layers - 1) * moe + 4 * d


def _token(s: dict, ctx: int) -> tuple[int, int]:
    """(FLOPs of the layers but the routed experts, latent-cache bytes)
    for one token seeing ``ctx`` positions (itself included)."""
    d, h, dn, dr, dv, r, _, layers = _dims(s)
    per_layer = 2 * _attn_weights(s) + 2 * h * (dn + dr + dv) * ctx
    flops = layers * per_layer + 2 * _dense_ffn(s) \
        + (layers - 1) * 2 * (_router(s) + _shared_ffn(s))
    return flops, layers * (r + dr) * 2 * ctx


def prefill(s: dict, start: int, n: int, last: bool = True,
            held: int = 0) -> tuple[float, float]:
    """One prefill call: ``n`` real tokens at positions start..start+n-1,
    ``held`` of their picks on experts held here; ``last``: the call ends
    its prompt, so its logits are needed."""
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    head = 2 * d * v if last else 0   # logits FLOPs; bf16 matrix bytes
    flops = head + routed_flops(s, held)
    for p in range(start, start + n):
        flops += _token(s, p + 1)[0]
    _, kv = _token(s, start + n)            # the row's valid positions
    byts = _weight_bytes(s) + head + kv + 2 * n * d
    return float(flops), float(byts)


def decode(s: dict, rows, steps: int, held: int = 0) -> tuple[float, float]:
    """One decode quantum: ``rows`` = [(position, steps taken)], each row
    decoding one token per step from its position; ``steps`` = the most
    any row needed; ``held`` picks on experts held here."""
    d, v = int(s["hidden_size"]), int(s["vocab_size"])
    flops, byts = routed_flops(s, held), 0
    for pos, n in rows:
        for j in range(n):
            f, kv = _token(s, pos + j + 1)
            flops += f + 2 * d * v
            byts += kv + 2 * d
    return float(flops), float(byts + steps * (_weight_bytes(s) + 2 * d * v))
