"""Mixture-of-Experts layer: dropless top-k routing, computed for the
routed experts this chip holds.

The router scores every routed expert, at its published width and in
float32; each token picks its ``top_k`` experts by softmax probability
(greedy), and the picked probabilities are its gates, divided by their
sum where the config says so (``MoEConfig.norm_topk_prob``).  The layer
returns the part of the result that the held experts give,

    y_t = sum over the picks e of token t that this chip holds:
          gate_te * expert_e(x_t),

so the parts of all chips of an expert-parallel deployment add up to the
whole layer.  It is one gate-masked product over the held experts: each
runs on every token of the call, and a token's gate on an expert it did
not pick is zero.  No token is dropped, so a row's output does not depend
on the rows beside it in its batch or chunk.  At serving sizes (at most
96 tokens a call, 8 held experts) nearly every held expert is picked by
some token of each call, so the product reads each held expert's weights
once, as a routed dispatch would, at T FLOPs per weight byte.

Shared experts and Arctic's dense residual branch are applied in the
model assembly, not here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.params import ParamSpec

# routing counts a layer returns, summed over its real tokens:
# picks over all experts, picks that land on held experts, and the token
# count of the busiest held expert
N_ROUTING_COUNTS = 3


def moe_specs(cfg: ModelConfig, moe: MoEConfig) -> dict:
    """Router over all routed experts; weights of the held ones.  Expert
    weights state their fan-in scale: the default initializer would read
    the expert axis as the fan-in."""
    m, f, h = cfg.d_model, moe.expert_d_ff, moe.n_held
    return {
        "router": ParamSpec((m, moe.num_experts), jnp.float32,
                            ("embed", None)),
        "w_gate": ParamSpec((h, m, f), axes=("expert", "embed", "mlp"),
                            init_scale=m ** -0.5),
        "w_up": ParamSpec((h, m, f), axes=("expert", "embed", "mlp"),
                          init_scale=m ** -0.5),
        "w_down": ParamSpec((h, f, m), axes=("expert", "mlp", "embed"),
                            init_scale=f ** -0.5),
    }


def route(router: jax.Array, x: jax.Array, moe: MoEConfig):
    """Tokens ``x (T, M)`` -> (probs (T, E), picks (T, k) int32, gates
    (T, k) float32)."""
    logits = jnp.einsum("tm,me->te", x.astype(jnp.float32), router,
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, picks = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-20)
    return probs, picks, gates


def apply_moe(params: dict, x: jax.Array, cfg: ModelConfig, moe: MoEConfig,
              mask: jax.Array | None = None,
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """-> (held experts' part of the output (B,S,M), load-balance loss
    scalar fp32, routing counts int32 (N_ROUTING_COUNTS,)).

    ``mask`` (B,S) bool marks the real tokens the counts cover (pad
    positions of a prefill chunk and frozen rows of a decode quantum are
    computed but not counted); None counts every token."""
    b, s, m = x.shape
    e, k, h, h0 = moe.num_experts, moe.top_k, moe.n_held, \
        moe.first_held_expert
    xt = x.reshape(b * s, m)
    probs, picks, gates = route(params["router"], xt, moe)

    # each token's gate on each held expert, zero where it did not pick it
    held = jax.nn.one_hot(picks - h0, h, dtype=jnp.float32)   # (T,k,H)
    held_gates = jnp.einsum("tkh,tk->th", held, gates)

    dt = x.dtype
    g = jnp.einsum("tm,hmf->htf", xt, params["w_gate"].astype(dt))
    u = jnp.einsum("tm,hmf->htf", xt, params["w_up"].astype(dt))
    act = jax.nn.silu if cfg.activation != "geglu" else (
        lambda a: jax.nn.gelu(a, approximate=True))
    y = jnp.einsum("htf,hfm->htm", act(g.astype(jnp.float32)).astype(dt) * u,
                   params["w_down"].astype(dt))
    out = jnp.einsum("htm,th->tm", y.astype(jnp.float32), held_gates)

    # switch-style load-balance loss over all experts (training)
    frac = jnp.mean(jnp.sum(jax.nn.one_hot(picks, e, dtype=jnp.float32),
                            axis=1), axis=0) / k
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

    real = (jnp.ones((b * s,), jnp.int32) if mask is None
            else mask.reshape(b * s).astype(jnp.int32))
    per_expert = jnp.einsum("tkh,t->h", held.astype(jnp.int32), real)
    counts = jnp.stack([k * jnp.sum(real), jnp.sum(per_expert),
                        jnp.max(per_expert)])
    return out.astype(dt).reshape(b, s, m), aux.astype(jnp.float32), counts
