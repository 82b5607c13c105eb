"""DeepSeek-V2 in the program: its ``ModelConfig`` from the configuration
file's published keys, and its parameter tree as the reference's weights.

The file states this chip's share of an expert-parallel deployment:
``n_routed_experts`` experts held here, from ``first_held_expert`` on, of
the ``router_experts`` the router scores (the published count).
"""
from __future__ import annotations

import dataclasses

PROGRAM_NORM_EPS = 1e-6     # repro.models.layers.apply_norm's fixed eps

# what the configuration must state for the program to run it as published
PUBLISHED_AS_RUN = {"hidden_act": "silu", "scoring_func": "softmax",
                    "topk_method": "greedy", "n_group": 1, "topk_group": 1,
                    "routed_scaling_factor": 1, "moe_layer_freq": 1,
                    "first_k_dense_replace": 1,
                    "attention_bias": False, "q_lora_rank": None,
                    "tie_word_embeddings": False, "torch_dtype": "bfloat16"}


def model_config(name: str, spec: dict):
    """The program's config for ``spec``; refuses at once, with a
    ``ValueError``, what the program cannot run."""
    from repro.configs import base
    if not hasattr(base, "YarnConfig"):
        raise ValueError(f"{name}: the program has no YaRN rope scaling")
    held_fields = {"held_experts", "first_held_expert", "norm_topk_prob"}
    if not held_fields <= {f.name for f in
                           dataclasses.fields(base.MoEConfig)}:
        raise ValueError(f"{name}: the program's MoE layer cannot hold a "
                         "share of the routed experts")
    for key, want in PUBLISHED_AS_RUN.items():
        if spec.get(key) != want:
            raise ValueError(f"{name}: the program runs {key}={want!r}, "
                             f"the file states {spec.get(key)!r}")
    if spec["rms_norm_eps"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{name}: the program's norms use eps "
                         f"{PROGRAM_NORM_EPS}, the file states "
                         f"{spec['rms_norm_eps']}")
    heads = int(spec["num_attention_heads"])
    if int(spec["num_key_value_heads"]) != heads:
        raise ValueError(f"{name}: MLA gives every head its own key")
    rs = spec["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"{name}: rope scaling {rs.get('type')!r}")
    held, first = int(spec["n_routed_experts"]), int(spec["first_held_expert"])
    routed = int(spec["router_experts"])
    if not 0 <= first < first + held <= routed:
        raise ValueError(f"{name}: experts {first}..{first + held - 1} are "
                         f"not among the {routed} routed")
    f = int(spec["moe_intermediate_size"])
    return base.ModelConfig(
        name=name, family="moe",
        num_layers=int(spec["num_hidden_layers"]),
        d_model=int(spec["hidden_size"]), num_heads=heads,
        num_kv_heads=heads, head_dim=int(spec["v_head_dim"]), d_ff=f,
        vocab_size=int(spec["vocab_size"]),
        moe=base.MoEConfig(
            num_experts=routed, top_k=int(spec["num_experts_per_tok"]),
            expert_d_ff=f, num_shared_experts=int(spec["n_shared_experts"]),
            shared_d_ff=int(spec["n_shared_experts"]) * f,
            norm_topk_prob=bool(spec["norm_topk_prob"]),
            held_experts=held, first_held_expert=first),
        mla=base.MLAConfig(
            kv_lora_rank=int(spec["kv_lora_rank"]),
            qk_nope_head_dim=int(spec["qk_nope_head_dim"]),
            qk_rope_head_dim=int(spec["qk_rope_head_dim"]),
            v_head_dim=int(spec["v_head_dim"]), q_lora_rank=None,
            yarn=base.YarnConfig(
                factor=float(rs["factor"]),
                original_max_position_embeddings=int(
                    rs["original_max_position_embeddings"]),
                beta_fast=float(rs["beta_fast"]),
                beta_slow=float(rs["beta_slow"]),
                mscale=float(rs["mscale"]),
                mscale_all_dim=float(rs["mscale_all_dim"]))),
        first_dense_layers=1,
        first_dense_d_ff=int(spec["intermediate_size"]),
        activation="swiglu", norm_type="rmsnorm", pos_embed="rope",
        rope_theta=float(spec["rope_theta"]), tie_embeddings=False)


def _attn(a: dict, ln1: dict) -> dict:
    return {"ln1": ln1["scale"], "wq": a["wq"], "w_dkv": a["w_dkv"],
            "kv_norm": a["kv_norm"]["scale"], "w_uk": a["w_uk"],
            "w_uv": a["w_uv"], "wo": a["wo"]}


def reference_weights(params) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays, no copy): ``dense`` (the leading dense layer, unstacked) and
    ``moe`` (the MoE layers, stacked on a leading axis)."""
    p0 = params["pro_0"]
    b = params["blocks"]["moe_ds"]
    return {
        "embedding": params["embed"]["embedding"],
        "unembed": params["embed"]["unembed"],
        "final_w": params["final_norm"]["scale"],
        "dense": {**_attn(p0["attn"], p0["ln1"]), "ln2": p0["ln2"]["scale"],
                  "w_gate": p0["mlp"]["w_gate"], "w_up": p0["mlp"]["w_up"],
                  "w_down": p0["mlp"]["w_down"]},
        "moe": {**_attn(b["attn"], b["ln1"]), "ln2": b["ln2"]["scale"],
                "router": b["moe"]["router"],
                "e_gate": b["moe"]["w_gate"], "e_up": b["moe"]["w_up"],
                "e_down": b["moe"]["w_down"],
                "s_gate": b["shared"]["w_gate"], "s_up": b["shared"]["w_up"],
                "s_down": b["shared"]["w_down"]},
    }
