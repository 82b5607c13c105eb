"""StarCoder2 in the program: its ``ModelConfig`` from the configuration
file's published keys, and its parameter tree as the reference's weights.
"""
from __future__ import annotations

PROGRAM_NORM_EPS = 1e-6     # repro.models.layers.apply_norm's fixed eps


def model_config(name: str, spec: dict):
    """The program's config for ``spec``; refuses what it cannot run."""
    from repro.configs.base import ModelConfig
    if spec["norm_epsilon"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{name}: the program's norms use eps "
                         f"{PROGRAM_NORM_EPS}, the file states "
                         f"{spec['norm_epsilon']}")
    if spec["use_bias"]:
        raise ValueError(f"{name}: the program has no attention biases")
    if spec["hidden_act"] != "gelu_pytorch_tanh":
        raise ValueError(f"{name}: activation {spec['hidden_act']!r}")
    if spec["torch_dtype"] != "bfloat16":
        raise ValueError(f"{name}: the program serves bfloat16")
    heads = int(spec["num_attention_heads"])
    return ModelConfig(
        name=name, family="dense",
        num_layers=int(spec["num_hidden_layers"]),
        d_model=int(spec["hidden_size"]), num_heads=heads,
        num_kv_heads=int(spec["num_key_value_heads"]),
        head_dim=int(spec["hidden_size"]) // heads,
        d_ff=int(spec["intermediate_size"]),
        vocab_size=int(spec["vocab_size"]), activation="gelu",
        norm_type="layernorm", pos_embed="rope",
        rope_theta=float(spec["rope_theta"]),
        sliding_window=spec["sliding_window"],
        tie_embeddings=bool(spec["tie_word_embeddings"]))


def reference_weights(params) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays, no copy)."""
    b = params["blocks"]["dense"]   # one scanned layer kind
    return {"embedding": params["embed"]["embedding"],
            "final_w": params["final_norm"]["scale"],
            "final_b": params["final_norm"]["bias"],
            "ln1_w": b["ln1"]["scale"], "ln1_b": b["ln1"]["bias"],
            "wq": b["attn"]["wq"], "wk": b["attn"]["wk"],
            "wv": b["attn"]["wv"], "wo": b["attn"]["wo"],
            "ln2_w": b["ln2"]["scale"], "ln2_b": b["ln2"]["bias"],
            "w_up": b["mlp"]["w_up"], "b_up": b["mlp"]["b_up"],
            "w_down": b["mlp"]["w_down"], "b_down": b["mlp"]["b_down"]}

