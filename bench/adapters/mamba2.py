"""Mamba-2 in the program: its ``ModelConfig`` from the configuration
file's published keys, and its parameter tree as the reference's weights.
"""
from __future__ import annotations

PROGRAM_NORM_EPS = 1e-6     # repro.models.layers.apply_norm's fixed eps


def model_config(name: str, spec: dict):
    """The program's config for ``spec``; refuses what it cannot run."""
    from repro.configs.base import ModelConfig, SSMConfig
    if spec["norm_epsilon"] != PROGRAM_NORM_EPS:
        raise ValueError(f"{name}: the program's norms use eps "
                         f"{PROGRAM_NORM_EPS}, the file states "
                         f"{spec['norm_epsilon']}")
    if spec["residual_in_fp32"] or not spec["rms_norm"] \
            or spec["norm_before_gate"]:
        raise ValueError(f"{name}: the program keeps a bfloat16 residual "
                         "and RMS norms after the gate")
    d = int(spec["d_model"])
    return ModelConfig(
        name=name, family="ssm", num_layers=int(spec["n_layer"]),
        d_model=d, num_heads=0, num_kv_heads=0, head_dim=0, d_ff=0,
        vocab_size=int(spec["vocab_size"]),
        ssm=SSMConfig(d_inner=int(spec["expand"]) * d,
                      head_dim=int(spec["headdim"]),
                      state_dim=int(spec["d_state"]),
                      num_groups=int(spec["ngroups"]),
                      conv_width=int(spec["d_conv"]),
                      chunk_size=int(spec["chunk_size"])),
        norm_type="rmsnorm", pos_embed="none",
        tie_embeddings=bool(spec["tie_embeddings"]))


def reference_weights(params) -> dict:
    """The program's parameter tree under the reference's names (the same
    arrays, no copy)."""
    b = params["blocks"]["ssm"]   # one scanned layer kind
    m = b["mixer"]
    return {"embedding": params["embed"]["embedding"],
            "final_w": params["final_norm"]["scale"],
            "norm_w": b["ln1"]["scale"], "in_proj": m["in_proj"],
            "conv_w": m["conv_w"], "conv_b": m["conv_b"],
            "dt_bias": m["dt_bias"], "A_log": m["A_log"], "D": m["D"],
            "gate_norm_w": m["norm"]["scale"], "out_proj": m["out_proj"]}
