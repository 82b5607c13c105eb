"""The DeepSeek-V2-Lite cell of the benchmark at a size a CPU test can
serve: the configuration's published keys with every width cut, and a
tiny copy of the cell (its traffic shrunk as ``benchtools`` shrinks the
backlog) written under a root."""
from __future__ import annotations

import copy
import json
import pathlib

from benchtools import ROOT

CONFIG = json.loads((ROOT / "bench/configs/dsv2-lite-ep8.json").read_text())
NAME = "deepseek-v2-lite-16b"
SPEC = CONFIG["models"][NAME]
CELL = "dsv2l-gen-backlog"

# 16 routed experts of which 4 are held, from the fifth on (a share that
# does not start at expert 0), top-6 as published; the published rope
# scaling is kept
TINY = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
            n_routed_experts=4, router_experts=16, first_held_expert=4,
            num_hidden_layers=3, vocab_size=256,
            max_position_embeddings=256)
TINY_SPEC = dict(SPEC, **TINY)

# The tiny cell's limit on the widest gap, from its own readings on the
# CPU: the sound program reads 0.004 to 0.031 (seeds 1-6), the float8
# control 0.22 to 0.35 (seeds 1-3).
TINY_LIMIT = 0.1


def write_tiny_root(root: pathlib.Path,
                    limit: float = TINY_LIMIT) -> pathlib.Path:
    """BENCHMARK.json and the cell's data files, the model cut to
    ``TINY_SPEC`` and the backlog to 64 short requests in groups of 4."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    cfg = copy.deepcopy(CONFIG)
    cfg["models"] = {NAME: dict(TINY_SPEC)}
    cfg["engine"] = {NAME: {"batch_slots": 4, "max_len": 256}}
    (root / "bench/configs" / f"{work['config']}.json").write_text(
        json.dumps(cfg))
    traffic = json.loads((ROOT / "bench/traffic" /
                          f"{work['traffic']}.json").read_text())
    traffic.update(n_requests=64, prompt_len=[8, 24], output_len=[8, 40],
                   group=4)
    (root / "bench/traffic" / f"{work['traffic']}.json").write_text(
        json.dumps(traffic))
    cell = json.loads((ROOT / "bench/cells" / f"{CELL}.json").read_text())
    cell["correct"]["limits"] = {NAME: limit}
    (root / "bench/cells" / f"{CELL}.json").write_text(json.dumps(cell))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
