"""Shared helpers of the benchmark's CPU tests: the checkout root on the
path, the published configurations the tests read, and a tiny copy of
the benchmark (same cells, traffic kinds and metric names, widths small
enough for a CPU) written under a root."""
from __future__ import annotations

import copy
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# starcoder2-3b as the benchmark's configuration runs it, and mamba2-780m
# as published (arXiv:2405.21060; the program's values where it departs),
# which no cell serves yet but whose reference and counts the benchmark
# keeps for a co-located cell
MODELS = {
    **json.loads((ROOT / "bench/configs/sc2-3b.json").read_text())["models"],
    **json.loads((ROOT / "tests/bench/data/mamba2-780m.json").read_text())}

TINY_SC2 = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
                num_hidden_layers=2, intermediate_size=128, vocab_size=256,
                max_position_embeddings=256)
TINY_MB2 = dict(d_model=64, n_layer=2, vocab_size=256, d_state=16,
                headdim=16, chunk_size=16)
TINY_ENGINE = {"batch_slots": 4, "max_len": 256}

# A test-only open-loop cell of two co-located tenants: the harness's
# open-loop path, tail metrics and the mamba2 modules at a tiny size.
COLO = {
    "config": {"name": "colo-sc2-mb2", "models": MODELS,
               "engine": {k: dict(TINY_ENGINE) for k in MODELS},
               "runtime": {"policy": "VeltairPolicy",
                           "hardware": "TPU_V5E_POD", "wall_clock": True}},
    "traffic": {"kind": "open_loop", "rate_per_s": 4.0, "burstiness": 2.0,
                "interval_s": 1.0,
                "popularity": {"starcoder2-3b": 0.75, "mamba2-780m": 0.25},
                "prompt_len": [16, 48], "output_len": 8},
    "cell": {"drain_cap_s": 60,
             "correct": {"sample": {k: 4 for k in MODELS},
                         "limits": {k: 0.0 for k in MODELS}}},
    "workload": {"name": "colo-chat-burst", "config": "colo-sc2-mb2",
                 "traffic": "chat-burst", "chips": 1, "why": "test only"},
    "end_to_end": [
        {"name": "ttft_p90_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["colo-chat-burst"]},
        {"name": "tpot_p90_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock", "workloads": ["colo-chat-burst"]}],
}


def tiny_models(models: dict) -> dict:
    out = {}
    for name, m in models.items():
        out[name] = dict(m, **(TINY_SC2 if m["family"] == "starcoder2"
                               else TINY_MB2))
    return out


# The tiny cells' limit on the widest gap, from their own readings on the
# CPU: the sound program reads 0 to 0.0023 (backlog, seeds 1-5), the
# float8 control 0.0141 to 0.0232 on the same seeds.
TINY_LIMIT = 0.008


def write_tiny_root(root: pathlib.Path,
                    limit: float = TINY_LIMIT) -> pathlib.Path:
    """BENCHMARK.json and data files of the real benchmark, shrunk, plus
    the test-only open-loop cell ``colo-chat-burst``."""
    bench = copy.deepcopy(json.loads((ROOT / "BENCHMARK.json").read_text()))
    for d in ("configs", "traffic", "cells"):
        (root / "bench" / d).mkdir(parents=True, exist_ok=True)
    configs = {c["name"]: json.loads((ROOT / c["file"]).read_text())
               for c in bench["configs"]}
    traffic = {w["traffic"]: json.loads(
        (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
        for w in bench["workloads"]}
    cells = {w["name"]: json.loads(
        (ROOT / "bench" / "cells" / f"{w['name']}.json").read_text())
        for w in bench["workloads"]}
    configs["colo-sc2-mb2"] = copy.deepcopy(COLO["config"])
    traffic["chat-burst"] = dict(COLO["traffic"])
    cells["colo-chat-burst"] = copy.deepcopy(COLO["cell"])
    bench["configs"].append({"name": "colo-sc2-mb2",
                             "file": "bench/configs/colo-sc2-mb2.json"})
    bench["workloads"].append(dict(COLO["workload"]))
    bench["end_to_end"] = COLO["end_to_end"] + bench["end_to_end"]
    for c in bench["configs"]:
        cfg = configs[c["name"]]
        cfg["models"] = tiny_models(cfg["models"])
        cfg["engine"] = {k: dict(TINY_ENGINE) for k in cfg["models"]}
        (root / c["file"]).write_text(json.dumps(cfg))
    for name, tr in traffic.items():
        if tr["kind"] == "backlog":
            tr.update(n_requests=64, prompt_len=[8, 24], output_len=[8, 40],
                      group=4)
        (root / "bench" / "traffic" / f"{name}.json").write_text(
            json.dumps(tr))
    for name, cell in cells.items():
        cell["correct"]["limits"] = {k: limit
                                     for k in cell["correct"]["limits"]}
        (root / "bench" / "cells" / f"{name}.json").write_text(
            json.dumps(cell))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
