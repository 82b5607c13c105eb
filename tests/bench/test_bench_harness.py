"""The harness's own contract: no CPU fallback, no run without the
program, and cells and metrics found by name from new files."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchtools import ROOT
from bench.lib import spec


def run_bench(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sc2-gen-backlog",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_exits_without_a_result_on_the_cpu():
    p = run_bench(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_exits_without_a_result_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = run_bench(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_cell_and_metric_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]))
        for model in cell.config["models"].values():
            for kind in ("adapters", "reference", "counts"):
                spec.family_module(kind, model["family"])


def test_new_cell_and_metric_are_found_by_name(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    (tiny_root / "bench/traffic/chat-steady.json").write_text(json.dumps(
        dict(json.loads((tiny_root / "bench/traffic/chat-burst.json")
                        .read_text()), burstiness=0.0)))
    (tiny_root / "bench/cells/colo-chat-steady.json").write_text(
        (tiny_root / "bench/cells/colo-chat-burst.json").read_text())
    (tiny_root / "bench/metrics").mkdir(exist_ok=True)
    (tiny_root / "bench/metrics/engine.new_counter.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench["workloads"].append({"name": "colo-chat-steady",
                               "config": "colo-sc2-mb2",
                               "traffic": "chat-steady", "chips": 1,
                               "why": "steady"})
    for m in bench["end_to_end"]:
        if m["name"] == "ttft_p90_s":
            m["workloads"].append("colo-chat-steady")
    bench["per_layer"].append({"name": "engine.new_counter", "unit": "count",
                               "better": "lower", "source": "program_counter",
                               "layer": "engine", "moves": "ttft_p90_s"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("colo-chat-steady", tiny_root)
    assert cell.traffic["burstiness"] == 0.0
    names = [m["name"] for m in cell.per_layer]
    assert names == ["engine.new_counter"]        # no workloads key: moves
    assert spec.metric_reader("engine.new_counter", tiny_root)(None) == 42.0
    burst = spec.load_cell("colo-chat-burst", tiny_root)
    assert "engine.new_counter" in [m["name"] for m in burst.per_layer]
    backlog = spec.load_cell("sc2-gen-backlog", tiny_root)
    assert "engine.new_counter" not in [m["name"] for m in backlog.per_layer]


def test_unknown_names_are_refused():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such-cell")
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")
