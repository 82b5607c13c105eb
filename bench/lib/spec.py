"""What a run measures, read by name from the benchmark's data files.

``BENCHMARK.json`` at the checkout root names every cell, configuration
and metric.  Everything that belongs to one of them sits in a file of its
own, found by that name:

    bench/configs/<config>.json   model sizes, engine arguments, `reduced`
    bench/traffic/<traffic>.json  parameters of the one traffic generator
    bench/cells/<cell>.json       drain cap, correctness sample and limits
    bench/metrics/<metric>.py     reader of one per-layer metric

so a new cell, configuration or metric is a new file and never an edit.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class SpecError(Exception):
    """A name in BENCHMARK.json has no file, or a file is malformed."""


def _load_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None
    except json.JSONDecodeError as e:
        raise SpecError(f"{path.relative_to(ROOT)}: {e}") from None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    cell: dict              # bench/cells/<cell>.json
    end_to_end: list[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: list[dict]


def reports(metric: dict, cell: str, e2e_names: set[str]) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists, or, without the key, every cell that reports the
    end-to-end metric it moves (end-to-end metrics without the key are
    reported everywhere)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    base = root / "bench"
    e2e = [m for m in bench["end_to_end"] if reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if reports(m, name, names)]
    return Cell(name=name, chips=int(w["chips"]),
                config=_load_json(root / cfg_entry["file"]),
                traffic=_load_json(base / "traffic" / f"{w['traffic']}.json"),
                cell=_load_json(base / "cells" / f"{name}.json"),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, root: pathlib.Path = ROOT):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.is_file():
        raise SpecError(f"metric {name!r} has no reader at "
                        f"{path.relative_to(root)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@functools.cache
def family_module(kind: str, family: str):
    """``bench/<kind>/<family>.py`` (kind: adapters, reference, counts)."""
    path = BENCH / kind / f"{family}.py"
    if not path.is_file():
        raise SpecError(f"family {family!r} has no {kind} module at "
                        f"{path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{family}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
