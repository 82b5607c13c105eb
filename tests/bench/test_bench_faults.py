"""A whole run at a tiny size on the CPU (past the harness's look for a
chip), sound, with the timed path broken underneath, and with the float8
control judged in the program's place: ``correct`` must hold for the
sound program and fail for the control and for each fault a serving
cell can have."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

import bench.run as bench_run
import repro.launch.compile_cache as compile_cache
from bench.lib.spec import load_cell
from repro.serving.engine import ServingEngine
from repro.serving.version_cache import VersionCache

PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_cell(root, name: str, seed: int, monkeypatch,
             control: bool = False) -> dict:
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: PEAKS)
    # tests keep JAX's persistent compilation cache off
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    return bench_run.run(load_cell(name, root), seed, 3.0, False,
                         jax.devices(), control=control)


@pytest.mark.parametrize("name", ["colo-chat-burst", "sc2-gen-backlog"])
def test_sound_program_is_correct(tiny_root, name, monkeypatch):
    res = run_cell(tiny_root, name, 2**32 + 11, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    gaps = [k for k in res["checks"] if k.startswith("gap.")]
    assert len(gaps) == len(load_cell(name, tiny_root).config["models"])


def test_token_altered_where_produced_is_caught(tiny_root, monkeypatch):
    finish = ServingEngine.finish_quantum

    def altered(self, handle):
        reqs = ([self.slot_req[i] for i in handle.active]
                if handle is not None else [])
        done = finish(self, handle)
        for r in reqs:                     # the newest token of each row
            r.output[-1] = (r.output[-1] + 1) % self.cfg.vocab_size
        return done
    monkeypatch.setattr(ServingEngine, "finish_quantum", altered)
    res = run_cell(tiny_root, "sc2-gen-backlog", 5, monkeypatch)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_in_the_programs_place_is_not_correct(
        tiny_root, seed, monkeypatch):
    res = run_cell(tiny_root, "sc2-gen-backlog", seed, monkeypatch,
                   control=True)
    assert res["correct"] is False, res["checks"]


def test_step_returning_its_state_unchanged_is_caught(tiny_root,
                                                     monkeypatch):
    quantum = VersionCache.quantum

    def frozen(self, entry, k, params, cache, batch):
        fn = quantum(self, entry, k, params, cache, batch)

        def step(params, tokens, cache, pos, n_left):
            kept = jax.tree_util.tree_map(jnp.copy, cache)   # cache donated
            block, _, new_pos = fn(params, tokens, cache, pos, n_left)
            return block, kept, new_pos
        return step
    monkeypatch.setattr(VersionCache, "quantum", frozen)
    res = run_cell(tiny_root, "sc2-gen-backlog", 6, monkeypatch)
    assert res["correct"] is False, res["checks"]
