"""The program's serving-loop spans, read back from a trace: their
arguments add up to the engines' own counters, the readers of the span
metrics reduce them, the step programs keep the jitted names the trace
reduction keys on, and a recorded chip trace reduces to its numbers."""
from __future__ import annotations

import collections
import glob
import json
import re

import jax
import jax.numpy as jnp
import pytest

import benchtools  # noqa: F401  (puts the checkout on the path)
from bench.lib import serve, spans, trace, traffic
from bench.lib.context import Context
from bench.lib.spec import load_cell, metric_reader
from repro.serving import spans as program_spans

SEED = 2**31 + 7
N_REQUESTS = 8
METRICS = ("engine.decode_slot_yield.batch",
           "engine.prefill_held_share.batch",
           "runtime.steps_per_quantum.batch")


def _newest_trace(trace_dir) -> str:
    return sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb",
                            recursive=True))[-1]


@pytest.fixture(scope="module")
def served(tiny_root, tmp_path_factory):
    """The tiny backlog cell served through ``ClusterRuntime`` (fused
    quanta, no speculation) with the profiler open over the whole serve:
    (engine, counter deltas, loaded trace, trace directory)."""
    cell = load_cell("sc2-gen-backlog", tiny_root)
    tenants = serve.build_tenants(cell.config, SEED, lambda msg: None)
    engines = serve.build_engines(cell.config, tenants,
                                  serve.plans_for(cell.config, tenants))
    requests = traffic.generate(cell.traffic, [t.name for t in tenants],
                                1.0, SEED)[:N_REQUESTS]
    runtime = serve.make_runtime(cell.config, engines, SEED)
    eng = engines[0].engine
    keys = ("tokens_decoded", "quantum_calls", "prefill_chunks",
            "prefill_tokens")
    before = {k: getattr(eng, k) for k in keys}
    trace_dir = tmp_path_factory.mktemp("spans_trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        runtime.serve(serve.workload(requests, SEED))
    finally:
        jax.profiler.stop_trace()
    delta = {k: getattr(eng, k) - before[k] for k in keys}
    assert len(runtime.outputs) == N_REQUESTS
    return eng, delta, trace.load(_newest_trace(trace_dir)), trace_dir


def test_dispatch_spans_add_up_to_the_engine_counters(served):
    _, delta, data, _ = served
    quanta = spans.dispatches(data)
    chunks = spans.spans(data, program_spans.PREFILL_DISPATCH)
    finished = sum(int(c["finished"]) for c in chunks)
    assert finished == N_REQUESTS
    assert len(quanta) == delta["quantum_calls"] > 0
    assert sum(int(q["tokens"]) for q in quanta) == \
        delta["tokens_decoded"] - finished
    assert len(chunks) == delta["prefill_chunks"]
    assert sum(int(c["tokens"]) for c in chunks) == delta["prefill_tokens"]
    for q in quanta:
        assert q["kind"] == "decode"
        assert 1 <= int(q["steps"]) <= int(q["bucket"]) <= 16
        assert int(q["steps"]) <= int(q["asked"])
        assert int(q["rows"]) + int(q["prefilling"]) <= int(q["slots"])
        assert int(q["tokens"]) <= int(q["rows"]) * int(q["steps"])


def test_spans_nest_and_link_by_request(served):
    _, _, data, _ = served
    names = collections.Counter(
        ev.name for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith(trace.SPAN_PREFIXES))
    known = {v for k, v in vars(program_spans).items() if k.isupper()}
    assert set(names) <= known
    n_quanta = names[program_spans.QUANTUM_DISPATCH]
    assert names[program_spans.QUANTUM_SYNC] == n_quanta
    assert names[program_spans.QUANTUM_BOOKKEEP] == n_quanta
    assert names[program_spans.PREFILL_SYNC] == N_REQUESTS
    assert names[program_spans.RUNTIME_TICK] >= \
        n_quanta + names[program_spans.PREFILL_DISPATCH]
    admitted = {int(a["rid"])
                for a in spans.spans(data, program_spans.ENGINE_ADMIT)}
    prefilled = {int(c["rid"]) for c in
                 spans.spans(data, program_spans.PREFILL_DISPATCH)}
    assert admitted == prefilled and len(admitted) == N_REQUESTS


def test_span_readers_give_the_counter_sums(served, monkeypatch):
    eng, delta, data, trace_dir = served
    monkeypatch.setattr(spans, "TRACE_DIR", trace_dir)
    quanta = spans.dispatches(data)
    steps = sum(int(q["steps"]) for q in quanta)
    finished = sum(int(c["finished"]) for c in
                   spans.spans(data, program_spans.PREFILL_DISPATCH))
    slot_steps = eng.slots * steps
    want = {
        "engine.decode_slot_yield.batch":
            100.0 * (delta["tokens_decoded"] - finished) / slot_steps,
        "engine.prefill_held_share.batch":
            100.0 * sum(int(q["prefilling"]) * int(q["steps"])
                        for q in quanta) / slot_steps,
        "runtime.steps_per_quantum.batch": steps / delta["quantum_calls"],
    }
    ctx = Context(stamps=None, counters0={}, counters1={}, end=0.0,
                  specs={}, peaks={}, reduction=object())
    for name in METRICS:
        assert metric_reader(name)(ctx) == want[name], name
    untraced = Context(stamps=None, counters0={}, counters1={}, end=0.0,
                       specs={}, peaks={})
    for name in METRICS:
        assert metric_reader(name)(untraced) is None, name


def test_span_readers_are_silent_without_dispatch_spans(tmp_path,
                                                        monkeypatch):
    jax.profiler.start_trace(str(tmp_path))
    try:
        jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    ctx = Context(stamps=None, counters0={}, counters1={}, end=0.0,
                  specs={}, peaks={}, reduction=object())
    for name in METRICS:
        assert metric_reader(name)(ctx) is None, name


def _module_name(hlo_text: str) -> str:
    return re.search(r"(?:HloModule|module @)\s*([A-Za-z0-9_.]+)",
                     hlo_text).group(1)


def test_step_programs_keep_the_names_the_reduction_keys_on(served):
    """A rename of ``prefill_chunk`` or ``qfn`` in
    ``serving/version_cache.py`` would silence the rooflines."""
    eng, _, _, _ = served
    width = eng.prefill_buckets[-1]
    chunk = eng._entry.prefill_chunk.lower(
        eng.params, jnp.zeros((1, width), jnp.int32), eng._empty_row,
        jnp.int32(0), jnp.int32(width)).as_text()
    quantum = eng.version_cache.quantum(eng._entry, 1, eng.params,
                                        eng.cache, eng.slots).as_text()
    assert trace.kind_of(_module_name(chunk)) == "prefill"
    assert trace.kind_of(_module_name(quantum)) == "decode"


DATA = benchtools.ROOT / "tests/bench/data"


@pytest.fixture(scope="module")
def recorded():
    """A chip trace of sc2-gen-backlog with the program's spans, its
    reduction, and the numbers recorded with it."""
    data = trace.load(str(DATA / "spans_trace.xplane.pb.gz"))
    expected = json.loads((DATA / "spans_trace.expected.json").read_text())
    return data, trace.reduce(data), expected


def test_recorded_trace_span_metrics(recorded):
    data, _, expected = recorded
    quanta = spans.dispatches(data)
    assert len(quanta) == expected["quanta"]
    got = {"engine.decode_slot_yield.batch": spans.slot_yield(quanta),
           "engine.prefill_held_share.batch":
               spans.prefill_held_share(quanta),
           "runtime.steps_per_quantum.batch":
               spans.steps_per_quantum(quanta)}
    for name in METRICS:
        assert got[name] == pytest.approx(expected["metrics"][name],
                                          rel=1e-12), name
    assert 1 <= got["runtime.steps_per_quantum.batch"] <= 16
    assert got["engine.decode_slot_yield.batch"] \
        + got["engine.prefill_held_share.batch"] <= 100


def test_recorded_trace_idle_gaps_by_program_span(recorded):
    _, red, expected = recorded
    assert red.busy_s == pytest.approx(expected["busy_s"], rel=1e-9)
    assert [g[0] for g in red.idle_gaps] == \
        [g[0] for g in expected["idle_gaps"]]
    for (_, got), (name, want) in zip(red.idle_gaps, expected["idle_gaps"]):
        assert got == pytest.approx(want, rel=1e-9), name
    children = ("engine.quantum.", "engine.prefill.", "runtime.tick",
                "runtime.admit", "runtime.counters")
    assert any(g[0].startswith(children) for g in red.idle_gaps)
