"""Smoke run of the serving path on one TPU chip, at published widths.

    python chip_smoke.py [--seed N]

One process owns the chip for the whole run and starts no other.  Four
phases, each printing its own lines first:

1. device   — JAX's devices; anything but a TPU is a one-line failure.
2. single   — starcoder2-3b (bf16 weights drawn from ``--seed``) in one
   ``ServingEngine`` behind ``OnlineRuntime`` (wall clock, SLO scheduler):
   16 requests, prompts of 64-512 tokens, 32 new tokens each.
3. colocate — starcoder2-3b and mamba2-780m engines sharing the chip
   behind ``ClusterRuntime`` (wall clock).
4. pallas   — the compiled Pallas kernels (``dispatch`` mode "pallas"):
   a dense and a paged starcoder2-3b engine and a mamba2-780m engine
   serve a few requests at tile level 0, and their compiled executables
   must hold the kernels.  Then the same version-cache code, at the same
   tiles, runs the models' first ``CMP_LAYERS`` layers in both modes:
   prefill logits (dense, SSM) and the logits of one decode step on a
   dense row (dense) or through a page table (paged) must agree with
   "xla" mode.

Every request must return all its tokens, every token must lie in the
vocabulary, prefill logits must be finite, warmed engines must not trace
again while serving, and decode must have synced with the host.  Any
failed check exits non-zero.  The seconds and ``peak_bytes_in_use``
printed per phase are smoke timings of one run, compilation included —
not benchmark metrics.  On success the last line of standard output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SC, MB = "starcoder2-3b", "mamba2-780m"
SLOTS, MAX_LEN = 8, 2048
PAGE_SIZE = 16
# Phase 4 compares logits of the Pallas kernels against the XLA path run
# at float32 matmul precision ("highest"): relative L2 error of the logit
# vector, ||pallas - xla|| / ||xla||.  Both paths keep activations in
# bf16 (unit roundoff 2^-8 ~ 0.4%) but round at different points (the
# kernels accumulate whole tiles in f32 and cast once), so each layer
# adds an O(2^-8) relative error.  A random-weight model amplifies such
# differences with depth: on a CPU, where both paths compute in float32,
# mamba2-780m's Pallas-vs-XLA prefill logits differ by 5.2e-3 at 4
# layers, 2.9e-2 at 16 and 8.6e-2 at 48, and two mathematically
# identical XLA formulations (SSD chunk 16 vs 256) differ by 2.7e-2 at
# 16 layers.  Full-depth logits therefore cannot tell a kernel fault from
# that growth, so the comparison runs the first CMP_LAYERS layers (every
# width published), where 5e-2 is ten times the expected difference and
# still fails a wrong mask, kv head, page, chunk or state scale, which
# are O(1).  The XLA path at default precision is printed too but not
# held to this bound: on a TPU it rounds float32 matmul operands to bf16.
LOGIT_REL_TOL = 5e-2
CMP_LAYERS = 4
_KERNEL_CALL = re.compile(
    r'%([A-Za-z_]+)[.\d]* = [^\n]*custom_call_target="tpu_custom_call"')


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def report(phase: str, t0: float, dev, extra: str = "") -> None:
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use", "not reported")
    print(f"[{phase}] done: smoke time {time.perf_counter() - t0:.1f} s, "
          f"peak_bytes_in_use {peak}{extra}", flush=True)


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------
def check_outputs(tag: str, outputs: dict, rids, max_new: int,
                  vocab: int) -> int:
    """Every request returned max_new + 1 tokens (the prefill's first
    token plus max_new decoded), all inside the vocabulary."""
    n = 0
    for rid in rids:
        toks = outputs.get(rid)
        check(toks is not None, f"{tag}: request {rid} never finished")
        check(len(toks) == max_new + 1,
              f"{tag}: request {rid} returned {len(toks)} tokens, "
              f"expected {max_new + 1}")
        check(all(0 <= t < vocab for t in toks),
              f"{tag}: request {rid} has a token outside [0, {vocab})")
        n += len(toks)
    return n


def prompt(rng, n: int, vocab: int):
    import numpy as np
    return rng.integers(0, vocab, n).astype(np.int32)


def prefill(model, params, entry, toks, chunk: int = 16):
    """Prefill ``toks`` (a multiple of ``chunk`` long) through ``entry``'s
    chunked-prefill executable into a fresh row; -> (last logits (V,),
    the filled one-row cache)."""
    import jax.numpy as jnp
    import numpy as np
    row = model.init_cache(1, MAX_LEN)
    for s in range(0, len(toks), chunk):
        logits, row = entry.prefill_chunk(
            params, jnp.asarray(toks[s:s + chunk])[None], row,
            jnp.int32(s), jnp.int32(chunk))
    return np.asarray(logits[0], np.float32), row


def paged_row(model, row):
    """A one-row dense cache moved into page pools of PAGE_SIZE positions
    behind a page table that maps logical page i to physical page n - i
    (reversed, so a kernel that ignored the table would read the wrong
    keys); physical page 0 is the trash page."""
    import jax
    import jax.numpy as jnp

    from repro.models.model import path_keys
    axes = model.paged_leaf_axes()
    n = MAX_LEN // PAGE_SIZE
    phys = jnp.arange(n, 0, -1, dtype=jnp.int32)

    def pool(path, leaf):
        if path_keys(path) not in axes:
            return leaf
        ba, sa = axes[path_keys(path)]          # batch, seq axes of the row
        r = jnp.squeeze(leaf, ba)
        r = r.reshape(*r.shape[:sa - 1], n, PAGE_SIZE, *r.shape[sa:])
        r = jnp.moveaxis(r, sa - 1, ba)         # pages where the batch was
        shape = list(r.shape)
        shape[ba] = n + 1
        return jnp.zeros(shape, leaf.dtype).at[
            (slice(None),) * ba + (phys,)].set(r)
    cache = jax.tree_util.tree_map_with_path(pool, row)
    cache["page_table"] = phys[None]
    return cache


def depth_cut(cfg, params, n: int):
    """The first ``n`` layers of a model at its published widths: the
    config and a params tree whose stacked blocks are sliced (embedding,
    final norm and head shared)."""
    import dataclasses

    import jax
    n = min(n, cfg.num_layers)
    cut = dict(params)
    cut["blocks"] = jax.tree_util.tree_map(lambda p: p[:n], params["blocks"])
    return dataclasses.replace(cfg, num_layers=n), cut


def cut_logits(cfg, params, tiles, probe, paged: bool) -> dict:
    """Logits of ``cfg``/``params`` through a version-cache entry built
    for ``tiles`` in the current dispatch mode: the last prefill logits of
    ``probe``, then those of one decode step after it, on the dense row
    or (``paged``) through a page table."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serving.version_cache import VersionCache
    model = build_model(cfg)
    entry = VersionCache(model).get(tiles)
    first, row = prefill(model, params, entry, probe)
    cache = paged_row(model, row) if paged else row
    logits, _ = entry.decode(params, {"tokens": jnp.asarray(probe[:1])},
                             cache, jnp.int32(len(probe)))
    return {"prefill": first, "decode": np.asarray(logits[0], np.float32)}


def check_finite(tag: str, logits) -> None:
    import numpy as np
    check(bool(np.isfinite(logits).all()), f"{tag}: prefill logits not "
          "finite")


def init_params(cfg, seed: int):
    """bf16 parameters at published widths, drawn from ``seed``."""
    import jax

    from repro.models import build_model
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    return params


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_device():
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"no TPU: JAX could not start a backend "
                           f"({str(e).splitlines()[0]})") from None
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's device is {dev.platform} ({dev.device_kind})")
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devices)}, jax {jax.__version__}", flush=True)
    return dev, devices


def phase_single(dev, cfg, params, hw, seed: int) -> None:
    from repro.core.scheduler import VeltairPolicy
    from repro.serving import OnlineRuntime, Workload
    from repro.serving.engine import ServingEngine
    from repro.serving.tenants import cluster_plan

    import numpy as np
    t0 = time.perf_counter()
    engine = ServingEngine(cfg, params, batch_slots=SLOTS, max_len=MAX_LEN)
    stats = engine.warmup()
    traces = engine.version_cache.traces
    print(f"[single] {cfg.name}: warmup {time.perf_counter() - t0:.1f} s, "
          f"version cache {stats}", flush=True)
    n_req, max_new = 16, 32
    wl = Workload.poisson([cfg.name], 50.0, n_req, prompt_len=512,
                          prompt_len_spread=448, max_new_tokens=max_new,
                          seed=seed)
    runtime = OnlineRuntime(engine, VeltairPolicy(hw),
                            {cfg.name: cluster_plan(cfg.name, hw)}, hw,
                            wall_clock=True, scheduler="slo", seed=seed)
    t1 = time.perf_counter()
    runtime.serve(wl)
    serve_s = time.perf_counter() - t1
    n_tok = check_outputs("single", runtime.outputs, range(n_req), max_new,
                          cfg.vocab_size)
    check(engine.version_cache.traces == traces,
          f"single: {engine.version_cache.traces - traces} traces after "
          "warmup")
    check(engine.host_syncs > 0, "single: no host sync")
    toks = prompt(np.random.default_rng(seed), 512, cfg.vocab_size)
    check_finite("single", prefill(
        engine.model, params, engine.version_cache.get({}), toks)[0])
    print(f"[single] served {n_req} requests (prompts "
          f"{min(wl.prompt_lengths())}-{max(wl.prompt_lengths())} tokens), "
          f"{n_tok} tokens in {serve_s:.1f} s, host syncs "
          f"{engine.host_syncs}, traces after warmup 0", flush=True)
    report("single", t0, dev)


def phase_colocate(dev, cfgs, params, hw, seed: int) -> None:
    from repro.core.scheduler import VeltairPolicy
    from repro.serving import ClusterRuntime, EngineTenant, Workload, \
        cluster_plans
    from repro.serving.engine import ServingEngine

    import numpy as np
    t0 = time.perf_counter()
    names = list(cfgs)
    plans = cluster_plans(names, hw)
    tenants = [EngineTenant(
        name=a, plan=plans[a],
        engine=ServingEngine(cfgs[a], params[a], batch_slots=SLOTS,
                             max_len=MAX_LEN,
                             version_sets=plans[a].version_sets))
        for a in names]
    runtime = ClusterRuntime(tenants, VeltairPolicy(hw), hw,
                             wall_clock=True, seed=seed)
    runtime.warmup()
    traces = {t.name: t.engine.version_cache.traces for t in tenants}
    print(f"[colocate] warmup {time.perf_counter() - t0:.1f} s", flush=True)
    n_req, max_new = 8, 16
    wl = Workload.poisson(names, 50.0, n_req, prompt_len=256,
                          prompt_len_spread=192, max_new_tokens=max_new,
                          seed=seed + 1)
    t1 = time.perf_counter()
    runtime.serve(wl)
    serve_s = time.perf_counter() - t1
    owner = {rid: name for rid, (_, name) in enumerate(sorted(wl.arrivals))}
    rng = np.random.default_rng(seed + 1)
    for t in tenants:
        eng = t.engine
        rids = [r for r, name in owner.items() if name == t.name]
        check(rids, f"colocate: no request for {t.name}")
        n_tok = check_outputs(f"colocate/{t.name}", runtime.outputs, rids,
                              max_new, eng.cfg.vocab_size)
        check(eng.version_cache.traces == traces[t.name],
              f"colocate/{t.name}: traces after warmup")
        check(eng.host_syncs > 0, f"colocate/{t.name}: no host sync")
        check_finite(f"colocate/{t.name}", prefill(
            eng.model, eng.params, eng.version_cache.get({}),
            prompt(rng, 256, eng.cfg.vocab_size))[0])
        print(f"[colocate] {t.name}: {len(rids)} requests, {n_tok} tokens, "
              f"host syncs {eng.host_syncs}, traces after warmup 0",
              flush=True)
    print(f"[colocate] serve {serve_s:.1f} s", flush=True)
    report("colocate", t0, dev)


def kernels_in(text: str) -> set[str]:
    """Names of the Pallas kernels compiled into an executable."""
    return set(_KERNEL_CALL.findall(text))


def phase_pallas(dev, cfgs, params, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import dispatch
    from repro.serving.engine import Request, ServingEngine

    t0 = time.perf_counter()
    max_new, k_bucket = 16, 16
    dense_kernels = {"block_matmul", "flash_attention"}
    arms = (   # label, arch, engine kwargs, kernels: prefill, decode;
        #        the logits compared with "xla" mode
        ("dense", SC, {}, dense_kernels, dense_kernels,
         ("prefill", "decode")),
        ("paged", SC, {"page_size": PAGE_SIZE}, dense_kernels,
         {"block_matmul", "flash_attention_paged"}, ("decode",)),
        ("ssm", MB, {}, {"ssd_scan"}, set(), ("prefill",)),
    )
    rng = np.random.default_rng(seed + 2)
    probe = {arch: prompt(rng, 128, cfgs[arch].vocab_size) for arch in cfgs}
    cut = {arch: depth_cut(cfgs[arch], params[arch], CMP_LAYERS)
           for arch in cfgs}
    tiles, got = {}, {}
    dispatch.set_mode("pallas")
    try:
        for label, arch, kw, want_prefill, want_decode, _ in arms:
            cfg = cfgs[arch]
            eng = ServingEngine(cfg, params[arch], batch_slots=SLOTS,
                                max_len=MAX_LEN, **kw)
            eng.set_interference_level(0.0)
            eng.warmup(levels=[0.0], quantum_buckets=(k_bucket,))
            traces = eng.version_cache.traces
            # every row is admitted and prefilled before the one decode
            # quantum of max_new steps, so only the warmed K-bucket runs
            reqs = [Request(rid=i, prompt=prompt(rng, n, cfg.vocab_size),
                            max_new_tokens=max_new)
                    for i, n in enumerate((64, 128, 192, 256))]
            done = eng.run_to_completion(reqs)
            check_outputs(f"pallas/{label}", {r.rid: r.output for r in done},
                          range(len(reqs)), max_new, cfg.vocab_size)
            check(eng.version_cache.traces == traces,
                  f"pallas/{label}: traces after warmup")
            check(eng.host_syncs > 0, f"pallas/{label}: no host sync")
            entry = eng.version_cache.get(eng.tiles_for_level(0.0))
            row = eng.model.init_cache(1, MAX_LEN)
            prefill_text = entry.prefill_chunk.lower(
                eng.params, jnp.zeros((1, 16), jnp.int32), row,
                jnp.int32(0), jnp.int32(16)).compile().as_text()
            found = {"prefill": kernels_in(prefill_text),
                     "decode": kernels_in(entry.quanta[k_bucket].as_text())}
            for kind, want in (("prefill", want_prefill),
                               ("decode", want_decode)):
                check(want <= found[kind],
                      f"pallas/{label}: {kind} executable lacks "
                      f"{sorted(want - found[kind])} (has "
                      f"{sorted(found[kind])})")
            tiles[label] = entry.tiles
            print(f"[pallas] {label} {arch}: {len(done)} requests, kernels "
                  f"prefill {sorted(found['prefill'])}, decode "
                  f"{sorted(found['decode'])}", flush=True)
            del eng, entry, row
            gc.collect()
        for label, arch, kw, *_ in arms:
            got[label] = cut_logits(*cut[arch], tiles[label], probe[arch],
                                    paged="page_size" in kw)
    finally:
        dispatch.set_mode("xla")
    # the XLA path at both matmul precisions (see LOGIT_REL_TOL)
    ref = {}
    for label, arch, kw, *_ in arms:
        ref[label, "default"] = cut_logits(*cut[arch], {}, probe[arch],
                                           paged="page_size" in kw)
        with jax.default_matmul_precision("highest"):
            ref[label, "highest"] = cut_logits(*cut[arch], {}, probe[arch],
                                               paged="page_size" in kw)

    def rel(a, b) -> float:
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))
    for label, arch, *_, compared in arms:
        for kind in compared:
            mine = got[label][kind]
            hi, lo = ref[label, "highest"][kind], ref[label, "default"][kind]
            check_finite(f"pallas/{label}", mine)
            err = rel(mine, hi)
            print(f"[pallas] {label}: {kind} logits of "
                  f"{cut[arch][0].num_layers} layers, "
                  f"relative L2 error vs xla-highest {err:.3e} (tolerance "
                  f"{LOGIT_REL_TOL:.0e}); vs xla-default {rel(mine, lo):.3e};"
                  f" xla-default vs xla-highest {rel(lo, hi):.3e}; argmax "
                  f"{'agrees' if mine.argmax() == hi.argmax() else 'differs'}",
                  flush=True)
            check(err <= LOGIT_REL_TOL, f"pallas/{label}: {kind} logits "
                  f"differ from xla-highest by {err:.3e} > "
                  f"{LOGIT_REL_TOL:.0e}")
    report("pallas", t0, dev)


# --------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    events = {"hits": 0, "misses": 0}
    try:
        dev, devices = phase_device()

        import jax

        from repro.configs import get_config
        from repro.core import cost_model as cm
        from repro.launch.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        cache_files = (len(list(pathlib.Path(cache_dir).glob("*")))
                       if pathlib.Path(cache_dir).is_dir() else 0)

        def count(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                events["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                events["misses"] += 1
        jax.monitoring.register_event_listener(count)
        print(f"[device] compile cache {cache_dir} ({cache_files} entries "
              "at start)", flush=True)

        t0 = time.perf_counter()
        cfgs = {a: get_config(a) for a in (SC, MB)}
        params = {a: init_params(cfgs[a], args.seed + i)
                  for i, a in enumerate(cfgs)}
        report("params", t0, dev)
        hw = cm.TPU_V5E_POD
        phase_single(dev, cfgs[SC], params[SC], hw, args.seed)
        gc.collect()
        phase_colocate(dev, cfgs, params, hw, args.seed)
        gc.collect()
        phase_pallas(dev, cfgs, params, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"[device] compile cache: {events['hits']} hits, "
          f"{events['misses']} misses", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
