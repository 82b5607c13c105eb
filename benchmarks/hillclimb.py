"""Perf hillclimbing driver — hypothesis -> change -> measure -> validate.

Two entry points share the greedy search core here:

* the roofline variant driver (``main``): measures a cell's roofline
  terms under named variants (sharding rules, config tweaks, train
  knobs) and appends records to results/hillclimb.jsonl.  The §Perf log
  in EXPERIMENTS.md is written from these records.

      PYTHONPATH=src:. python benchmarks/hillclimb.py --cell gemma-decode \
          --variant baseline seqshard

* the tile-ladder autotuner (:func:`search_tile_ladder`, driven by
  ``tools/autotune_ladder.py``): per interference-grid level, hillclimb
  the (bm, bk, bn) tile lattice of ``schedule_space.enumerate_versions``
  candidates under the analytic cost model, warm-started from the
  previous level's winner and constrained to a non-growing matmul
  working set — which makes the emitted :class:`LadderSpec` satisfy its
  exclusive->shared ordering invariant by construction.

The heavy roofline dependencies (mesh construction, model plans, the
512-device XLA host-platform flag) are imported lazily inside the
functions that need them, so importing this module for the search
helpers costs nothing.
"""
import argparse
import dataclasses
import json
import os

from repro.core import cost_model as cm
from repro.core import schedule_space as ss
from repro.core.multiversion import LadderSpec, _matmul_bytes


# -- greedy search core -------------------------------------------------------
def local_search(start, neighbors_fn, score_fn, max_iters: int = 64):
    """Greedy hillclimb from ``start``: move to the best-scoring neighbor
    while one improves.  Scores are memoized per state (states must be
    hashable).  Returns ``(best_state, best_score, iters)``."""
    scores: dict = {}

    def score(s):
        hit = scores.get(s)
        if hit is None:
            hit = scores[s] = score_fn(s)
        return hit

    cur, cur_score = start, score(start)
    iters = 0
    for _ in range(max_iters):
        cands = [n for n in neighbors_fn(cur) if n != cur]
        if not cands:
            break
        best = min(cands, key=score)
        if score(best) >= cur_score:
            break
        cur, cur_score = best, score(best)
        iters += 1
    return cur, cur_score, iters


def _attention_tiles(bm: int) -> dict:
    """Attention tiling derived from the matmul M-tile — the same
    footprint coupling ``DEFAULT_LEVEL_TILES`` uses (the search space is
    the GEMM lattice; attention follows its locality scale)."""
    return {"bq": max(int(bm), 64), "bkv": max(2 * int(bm), 128)}


def search_tile_ladder(layer: cm.GemmLayer, hw: cm.HardwareSpec, *,
                       tiles=ss.TILES, unrolls=ss.UNROLLS,
                       units: int | None = None,
                       name: str | None = None,
                       max_iters: int = 64) -> LadderSpec:
    """Autotune a full interference-level -> tile-table ladder for one
    representative layer.

    Per grid level: hillclimb the (bm, bk, bn) lattice minimizing
    ``cost_model.latency`` at that level's pressure, warm-started from
    the previous level's winner, with candidates restricted to a matmul
    working set no larger than that winner's.  The restriction is the
    ladder's validate() invariant, enforced during search rather than
    patched up after.
    """
    units = units or max(hw.n_units // 4, 1)
    cands = ss.enumerate_versions(layer, hw, tiles=tiles, unrolls=unrolls)
    if not cands:
        raise ValueError(f"no feasible tile candidates for {layer.name} "
                         f"on {hw.name}")
    # best version (over unroll) per tiling — the hillclimb walks tilings
    by_tiling: dict[tuple, cm.CodeVersion] = {}
    for v in cands:
        key = (v.bm, v.bk, v.bn)
        cur = by_tiling.get(key)
        if cur is None or cm.latency(hw, v, units, cm.Interference()) < \
                cm.latency(hw, cur, units, cm.Interference()):
            by_tiling[key] = v
    axes = tuple(sorted({k[i] for k in by_tiling}) for i in range(3))

    def neighbors(key):
        out = []
        for i in range(3):
            axis = axes[i]
            j = axis.index(key[i])
            for dj in (-1, 1):
                if 0 <= j + dj < len(axis):
                    nk = list(key)
                    nk[i] = axis[j + dj]
                    nk = tuple(nk)
                    if nk in by_tiling:
                        out.append(nk)
        return out

    def bytes_of(key) -> int:
        return _matmul_bytes({"matmul": {"bm": key[0], "bk": key[1],
                                         "bn": key[2]}})

    levels, scores = [], []
    prev_key, cap = None, None
    for itf in cm.level_grid():
        def score(key):
            if cap is not None and bytes_of(key) > cap:
                return float("inf")
            return cm.latency(hw, by_tiling[key], units, itf)

        if prev_key is None:
            start = min(by_tiling, key=score)
        else:
            start = prev_key          # warm start: always feasible (== cap)
        best, best_s, _ = local_search(start, neighbors, score,
                                       max_iters=max_iters)
        bm, bk, bn = best
        levels.append({"matmul": {"bm": bm, "bk": bk, "bn": bn},
                       "attention": _attention_tiles(bm)})
        scores.append(float(best_s))
        prev_key, cap = best, bytes_of(best)

    spec = LadderSpec(
        name=name or f"{layer.name}@{hw.name}", hw=hw.name,
        levels=levels, scores=scores,
        meta={"layer": layer.name, "units": units,
              "m": layer.m, "k": layer.k, "n": layer.n,
              "tiles": [int(t) for t in tiles],
              "unrolls": [int(u) for u in unrolls]})
    spec.validate()
    return spec


# -- roofline variant driver (heavy imports stay lazy) ------------------------
def _roofline():
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=512")
    import benchmarks.roofline as R
    return R


def measure_variant(arch: str, shape_name: str, *, rules=None, cfg=None,
                    accum: int | None = None, label: str = "baseline"):
    """Roofline terms for one cell variant (d1/d2 extrapolated)."""
    R = _roofline()
    from repro.configs import get_config, get_shape
    from repro.launch.mesh import make_production_mesh
    from repro.models.model import make_plan

    base_cfg = cfg or get_config(arch)
    shape = get_shape(shape_name)
    plan = make_plan(base_cfg)
    mesh = make_production_mesh()
    eff_accum = accum if accum is not None else (
        R.TRAIN_KNOBS[arch][1] if shape.mode == "train" else 1)
    mb_shape = (dataclasses.replace(
        shape, global_batch=max(shape.global_batch // eff_accum, 1))
        if eff_accum > 1 else shape)

    def meas(groups):
        return R._measure(arch, shape_name, R._depth_cfg(base_cfg, groups),
                          mesh, mb_shape, rules=rules)

    d1, d2 = meas(1), meas(2)
    totals = {k: (d1[k] + (plan.n_groups - 1) * (d2[k] - d1[k])) * eff_accum
              for k in ("flops", "bytes", "link")}
    rec = {
        "cell": f"{arch}x{shape_name}", "variant": label,
        "accum": eff_accum,
        "compute_s": totals["flops"] / R.PEAK_FLOPS,
        "memory_s": totals["bytes"] / R.HBM_BW,
        "collective_s": totals["link"] / R.LINK_BW,
    }
    rec["bound_s"] = max(rec["compute_s"], rec["memory_s"],
                         rec["collective_s"])
    rec["dominant"] = max(
        ("compute", rec["compute_s"]), ("memory", rec["memory_s"]),
        ("collective", rec["collective_s"]), key=lambda kv: kv[1])[0]
    with open(os.path.join(R.RESULTS, "hillclimb.jsonl"), "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"[hillclimb] {rec['cell']} {label}: "
          f"comp={rec['compute_s']*1e3:.2f}ms mem={rec['memory_s']*1e3:.2f}ms "
          f"coll={rec['collective_s']*1e3:.2f}ms dom={rec['dominant']}",
          flush=True)
    return rec


# named variants --------------------------------------------------------------
def gemma_decode(variants):
    arch, shp = "gemma-2b", "decode_32k"
    if "baseline" in variants:
        measure_variant(arch, shp, label="baseline")
    if "seqshard" in variants:
        # context-parallel decode: shard the KV-cache sequence axis over
        # the (otherwise idle, kv_heads=1) model axis
        from repro.dist import sharding as shd
        rules = shd.make_rules("serve", False, seq_parallel=True)
        measure_variant(arch, shp, rules=rules, label="seqshard-kv")


def arctic_train(variants):
    arch, shp = "arctic-480b", "train_4k"
    if "baseline" in variants:
        measure_variant(arch, shp, label="baseline(accum16)")
    for v in variants:
        if v.startswith("accum"):
            measure_variant(arch, shp, accum=int(v[5:]),
                            label=f"accum{int(v[5:])}")


def deepseek_decode(variants):
    """Decode takes the absorbed MLA form (chosen from its one-token
    shape); ``seqshard`` also shards the latent cache's positions."""
    arch, shp = "deepseek-v2-lite-16b", "decode_32k"
    from repro.dist import sharding as shd
    if "baseline" in variants:
        measure_variant(arch, shp, label="baseline(absorbed-mla)")
    if "seqshard" in variants:
        rules = shd.make_rules("serve", False, seq_parallel=True)
        measure_variant(arch, shp, rules=rules, label="mla-absorb+seqshard")


CELLS = {"gemma-decode": gemma_decode, "arctic-train": arctic_train,
         "deepseek-decode": deepseek_decode}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, choices=sorted(CELLS))
    ap.add_argument("--variant", nargs="+", default=["baseline"])
    args = ap.parse_args()
    CELLS[args.cell](args.variant)


if __name__ == "__main__":
    main()
