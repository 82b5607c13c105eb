"""Serving entry point: batched requests through the ServingEngine.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
        --requests 8 --prompt-len 16 --max-new 8
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config, get_reduced_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(cfg, params, batch_slots=args.slots,
                           max_len=args.prompt_len + args.max_new + 2)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    t0 = time.time()
    done = engine.run_to_completion(reqs)
    dt = time.time() - t0
    toks = sum(len(r.output) for r in done)
    print(f"[serve] {cfg.name}: {len(done)}/{args.requests} requests, "
          f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s)")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.output[:10]}")


if __name__ == "__main__":
    main()
