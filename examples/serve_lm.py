"""Batched serving demo: prefill + lock-step decode over request lanes.

    PYTHONPATH=src python examples/serve_lm.py --arch llama3.2-3b
(uses the arch's reduced smoke config so it runs on one CPU)
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro import configs
from repro.models import lm
from repro.serve.lm_engine import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--lanes", type=int, default=4)
    ap.add_argument("--steps", type=int, default=24)
    args = ap.parse_args()

    cfg = configs.get_smoke_config(args.arch)
    if cfg.encoder_only:
        raise SystemExit(f"{args.arch} is encoder-only - no decode")
    params = lm.init_model(jax.random.PRNGKey(0), cfg)
    engine = ServeEngine(cfg=cfg, params=params, max_len=128,
                         temperature=0.8)

    key = jax.random.PRNGKey(7)
    prompts = jax.random.randint(key, (args.lanes, 12), 0, cfg.vocab)
    t0 = time.perf_counter()
    out = engine.generate(prompts, num_steps=args.steps, key=key)
    dt = time.perf_counter() - t0
    total = args.lanes * args.steps
    print(f"[serve] {args.arch} ({cfg.name}): {args.lanes} lanes x "
          f"{args.steps} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s incl. compile)")
    for i in range(args.lanes):
        print(f"  lane {i}: {out[i, :12].tolist()} ...")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
