"""Batched serving demo: continuous batching over the decode step.

  PYTHONPATH=src python examples/serve_acim.py --arch qwen2_5_3b
"""
import argparse
import time

import jax

from repro.configs import registry as creg
from repro.models.registry import build_model
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_3b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=8)
    args = ap.parse_args()

    cfg = creg.reduced(args.arch)
    api = build_model(cfg)
    params = api.init(jax.random.key(0))
    eng = ServeEngine(cfg, params, slots=args.slots, max_seq=128)
    for uid in range(args.requests):
        eng.submit(Request(uid=uid, prompt=[3 + uid, 7, 11],
                           max_new=args.max_new))
    t0 = time.time()
    done = eng.run(max_steps=512)
    dt = time.time() - t0
    toks = sum(len(c.tokens) for c in done)
    print(f"{len(done)} completions, {toks} tokens in {dt:.1f}s "
          f"({toks / dt:.1f} tok/s, {args.slots} slots)")
    for c in sorted(done, key=lambda c: c.uid):
        print(f"  req {c.uid}: {c.tokens}")


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
