"""Serve a small model with batched requests (prefill + streaming decode).

    PYTHONPATH=src python examples/serve_lm.py --arch llama3.2-1b
"""
import argparse
import os

import jax
import numpy as np

from repro.models.model import build
from repro.serve.engine import Engine
from repro.launch.runtime_env import enable_compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    enable_compile_cache(ROOT)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    model = build(args.arch, smoke=True)   # reduced config on CPU
    params = model.init(jax.random.PRNGKey(0))
    s_max = args.prompt_len + args.max_new
    eng = Engine(model, params, args.batch, s_max)

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, model.cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    out = eng.generate(prompts, max_new=args.max_new)
    print(f"arch={model.cfg.name} (smoke config)")
    print(f"generated {out.shape} tokens")
    print(f"prefill: {eng.stats.prefill_s*1e3:.1f} ms  decode: "
          f"{eng.stats.decode_s*1e3:.1f} ms "
          f"({eng.stats.tokens_per_s:.1f} tok/s)")
    for b in range(args.batch):
        print(f"  req{b}: {out[b].tolist()}")


if __name__ == "__main__":
    main()
