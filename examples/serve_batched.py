"""Serve a small model with batched requests: prefill + greedy decode.

The same ``ServeEngine`` steps that the decode_32k / long_500k dry-runs lower
to the 512-chip mesh, executed eagerly on CPU for a reduced model.

    PYTHONPATH=src python examples/serve_batched.py --arch mamba2-2.7b
    PYTHONPATH=src python examples/serve_batched.py --arch gemma-2b --new 24
"""
import argparse
import sys

sys.path.insert(0, "src")

import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import use_compile_cache
from repro.launch.serve import ServeEngine
from repro.models.transformer import DecoderLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    args = ap.parse_args()

    cfg = reduced(ARCHS[args.arch])
    if cfg.arch_type == "audio":
        raise SystemExit("use a decoder arch for this example")
    model = DecoderLM(cfg, attn_impl="dense", remat=False)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model)

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab_size)
    cache_len = args.prompt_len + args.new

    t0 = time.time()
    out = engine.generate(params, prompts, max_new=args.new,
                          cache_len=cache_len, dtype=jnp.float32)
    dt = time.time() - t0
    toks = args.batch * args.new
    print(f"arch={args.arch} ({cfg.arch_type}, reduced)  batch={args.batch}  "
          f"prompt={args.prompt_len}  new={args.new}")
    print(f"generated {toks} tokens in {dt:.2f}s  ({toks/dt:.1f} tok/s on CPU)")
    for i in range(min(2, args.batch)):
        print(f"  req{i}: ...{list(map(int, prompts[i, -4:]))} -> "
              f"{list(map(int, out[i]))}")


if __name__ == "__main__":
    use_compile_cache()
    main()
