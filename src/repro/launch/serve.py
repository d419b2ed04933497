"""Serving launcher: batched greedy generation with a registry arch.

  python -m repro.launch.serve --arch gemma3-4b-smoke --batch 4 \
      --prompt-len 16 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import frontends, transformer
from repro.serve import ServeEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-4b-smoke")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    key = jax.random.PRNGKey(args.seed)
    params = transformer.init_params(key, cfg)
    engine = ServeEngine(cfg, params,
                         max_len=args.prompt_len + args.new_tokens)
    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    extra = {}
    if cfg.frontend == "audio":
        extra["frames"] = frontends.audio_frames(key, cfg, args.batch)
    elif cfg.frontend == "vision":
        extra["patch_embeds"] = frontends.vision_patches(key, cfg, args.batch)
    t0 = time.time()
    out = engine.generate(prompts, new_tokens=args.new_tokens,
                          temperature=args.temperature, key=key,
                          extra_batch=extra)
    dt = time.time() - t0
    total = args.batch * args.new_tokens
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({total / dt:.1f} tok/s incl. prefill+compile)")
    print(out[:2])


if __name__ == "__main__":
    main()
