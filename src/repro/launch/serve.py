"""Serving launcher: batched prefill + decode on a reduced config (CPU) or
the production mesh (TPU).

  PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
      --batch 4 --prompt-len 16 --steps 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_transformer
from repro.serving.engine import decode_step, prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="glm4-9b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--max-len", type=int, default=0)
    ap.add_argument("--kernel", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    max_len = args.max_len or (args.prompt_len + args.steps)
    params = init_transformer(jax.random.key(args.seed), cfg)
    prompt = jax.random.randint(jax.random.key(args.seed + 1),
                                (args.batch, args.prompt_len), 0,
                                cfg.vocab_size)
    embeds = None
    if cfg.frontend != "none":
        embeds = jax.random.normal(
            jax.random.key(args.seed + 2),
            (args.batch, min(cfg.num_frontend_tokens, 8), cfg.d_model),
            jnp.dtype(cfg.dtype)) * 0.02

    t0 = time.time()
    logits, st = jax.jit(
        lambda p, t, e: prefill(p, cfg, t, max_len=max_len, embeds=e))(
            params, prompt, embeds)
    print(f"prefill: {args.batch}x{args.prompt_len} in "
          f"{time.time() - t0:.2f}s")

    step = jax.jit(lambda p, t, s: decode_step(p, cfg, t, s,
                                               decode_kernel=args.kernel))
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [tok]
    t0 = time.time()
    for _ in range(args.steps):
        logits, st = step(params, tok, st)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(tok)
    dt = time.time() - t0
    print(f"decode: {args.steps} steps × {args.batch} seqs in {dt:.2f}s "
          f"({args.steps * args.batch / dt:.1f} tok/s)")
    print("sample:", jnp.stack(outs, 1)[0][:16].tolist())


if __name__ == "__main__":
    main()
