"""Batched serving driver: prefill a prompt batch, decode N tokens.

CPU demo with smoke configs; the same step functions lower for the
production mesh in dryrun.py (decode_32k / long_500k cells).

  PYTHONPATH=src python -m repro.launch.serve --arch hymba-1.5b --steps 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config, get_smoke
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serve.serve_step import generate


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hymba-1.5b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--sample", default="greedy", choices=["greedy", "temp"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = (get_smoke if args.preset == "smoke" else get_config)(args.arch)
    cfg = cfg.scaled(remat=False)
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)
    if cfg.family == "audio":
        toks = rng.integers(0, cfg.vocab_size,
                            (args.batch, args.prompt_len, cfg.num_codebooks))
    else:
        toks = rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len))
    prompt = {"tokens": jnp.asarray(toks.astype(np.int32))}
    if cfg.family == "vlm":
        prompt["patches"] = jnp.asarray(
            rng.standard_normal((args.batch, cfg.num_patches, cfg.d_model)),
            jnp.bfloat16)

    t0 = time.perf_counter()
    out = generate(model, params, prompt, steps=args.steps,
                   sample=args.sample,
                   key=jax.random.key(args.seed + 1))
    out = jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    tps = args.batch * args.steps / dt
    print(f"{args.arch}: generated {out.shape} in {dt:.2f}s "
          f"({tps:.1f} tok/s incl. compile)")
    print("first sequence:", np.asarray(out)[0].tolist()[:16])


if __name__ == "__main__":
    main()
