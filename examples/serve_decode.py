"""Serving example: prefill a batch of requests, then decode with the
per-family KV/state caches — runs any assigned arch in its reduced form.

    PYTHONPATH=src python examples/serve_decode.py --arch mamba2-1.3b --tokens 16
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import build_model


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--quick", action="store_true",
                    help="1-seq short prompt/decode (CI smoke-test sizing, "
                         "tests/test_examples.py)")
    args = ap.parse_args(argv)
    if args.quick:
        args.batch, args.prompt_len, args.tokens = 1, 8, 3

    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    max_len = args.prompt_len + args.tokens + 1

    B = args.batch
    prompts = jax.random.randint(jax.random.key(1), (B, args.prompt_len),
                                 0, cfg.vocab_size)
    extras = {k: jax.random.normal(jax.random.key(2), shp, jnp.float32)
              for k, shp in model.extra_input_shapes(B, args.prompt_len).items()}

    prefill = jax.jit(make_prefill_step(model, max_cache_len=max_len))
    decode = jax.jit(make_decode_step(model))

    t0 = time.time()
    batch = {"tokens": prompts, **extras}
    logits, caches = prefill(params, batch)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    print(f"[prefill] {B} x {args.prompt_len} tokens in {time.time()-t0:.1f}s "
          f"({args.arch}, reduced)")

    out = [tok]
    t0 = time.time()
    for i in range(args.tokens - 1):
        pos = jnp.asarray(args.prompt_len + i, jnp.int32)
        logits, caches = decode(params, tok, caches, pos,
                                extras=extras or None)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
        out.append(tok)
    dt = time.time() - t0
    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"[decode ] {args.tokens} tokens x {B} seqs in {dt:.1f}s "
          f"({args.tokens * B / max(dt, 1e-9):.1f} tok/s on 1 CPU core)")
    for b in range(min(B, 2)):
        print(f"  seq {b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
