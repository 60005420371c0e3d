"""Batched serving of a fine-tuned (base + global LoRA) model: chunked
prefill through the cached sequence path, then greedy batched decode — the
inference path the decode_32k / long_500k dry-run shapes exercise.

The KV cache carries **per-slot** positions, so prefill feeds whole prompt
chunks (``--prefill-chunk`` tokens per jitted call) instead of one token per
step, and heterogeneous batch rows could ride different ring offsets.

  PYTHONPATH=src python examples/serve_batch.py [--arch qwen2-0.5b] \
      [--batch 4] [--prompt-len 16] [--gen 24] [--window 0] \
      [--prefill-chunk 8] [--int8-cache]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config, lora_targets
from repro.models import transformer as T
from repro.peft.lora import init_lora
from repro.train.step import make_serve_step


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window size (0 = full attention)")
    ap.add_argument("--prefill-chunk", type=int, default=8,
                    help="prompt tokens fed per jitted prefill call")
    ap.add_argument("--int8-cache", action="store_true")
    ap.add_argument("--decode-impl", default="dense",
                    choices=["dense", "streamed", "kernel"],
                    help="attention interior: dense oracle, streamed "
                         "ring-flash-decode (XLA), or the Pallas kernel")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch)
    if args.window:
        cfg = cfg.replace(sliding_window=args.window)
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    adapters = init_lora(params, lora_targets(cfg), 8, 16.0, key, sigma=0.05)

    B = args.batch
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(4, cfg.vocab_size, (B, args.prompt_len)))

    serve = jax.jit(make_serve_step(cfg, decode_impl=args.decode_impl))
    kv_dtype = jnp.int8 if args.int8_cache else jnp.dtype(cfg.dtype)
    C = max(1, min(args.prefill_chunk, args.prompt_len))
    cache = T.init_cache(cfg, B, capacity=args.prompt_len + args.gen,
                         kv_dtype=kv_dtype, prefill_chunk=C)
    print(f"== serving {cfg.name}: batch={B}, prompt={args.prompt_len}, "
          f"gen={args.gen}, window={args.window or 'full'}, "
          f"cache={kv_dtype}, prefill_chunk={C}, "
          f"decode_impl={args.decode_impl} ==")
    # chunked prefill: whole prompt chunks through the cached sequence path
    t0 = time.time()
    n_calls = 0
    for t in range(0, args.prompt_len, C):
        chunk = prompts[:, t: t + C]
        n = jnp.full((B,), chunk.shape[1], jnp.int32)
        logits, cache = serve(params, adapters, cache,
                              {"tokens": chunk, "n_tokens": n})
        n_calls += 1
    print(f"prefill: {args.prompt_len} tokens in {n_calls} calls, "
          f"{time.time()-t0:.2f}s")

    generated = []
    tok = jnp.argmax(logits, -1)[:, None]
    t0 = time.time()
    for _ in range(args.gen):
        generated.append(tok)
        logits, cache = serve(params, adapters, cache, {"tokens": tok})
        tok = jnp.argmax(logits, -1)[:, None]
    dt = time.time() - t0
    gen = jnp.concatenate(generated, axis=1)
    print(f"decode: {args.gen} steps × batch {B} in {dt:.2f}s "
          f"({B*args.gen/dt:.1f} tok/s on CPU)")
    for b in range(min(B, 2)):
        print(f"  seq{b}: {gen[b].tolist()}")


if __name__ == "__main__":
    main()
