"""Training launcher: LoRA fine-tuning of any registered architecture on the
host devices (smoke/real) — the single-tenant (non-federated) path.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 20 [--batch 4] [--seq 64] [--use-kernels]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.io import save
from repro.common.compile_cache import enable_compile_cache
from repro.common.config import LoRAConfig, OptimConfig
from repro.configs import get_config, get_smoke_config, lora_targets
from repro.data.synthetic import make_eval_data
from repro.models import transformer as T
from repro.optim.adamw import adamw_init
from repro.peft.lora import init_lora
from repro.train.step import make_eval_step, make_train_step


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    targets = lora_targets(cfg)
    adapters = init_lora(params, targets, args.rank, float(args.rank), key)
    opt_state = adamw_init(adapters)
    optim = OptimConfig(lr=args.lr)
    step = jax.jit(make_train_step(cfg, optim, remat=False,
                                   loss_chunk=min(args.seq, 512),
                                   use_kernels=args.use_kernels,
                                   grad_accum=args.grad_accum))
    eval_step = jax.jit(make_eval_step(cfg, loss_chunk=min(args.seq, 512)))

    rng = np.random.default_rng(0)
    ev = make_eval_data(num_samples=args.batch * 4, seq_len=args.seq,
                        vocab=cfg.vocab_size)

    def batch_at(i):
        lo = (i * args.batch) % (ev["tokens"].shape[0] - args.batch + 1)
        return {k: jnp.asarray(v[lo: lo + args.batch]) for k, v in ev.items()}

    print(f"training {cfg.name}: {cfg.param_count():,} params, LoRA rank "
          f"{args.rank} on {targets}")
    t0 = time.time()
    for i in range(args.steps):
        adapters, opt_state, metrics = step(params, adapters, opt_state,
                                            batch_at(i))
        if i % max(1, args.steps // 10) == 0 or i == args.steps - 1:
            print(f"step {i:4d} loss={float(metrics['loss']):.4f} "
                  f"acc={float(metrics['accuracy']):.3f} "
                  f"({time.time()-t0:.1f}s)")
    m = eval_step(params, adapters, batch_at(0))
    print(f"final eval: loss={float(m['loss']):.4f} acc={float(m['accuracy']):.3f}")
    if args.ckpt:
        save(args.ckpt, adapters, step=args.steps)
        print(f"adapters saved to {args.ckpt}")


if __name__ == "__main__":
    main()
