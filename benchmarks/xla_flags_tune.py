"""saxml-style XLA inference-flag tuning for the sharded serve hot loop.

XLA reads ``XLA_FLAGS`` once at backend init, so every (flag set × mesh
topology) cell runs in a fresh subprocess: the worker builds a mesh-sharded
``ServeEngine`` on ``--xla_force_host_platform_device_count=N`` host
devices, compiles the decode burst, times it, and prints one JSON line.
The parent never touches JAX; it sweeps the named flag sets, picks the
winner per topology, and records everything (winner + full per-set
timings) in a bench artifact:

  PYTHONPATH=src python benchmarks/xla_flags_tune.py --smoke --json BENCH_xla_flags.json

The sweep covers the documented cpu-backend levers (fast-math, thunk
runtime, concurrency-optimized scheduler).  Forced host devices exist only
on the CPU backend, so a worker that finds any other platform stops with an
error instead of timing something else.
``append_xla_flags`` semantics: a flag the user already set in the
environment is never overridden by a set below.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.common.xla_env import merge_flags, render_flags  # noqa: E402

# named flag sets: the cpu backend's documented performance levers.  Every
# cell runs on forced host devices, so there is no chip to tune here.
FLAG_SETS = {
    "BASE": {},
    "FASTMATH": {"xla_cpu_enable_fast_math": True},
    "NOTHUNKS": {"xla_cpu_use_thunk_runtime": False},
    "CONCSCHED": {"xla_cpu_enable_concurrency_optimized_scheduler": True},
}
# non-BASE sets apply ON TOP of BASE (saxml composes them the same way)
_COMPOSE_WITH_BASE = True

BURST = 8


def _worker(args) -> int:
    """One measurement cell; env (XLA_FLAGS) was fixed by the parent."""
    import jax

    platform = jax.devices()[0].platform
    if platform != "cpu":
        print(f"xla_flags_tune forces host devices and measures the CPU "
              f"backend only; this worker found {platform}", file=sys.stderr)
        return 2

    from repro.common.config import ModelConfig
    from repro.models import transformer as T
    from repro.serve.engine import SamplingParams, ServeEngine
    from repro.topology import make_serve_mesh

    cfg = ModelConfig(name="flagtune-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=8, num_kv_heads=4, head_dim=16,
                      d_ff=128, vocab_size=256, dtype="float32")
    params = T.init(cfg, jax.random.PRNGKey(0))
    B = 4
    eng = ServeEngine(cfg, params, batch_slots=B, capacity=128,
                      prefill_chunk=8, decode_impl="streamed",
                      mesh=make_serve_mesh(args.mesh))
    for i in range(B):
        eng.submit([1 + i, 2, 3, 4], SamplingParams(max_tokens=512))
    eng.run_steps(1)                      # prefill; slots now pure-decode

    fn = eng._get_burst(BURST, False)
    fargs = (eng.params, eng._adapters_arg(), eng.cache, eng._state)
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*fargs))     # trace + compile + first run
    compile_s = time.perf_counter() - t0
    times = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*fargs))
        times.append(time.perf_counter() - t0)
    best = min(times)
    us_per_step = best / BURST * 1e6
    print(json.dumps({
        "us_per_step": us_per_step,
        "tok_per_s": B * BURST / best,
        "compile_s": compile_s,
        "devices": len(jax.devices()),
    }))
    return 0


def _run_cell(set_name: str, flags: dict, mesh: int, args) -> dict:
    env = dict(os.environ)
    # merge_flags: a flag the user set in the parent env keeps its value
    env["XLA_FLAGS"] = merge_flags(
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={mesh}",
        *render_flags(flags).split())
    cmd = [sys.executable, os.path.abspath(__file__), "--worker",
           "--mesh", str(mesh), "--iters", str(args.iters)]
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=600)
    if proc.returncode != 0:
        print(proc.stdout, proc.stderr, file=sys.stderr)
        raise RuntimeError(f"worker failed: set={set_name} mesh={mesh}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--mesh", type=int, default=1, help=argparse.SUPPRESS)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--smoke", action="store_true",
                    help="topologies {1,2} instead of {1,2,4,8}")
    ap.add_argument("--json", default="", help="write the report here")
    args = ap.parse_args()

    if args.worker:
        return _worker(args)

    sets = FLAG_SETS
    base = sets.get("BASE", {})
    topologies = (1, 2) if args.smoke else (1, 2, 4, 8)

    report = {"suite": "xla_flags", "backend": "cpu",
              "burst": BURST,
              "flag_sets": {k: render_flags(v) for k, v in sets.items()},
              "topologies": {}}
    for mesh in topologies:
        results = {}
        for name, flags in sets.items():
            merged = dict(base, **flags) if _COMPOSE_WITH_BASE else flags
            results[name] = _run_cell(name, merged, mesh, args)
            print(f"mesh={mesh} {name:10s} "
                  f"{results[name]['us_per_step']:9.1f} us/step "
                  f"(compile {results[name]['compile_s']:.1f}s)")
        winner = min(results, key=lambda n: results[n]["us_per_step"])
        entry = {
            "results": results,
            "winner": winner,
            "winning_flags": render_flags(dict(base, **sets[winner])
                                          if _COMPOSE_WITH_BASE
                                          else sets[winner]),
            "speedup_winner_vs_base": (results["BASE"]["us_per_step"]
                                       / results[winner]["us_per_step"]),
        }
        report["topologies"][f"mesh_{mesh}"] = entry
        print(f"mesh={mesh}: winner={winner} "
              f"(x{entry['speedup_winner_vs_base']:.3f} vs BASE)")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
