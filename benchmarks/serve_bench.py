"""Serving-engine throughput: eager per-token loop vs the jitted engine step.

Arms over the same continuous-batching workload:

  * ``eager``      — the seed ServeEngine loop: one token per engine step,
                     per-row host-side sampling (eager argmax + int() sync),
                     a B+1-way key split every step;
  * ``jit_chunk1`` — the jitted engine step, chunked prefill OFF (width 1);
  * ``jit_chunkN`` — the jitted engine step with chunked prefill (whole
                     prompt chunks through the cached sequence path);
  * ``jit_chunkN_streamed`` — the same engine with ``decode_impl=
                     "streamed"`` (ring-flash-decode: online softmax over kv
                     blocks, no dense (B,H,C,cap) scores / (B,C,cap) mask).

The report's ``decode_impl`` axis compares the streamed hot loop against
the dense oracle (``speedup_streamed_vs_dense`` — must not regress).  Also
verifies every jitted arm compiles ONCE per executable (no per-step
retraces after warmup).

The ``multi_adapter`` axis serves the same workload through the
multi-tenant registry (``repro.serve.adapters``) with 1 / 8 / 32 live
adapters of mixed ranks, requests round-robining across them; it reports
per-arm tok/s, the 32-vs-1 slowdown ratio, and the trace counts — with a
registry hot-swap between the warmup and timed passes to prove adapter
churn causes zero retraces.  Emits JSON for CI artifacts::

    PYTHONPATH=src python benchmarks/serve_bench.py --smoke --json BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import ModelConfig
from repro.models import transformer as T
from repro.serve.engine import SamplingParams, ServeEngine
from repro.train.step import make_serve_step

SMOKE_MODEL = ModelConfig(name="servebench-tiny", family="dense", num_layers=2,
                          d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                          d_ff=128, vocab_size=256, dtype="float32")
FULL_MODEL = ModelConfig(name="servebench-small", family="dense", num_layers=4,
                         d_model=128, num_heads=8, num_kv_heads=4, head_dim=16,
                         d_ff=256, vocab_size=512, dtype="float32")


def _seed_sample_logits(logits, params, key):
    """The seed engine's per-row sampler, verbatim: python-branching eager
    ops (each one a separate dispatch) per slot per token."""
    if params.temperature <= 0.0:
        return jnp.argmax(logits)
    logits = logits / params.temperature
    if params.top_k:
        kth = jax.lax.top_k(logits, params.top_k)[0][-1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if params.top_p < 1.0:
        sorted_logits = jnp.sort(logits)[::-1]
        probs = jax.nn.softmax(sorted_logits)
        cum = jnp.cumsum(probs)
        cutoff_idx = jnp.searchsorted(cum, params.top_p, side="left")
        cutoff = sorted_logits[jnp.minimum(cutoff_idx, logits.shape[0] - 1)]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits)


class EagerLoop:
    """The seed engine's hot loop, kept as the measured baseline: single
    jitted model step per TOKEN, host-side per-row sampling, eager key
    splits — everything the jitted engine step collapses on-device."""

    def __init__(self, cfg, params, batch_slots, capacity, seed=0):
        self.cfg, self.params = cfg, params
        self.B = batch_slots
        self.key = jax.random.PRNGKey(seed)
        self.cache = T.init_cache(cfg, batch_slots, capacity, jnp.dtype(cfg.dtype))
        self._step = jax.jit(make_serve_step(cfg))
        self.slots = [None] * batch_slots
        self._pending = []
        self._last = np.zeros((batch_slots, 1), np.int32)
        self._left = {}

    def submit(self, prompt, params):
        self._pending.append([len(self._pending) + 1, list(prompt), params, []])
        return self._pending[-1][0]

    def run(self, max_steps=10000):
        results = {}
        for _ in range(max_steps):
            for i in range(self.B):
                if self.slots[i] is None and self._pending:
                    req = self._pending.pop(0)
                    self.slots[i] = req
                    self._left[i] = list(req[1])
            if all(s is None for s in self.slots) and not self._pending:
                break
            toks = self._last.copy()
            feeding = [False] * self.B
            for i, req in enumerate(self.slots):
                if req is None:
                    toks[i, 0] = 0
                elif self._left.get(i):
                    toks[i, 0] = self._left[i].pop(0)
                    feeding[i] = True
            logits, self.cache = self._step(self.params, None, self.cache,
                                            {"tokens": jnp.asarray(toks)})
            self.key, *keys = jax.random.split(self.key, self.B + 1)
            for i, req in enumerate(self.slots):
                if req is None or (feeding[i] and self._left.get(i)):
                    continue
                tok = int(_seed_sample_logits(logits[i], req[2], keys[i]))
                req[3].append(tok)
                self._last[i, 0] = tok
                if len(req[3]) >= req[2].max_tokens:
                    results[req[0]] = req[3]
                    self.slots[i] = None
        return results


def workload(engine, n_req, prompt_len, gen, rng, adapter_ids=None):
    # temperature sampling: the production path (the seed loop pays ~8 eager
    # dispatches + a host sync per slot per token here; the jitted step pays
    # zero extra — sampling compiles into the engine step)
    sp = SamplingParams(temperature=0.8, top_k=20, top_p=0.95, max_tokens=gen)
    uids = []
    for r in range(n_req):
        p = rng.integers(1, engine.cfg.vocab_size, prompt_len).tolist()
        if adapter_ids:
            uids.append(engine.submit(p, sp,
                                      adapter_id=adapter_ids[r % len(adapter_ids)]))
        else:
            uids.append(engine.submit(p, sp))
    t0 = time.perf_counter()
    out = engine.run()
    dt = time.perf_counter() - t0
    total = sum(len(out[u]) for u in uids)
    return dt, total


def multi_adapter_axis(cfg, params, args, gen, capacity, rng):
    """1 / 8 / 32 live mixed-rank adapters through ONE engine each: tok/s
    per arm + trace counts, with a hot-swap between warmup and the timed
    pass to prove registry churn never retraces."""
    from repro.configs import lora_targets
    from repro.peft.lora import init_lora
    from repro.serve.adapters import AdapterRegistry

    key = jax.random.PRNGKey(7)
    template = init_lora(params, lora_targets(cfg), 4, 8.0, key)
    ranks = [4, 8, 2, 6]
    axis = {}
    for n_ad in (1, 8, 32):
        reg = AdapterRegistry(template, page_rank=4, num_pages=2 * n_ad + 6,
                              max_adapters=n_ad + 3, max_rank=8)
        ids = [reg.register(
            f"t{j}", init_lora(params, lora_targets(cfg), ranks[j % len(ranks)],
                               8.0, jax.random.fold_in(key, j)))
            for j in range(n_ad)]
        eng = ServeEngine(cfg, params, batch_slots=args.slots,
                          capacity=capacity, prefill_chunk=args.chunk,
                          registry=reg)
        dt, total = workload(eng, args.requests, args.prompt_len, gen, rng,
                             adapter_ids=ids)
        warm_traces = dict(eng.trace_counts)
        # registry churn between passes: the timed pass runs against swapped
        # pool contents with the SAME executables
        ids[0] = reg.swap("t0", init_lora(params, lora_targets(cfg), 8, 8.0,
                                          jax.random.fold_in(key, 999)))
        dt2, _ = workload(eng, args.requests, args.prompt_len, gen, rng,
                          adapter_ids=ids)
        assert dict(eng.trace_counts) == warm_traces, (
            f"multi_adapter[{n_ad}]: registry churn retraced "
            f"({warm_traces} -> {dict(eng.trace_counts)})")
        dt = min(dt, dt2)
        axis[f"adapters_{n_ad}"] = {
            "wall_s": round(dt, 4), "tokens": total,
            "tok_per_s": round(total / dt, 2),
            "live_adapters": n_ad,
            "ranks": [ranks[j % len(ranks)] for j in range(min(n_ad, 4))],
            "trace_counts": {str(k): v for k, v in warm_traces.items()},
        }
        print(f"multi_adapter[{n_ad:2d}]     {total:5d} tokens in {dt:7.3f}s "
              f"({total / dt:8.1f} tok/s)")
    t1 = axis["adapters_1"]["tok_per_s"]
    t32 = axis["adapters_32"]["tok_per_s"]
    axis["slowdown_32_vs_1"] = round(t1 / t32, 2)
    axis["retraces_stable_under_churn"] = True
    print(f"multi-adapter slowdown (32 vs 1 live): {t1 / t32:.2f}x")
    return axis


def _mesh_worker(args, cfg, gen, capacity, rng) -> None:
    """One mesh-sharded measurement: this process was started with
    ``--xla_force_host_platform_device_count`` already in its env (XLA
    reads it at backend init, so it cannot be set in-process here)."""
    from repro.launch.dryrun import collective_bytes
    from repro.topology import make_serve_mesh

    params = T.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=args.slots, capacity=capacity,
                      prefill_chunk=args.chunk, decode_impl="streamed",
                      mesh=make_serve_mesh(args.mesh_worker))
    dt, total = workload(eng, args.requests, args.prompt_len, gen, rng)
    before = dict(eng.trace_counts)
    dt2, _ = workload(eng, args.requests, args.prompt_len, gen, rng)
    assert dict(eng.trace_counts) == before, (
        f"mesh_axis[{args.mesh_worker}]: retraced after warmup "
        f"({before} -> {dict(eng.trace_counts)})")
    dt = min(dt, dt2)
    totals = collective_bytes(eng.lower_step(width=1).compile().as_text())
    print(json.dumps({
        "devices": len(jax.devices()),
        "wall_s": round(dt, 4), "tokens": total,
        "tok_per_s": round(total / dt, 2),
        "trace_counts": {str(k): v for k, v in before.items()},
        "collective_bytes_per_step": {k: v for k, v in totals.items() if v},
    }))


def mesh_axis(args, gen):
    """Same streamed workload on a (data=1, model=N) mesh, 1 vs 2 forced
    host devices, each in a fresh subprocess: tok/s, per-step collective
    bytes from the compiled step, and trace counts for the gate."""
    from repro.common.xla_env import merge_flags

    axis = {}
    for name, n in (("single", 1), ("sharded", 2)):
        env = dict(os.environ)
        env["XLA_FLAGS"] = merge_flags(
            os.environ.get("XLA_FLAGS", ""),
            f"--xla_force_host_platform_device_count={n}")
        env["JAX_PLATFORMS"] = "cpu"    # forced host devices only
        cmd = [sys.executable, os.path.abspath(__file__),
               "--mesh-worker", str(n), "--slots", str(args.slots),
               "--requests", str(args.requests),
               "--prompt-len", str(args.prompt_len),
               "--gen", str(gen), "--chunk", str(args.chunk)]
        if args.smoke:
            cmd.append("--smoke")
        proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                              timeout=900)
        if proc.returncode != 0:
            print(proc.stdout, proc.stderr, file=sys.stderr)
            raise RuntimeError(f"mesh_axis worker (devices={n}) failed")
        axis[name] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"mesh_axis[{name:7s}] {axis[name]['tokens']:5d} tokens in "
              f"{axis[name]['wall_s']:7.3f}s ({axis[name]['tok_per_s']:8.1f} "
              f"tok/s) collectives={axis[name]['collective_bytes_per_step']}")
    axis["model_axis"] = 2
    axis["slowdown_sharded_vs_single"] = round(
        axis["single"]["tok_per_s"] / axis["sharded"]["tok_per_s"], 2)
    print(f"mesh-axis slowdown (2-device model-sharded vs 1): "
          f"{axis['slowdown_sharded_vs_single']:.2f}x")
    return axis


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small config + few iters (CI)")
    ap.add_argument("--json", default="", help="write results to this path")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="serving-realistic prompts: prefill dominates the "
                         "step count unless it is chunked")
    ap.add_argument("--gen", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--mesh-worker", type=int, default=0,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    cfg = SMOKE_MODEL if args.smoke else FULL_MODEL
    gen = args.gen or (32 if args.smoke else 48)
    capacity = args.prompt_len + gen + 8
    rng = np.random.default_rng(0)

    platform = jax.devices()[0].platform
    if platform != "cpu":
        # the mesh axis runs its arms on forced host (CPU) devices in child
        # processes, and this process now holds the chip: its numbers would
        # be CPU numbers filed next to chip numbers
        raise SystemExit(f"serve_bench measures the CPU backend only (its "
                         f"mesh_axis forces host devices); found {platform}")

    if args.mesh_worker:
        _mesh_worker(args, cfg, gen, capacity, rng)
        return

    def mk(kind):
        if kind == "eager":
            return EagerLoop(cfg, params, args.slots, capacity)
        chunk = 1 if kind == "jit_chunk1" else args.chunk
        impl = "streamed" if kind.endswith("_streamed") else "dense"
        return ServeEngine(cfg, params, batch_slots=args.slots,
                           capacity=capacity, prefill_chunk=chunk,
                           decode_impl=impl)

    params = T.init(cfg, jax.random.PRNGKey(0))
    arms = ["eager", "jit_chunk1", f"jit_chunk{args.chunk}",
            f"jit_chunk{args.chunk}_streamed"]

    results = {}
    trace_counts = {}
    for kind in arms:
        e = mk(kind)
        # first pass compiles this instance's executables, second is warm;
        # report the warm (min) timing for every arm
        dt, total = workload(e, args.requests, args.prompt_len, gen, rng)
        if isinstance(e, ServeEngine):
            before = dict(e.trace_counts)
        dt2, _ = workload(e, args.requests, args.prompt_len, gen, rng)
        dt = min(dt, dt2)
        if isinstance(e, ServeEngine):
            assert e.trace_counts == before, \
                f"{kind}: retraced after warmup ({before} -> {e.trace_counts})"
            trace_counts[kind] = before
        results[kind] = {"wall_s": round(dt, 4),
                         "tokens": total,
                         "tok_per_s": round(total / dt, 2),
                         "decode_impl": ("streamed" if kind.endswith("_streamed")
                                         else "dense")}
        print(f"{kind:20s} {total:5d} tokens in {dt:7.3f}s "
              f"({total / dt:8.1f} tok/s)")

    jit1 = results["jit_chunk1"]["tok_per_s"]
    jitN = results[f"jit_chunk{args.chunk}"]["tok_per_s"]
    jitS = results[f"jit_chunk{args.chunk}_streamed"]["tok_per_s"]
    eager = results["eager"]["tok_per_s"]
    speedup = jitN / eager
    print(f"speedup (jitted+chunked vs eager loop): {speedup:.2f}x")
    print(f"chunked prefill vs width-1: {jitN / jit1:.2f}x")
    print(f"streamed decode vs dense: {jitS / jitN:.2f}x")
    print(f"trace counts (stable across runs): {trace_counts}")

    multi_axis = multi_adapter_axis(cfg, params, args, gen, capacity, rng)
    m_axis = mesh_axis(args, gen)

    report = {
        "config": {"model": cfg.name, "batch_slots": args.slots,
                   "requests": args.requests, "prompt_len": args.prompt_len,
                   "gen": gen, "prefill_chunk": args.chunk,
                   "smoke": bool(args.smoke),
                   "backend": jax.default_backend()},
        "results": results,
        "decode_impl_axis": {
            "dense": jitN, "streamed": jitS,
            "speedup_streamed_vs_dense": round(jitS / jitN, 2)},
        "multi_adapter_axis": multi_axis,
        "mesh_axis": m_axis,
        "speedup_jit_vs_eager": round(speedup, 2),
        "speedup_chunked_vs_width1": round(jitN / jit1, 2),
        "trace_counts": {arm: {str(k): v for k, v in c.items()}
                         for arm, c in trace_counts.items()},
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
