"""Bring-up run of the federated round and the serving engine on a TPU.

Drives the system's main path once, through the entry points a user calls,
at qwen2-0.5b's full published widths (24 layers, d_model 896, 14 heads over
2 KV heads, d_ff 4864, vocabulary 151,936, bf16).  Weights and data are made
from ``--seed``; nothing is read from disk.

1. device  -- JAX must see a TPU.  There is no CPU fallback.
2. kernels -- each of the seven Pallas kernels runs natively and is compared
   with its float32 oracle in ``repro.kernels.ref``.
3. round   -- two FLoRIST rounds of heterogeneous-rank clients through
   ``FederatedTrainer`` on the gram route, so the ``adapter_gram`` kernel
   runs inside the finalize.  For one leaf and layer, the kept spectrum is
   compared with the SVD of the dense update sum_k w_k B_k A_k.
4. serve   -- the round's global adapter is registered and served next to
   the base model by ``ServeEngine`` on the kernel decode path (ring-decode
   attention, bgmv LoRA).  The logits of one prefill and one decode step are
   compared with the dense attention and XLA LoRA path.

With ``--four-chips`` only the sharded round runs: ``sharded_cohort`` with
``florist_sharded`` on a 4-device mesh, against ``cohort`` with ``florist``
on one device from the same seed.

Every time and memory figure printed is bring-up information, not a
benchmark number.  A failed check raises, so the exit code is non-zero; the
last line of standard output is one JSON object naming the device.

    python chip_smoke.py [--seed 0] [--four-chips]
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.common import telemetry  # noqa: E402
from repro.common.compile_cache import enable_compile_cache  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.common.config import FedConfig, LoRAConfig, OptimConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.aggregators import adapter_leaf_paths, get_path  # noqa: E402
from repro.core.aggregators.base import fold_scale  # noqa: E402
from repro.core.aggregators.florist import FloristAggregator  # noqa: E402
from repro.core.distributed import ShardedFloristAggregator  # noqa: E402
from repro.core.federated import FederatedTrainer  # noqa: E402
from repro.core.runtime.runners import ShardedCohortRunner  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve.adapters import AdapterRegistry  # noqa: E402
from repro.serve.engine import SamplingParams, ServeEngine  # noqa: E402
from repro.topology import make_fed_mesh  # noqa: E402
from repro.train.step import make_serve_step  # noqa: E402

MODEL = "qwen2-0.5b"
TAU = 0.9

# Tolerances, each on max|got - want| / max|want| against a float32 oracle
# computed at "highest" matmul precision from the same inputs.
#: the kernel's output is bf16 (relative step 2^-8), and lora_matmul and
#: flash attention also round an fp32 intermediate to bf16 before their
#: second matmul
TOL_BF16_OUT = 2e-2
#: fp32 output from bf16 or fp32 operands on the MXU, which may take fp32
#: operands in bf16 passes (relative error up to about 2^-9 per product)
TOL_MXU_F32 = 1e-2
#: wkv6 is fp32 elementwise math on the vector unit; the two sides differ
#: only in exp and summation-order rounding, carried over 512 steps
TOL_WKV = 1e-3
#: the finalize runs its thin SVDs as a Gram matrix (adapter_gram) plus an
#: fp32 eigh at the chip's default matmul precision; the oracle is a
#: highest-precision SVD of the dense update
TOL_SPECTRUM = 2e-2
#: the serving paths' logits are held to a bound measured in the same run:
#: the dense bf16 path's distance from the same weights run in float32 at
#: highest precision.  If the kernel path is no less faithful to the float32
#: model than the dense path, the two bf16 paths differ by at most twice
#: that (triangle inequality); a masking or paging fault is far outside it.
LOGITS_BOUND_FACTOR = 2.0
#: sharded and single-device rounds train each client with the same math
#: but partition it differently, so XLA may fuse and sum in another order
TOL_SHARDED = 2e-2


class PhaseLog:
    """Wall time, compiles and device memory peak per phase, printed as
    bring-up facts.  Each phase is a span; its compiles are what the
    telemetry counter charged to it and to the spans inside it."""

    @contextlib.contextmanager
    def phase(self, name: str):
        print(f"== phase: {name}", flush=True)
        with telemetry.span("smoke." + name) as sp:
            yield
        compiled = telemetry.compiles(sp.start, sp.end).values()
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        peak = ", ".join("n/a" if p is None else f"{p / 2**30:.2f} GiB"
                         for p in peaks)
        print(f"[bring-up] {name}: {sp.seconds:.1f} s wall, "
              f"{sum(n for n, _ in compiled)} executables compiled in "
              f"{sum(s for _, s in compiled):.1f} s (tracing, lowering, "
              f"backend), peak_bytes_in_use per device [{peak}]", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"shape {got.shape} != reference {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    scale = np.abs(want).max()
    if not scale > 0:
        raise AssertionError("the reference is all zeros")
    return float(np.abs(got - want).max() / scale)


def check(name: str, err: float, tol: float) -> bool:
    ok = err <= tol
    print(f"  {name:28s} rel err {err:.3e}  tol {tol:.0e}  "
          f"{'ok' if ok else 'FAIL'}", flush=True)
    return ok


def highest(fn, *args):
    """``fn`` jitted and run in float32 at highest matmul precision."""
    args = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
            else a for a in args]
    with jax.default_matmul_precision("highest"):
        return jax.jit(fn)(*args)


# -- phase 2: kernels -------------------------------------------------------


def _ring_state(rng, B: int, C: int, cap: int):
    """Per-row ring scalars AFTER a chunk write: some rows short, some
    wrapped past capacity, ragged chunk fill."""
    pos = rng.integers(C, 2 * cap, B)
    n = rng.integers(1, C + 1, B)
    length = np.minimum(pos, cap)
    return [jnp.asarray(x, jnp.int32) for x in (pos, length, n)]


def kernel_cases(seed: int, *, d: int, H: int, K: int, hd: int, slots: int,
                 chunk: int, cap: int, batch: int, seq: int, rank: int,
                 mla: tuple, rwkv: tuple):
    """name -> (kernel fn, oracle fn, args, tolerance) for the seven
    kernels (and the int8 ring-decode path) at the given widths."""
    rng = np.random.default_rng(seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))

    def normal(shape, dtype=jnp.bfloat16, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    Hm, kvr, rope, mla_scale = mla
    Hr, hr = rwkv
    cases = {}

    x = normal((batch, seq, d))
    w = normal((d, H * hd), scale=d ** -0.5)
    a = normal((rank, d), scale=d ** -0.5)
    b = normal((H * hd, rank), scale=rank ** -0.5)
    cases["lora_matmul"] = (
        lambda x, w, a, b: ops.lora_matmul(x, w, a, b, 2.0),
        lambda x, w, a, b: ref.lora_matmul_ref(
            x.reshape(-1, d), w, a, b, 2.0).reshape(batch, seq, -1),
        (x, w, a, b), TOL_BF16_OUT)

    qkv = (normal((batch, seq, H, hd)), normal((batch, seq, K, hd)),
           normal((batch, seq, K, hd)))
    cases["flash_attention"] = (
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
        lambda q, k, v: ref.flash_attention_ref(q, k, v, causal=True),
        qkv, TOL_BF16_OUT)

    stack = normal((d, 4 + 8 + 16 + 64), jnp.float32)
    cases["adapter_gram"] = (ops.adapter_gram, ref.adapter_gram_ref,
                             (stack,), TOL_MXU_F32)

    pr, pages, n_ad = 16, 16, 4
    pmax = -(-rank // pr)
    table = jnp.asarray(rng.integers(0, pages, (n_ad, pmax)), jnp.int32)
    ranks = jnp.asarray([0, pr, rank - pr // 2, rank], jnp.int32)
    scales = jnp.asarray([0.0, 1.0, 0.5, 2.0], jnp.float32)
    ids = jnp.asarray(rng.integers(0, n_ad, slots), jnp.int32)
    bg = (normal((slots, chunk, d)), normal((pages, pr, d), scale=d ** -0.5),
          normal((pages, H * hd, pr), scale=rank ** -0.5), table, ranks,
          scales, ids)
    cases["bgmv"] = (ops.bgmv, ref.bgmv_ref, bg, TOL_MXU_F32)

    ring = _ring_state(rng, slots, chunk, cap)
    dec = (normal((slots, chunk, H, hd)), normal((slots, cap, K, hd)),
           normal((slots, cap, K, hd)))
    cases["ring_decode"] = (ops.ring_decode, ref.ring_decode_ref,
                            dec + tuple(ring), TOL_MXU_F32)

    q8 = jnp.asarray(rng.integers(-127, 128, (2, slots, cap, K, hd)), jnp.int8)
    s8 = normal((2, slots, cap, K, 1), jnp.float32, 0.01)
    s8 = jnp.abs(s8) + 1e-3
    dec8 = (dec[0], q8[0], q8[1]) + tuple(ring) + (s8[0], s8[1])
    cases["ring_decode_int8"] = (
        lambda q, k, v, p, n, c, ks, vs: ops.ring_decode(
            q, k, v, p, n, c, k_scale=ks, v_scale=vs),
        lambda q, k, v, p, n, c, ks, vs: ref.ring_decode_ref(
            q, k, v, p, n, c, k_scale=ks, v_scale=vs),
        dec8, TOL_MXU_F32)

    lat = (normal((slots, chunk, Hm, kvr + rope)), normal((slots, cap, kvr)),
           normal((slots, cap, rope)))
    cases["mla_ring_decode"] = (
        lambda q, c, r, p, n, t: ops.mla_ring_decode(q, c, r, p, n, t,
                                                     scale=mla_scale),
        lambda q, c, r, p, n, t: ref.mla_ring_decode_ref(q, c, r, p, n, t,
                                                         scale=mla_scale),
        lat + tuple(ring), TOL_MXU_F32)

    rkv = tuple(normal((2, seq, Hr, hr), scale=0.5) for _ in range(3))
    decay = -jnp.exp(normal((2, seq, Hr, hr), jnp.float32))
    cases["wkv6"] = (ops.wkv6, ref.wkv6_ref,
                     rkv + (decay, normal((Hr, hr), jnp.float32, 0.5)),
                     TOL_WKV)
    return cases


def check_kernels(cases) -> None:
    """Run every case natively; all errors print before any failure
    raises."""
    bad = []
    for name, (fn, oracle, args, tol) in cases.items():
        lowered = jax.jit(fn).lower(*args)  # repro-lint: disable=jit-in-loop -- one kernel per pass
        if "tpu_custom_call" not in lowered.as_text():
            raise AssertionError(f"{name}: no Pallas kernel in the lowering")
        got = lowered.compile()(*args)
        if not check(name, rel_err(got, highest(oracle, *args)), tol):
            bad.append(name)
    if bad:
        raise AssertionError(f"kernels off their oracle: {bad}")


# -- phase 3: the federated round -------------------------------------------


class WitnessedFlorist(FloristAggregator):
    """FLoRIST that also keeps one layer of one leaf of every client block
    folded in this round, so the finalize's spectrum can be checked against
    the dense update sum_k w_k B_k A_k."""

    def __init__(self, layer: int, **kw):
        super().__init__(**kw)
        self.layer, self.path, self.blocks = layer, None, []

    def begin_round(self, dims=None) -> None:
        super().begin_round(dims)
        self.blocks = []

    def _accumulate(self, update, weight: float, rank: int) -> None:
        if self.path is None:
            self.path = sorted(adapter_leaf_paths(update))[0]
        B, A = fold_scale(get_path(update, self.path))
        self.blocks.append((B[self.layer], A[self.layer], weight))
        super()._accumulate(update, weight, rank)


def make_trainer(cfg, *, seed: int, ranks, sample: int, batch_size: int,
                 local_steps: int, seq_len: int, aggregator, runner):
    clients = sum(n for _, n in ranks)
    fed = FedConfig(num_clients=clients, clients_per_round=sample,
                    method=aggregator.name, tau=TAU, heterogeneous=True,
                    rank_distribution=ranks, seed=seed)
    lora = LoRAConfig(rank=max(r for r, _ in ranks), alpha=16.0)
    return FederatedTrainer(cfg, fed, lora, OptimConfig(lr=3e-4),
                            batch_size=batch_size, local_steps=local_steps,
                            seq_len=seq_len, svd_method="gram",
                            aggregator=aggregator, runner=runner)


def assert_finite_tree(tree, what: str) -> None:
    for leaf in jax.tree.leaves(tree):
        if not np.isfinite(np.asarray(leaf, np.float32)).all():
            raise AssertionError(f"non-finite values in {what}")


def print_ranks(gs) -> None:
    for path in sorted(gs.ranks):
        print(f"  kept ranks {'/'.join(map(str, path))}: {gs.ranks[path]}")


def federated_round(cfg, *, seed: int, rounds: int, **sizes):
    """``rounds`` FLoRIST rounds; returns the trainer after checking loss,
    adapters and one layer's spectrum against the dense update."""
    agg = WitnessedFlorist(layer=cfg.num_layers // 2, tau=TAU,
                           svd_method="gram")
    tr = make_trainer(cfg, seed=seed, aggregator=agg, runner="sequential",
                      **sizes)
    for rnd in range(rounds):
        t0 = time.perf_counter()
        rec = tr.run_round(rnd)
        print(f"  round {rnd}: eval_loss {rec.eval_loss:.4f}  "
              f"clients {len(agg.client_ranks)} ranks {agg.client_ranks}  "
              f"download rank {rec.global_rank_total}  "
              f"[bring-up] {time.perf_counter() - t0:.1f} s", flush=True)
        if not np.isfinite(rec.eval_loss):
            raise AssertionError(f"round {rnd}: eval loss {rec.eval_loss}")
    gs = tr.global_state
    assert_finite_tree(gs.global_adapters, "the global adapters")
    print_ranks(gs)

    p = gs.ranks[agg.path][agg.layer]
    kept = np.asarray(gs.spectra[agg.path][agg.layer][:p])
    with jax.default_matmul_precision("highest"):
        dense = sum(w * jnp.asarray(B, jnp.float32) @ jnp.asarray(A, jnp.float32)
                    for B, A, w in agg.blocks)
        want = np.asarray(jnp.linalg.svd(dense, compute_uv=False))
    print(f"  spectrum of {'/'.join(map(str, agg.path))} layer {agg.layer}: "
          f"kept rank {p} from {len(agg.blocks)} clients, "
          f"sigma_1 {kept[0]:.4e} (dense {want[0]:.4e})")
    err = float(np.abs(kept - want[:p]).max() / want[0])
    if not check("kept spectrum vs dense dW", err, TOL_SPECTRUM):
        raise AssertionError("kept spectrum is off the dense SVD")
    return tr


# -- phase 4: serving -------------------------------------------------------


def serve(cfg, trainer, *, seed: int, slots: int, prompts: int,
          prompt_len: int, new_tokens: int, page_rank: int = 16) -> None:
    gs = trainer.global_state
    rmax = max(max(v) for v in gs.ranks.values())
    max_rank = -(-rmax // page_rank) * page_rank
    registry = AdapterRegistry(gs.global_adapters, page_rank=page_rank,
                               num_pages=max_rank // page_rank + 1,
                               max_adapters=4, max_rank=max_rank)
    aid = registry.register("global", gs.global_adapters)
    capacity = prompt_len + new_tokens
    engine = ServeEngine(cfg, trainer.params, batch_slots=slots,
                         capacity=capacity, seed=seed, decode_impl="kernel",
                         registry=registry)
    if engine.lora_impl != "kernel":
        raise AssertionError("the kernel decode path must route LoRA "
                             "through bgmv")
    rng = np.random.default_rng(seed)
    sp = SamplingParams(max_tokens=new_tokens)
    served = {}
    for i in range(prompts):
        prompt = rng.integers(4, cfg.vocab_size, prompt_len).tolist()
        served[engine.submit(prompt, sp, adapter_id=aid if i % 2 else 0)] = i
    t0 = time.perf_counter()
    out = engine.run()
    secs = time.perf_counter() - t0
    if sorted(out) != sorted(served):
        raise AssertionError(f"served {sorted(out)}, submitted "
                             f"{sorted(served)}")
    for uid, toks in out.items():
        if len(toks) != new_tokens or not all(0 <= t < cfg.vocab_size
                                              for t in toks):
            raise AssertionError(f"request {uid}: bad output {toks}")
    print(f"  served {len(out)} requests ({prompts // 2} on adapter id {aid},"
          f" {prompts - prompts // 2} on the base), {new_tokens} tokens "
          f"each; [bring-up] {secs:.1f} s with compiles; "
          f"traces {engine.trace_counts}", flush=True)

    # one prefill chunk and one decode step: the kernel path, the dense
    # path, and the dense path on the same weights in float32
    C = engine.chunk
    ids = jnp.asarray([aid if i % 2 else 0 for i in range(slots)], jnp.int32)
    toks = jnp.asarray(rng.integers(4, cfg.vocab_size, (slots, C)), jnp.int32)
    n = jnp.asarray(rng.integers(1, C + 1, slots), jnp.int32)
    f32 = functools.partial(jax.tree.map, lambda x: x.astype(jnp.float32)
                            if jnp.issubdtype(x.dtype, jnp.floating) else x)
    paths = {"dense": (cfg, "dense", "xla", trainer.params,
                       registry.device_state),
             "kernel": (cfg, "kernel", "kernel", trainer.params,
                        registry.device_state),
             "float32": (cfg.replace(dtype="float32"), "dense", "xla",
                         f32(trainer.params), f32(registry.device_state))}
    logits = {}
    nxt = None
    for name, (c, impl, lora_impl, params, state) in paths.items():
        step = jax.jit(  # repro-lint: disable=jit-in-loop -- one path per pass
            make_serve_step(c, decode_impl=impl, lora_impl=lora_impl))
        with jax.default_matmul_precision(
                "highest" if name == "float32" else "default"):
            cache = T.init_cache(c, slots, capacity, jnp.dtype(c.dtype),
                                 prefill_chunk=C)
            pre, cache = step(params, state, cache, {
                "tokens": toks, "n_tokens": n, "adapter_ids": ids})
            if nxt is None:
                nxt = jnp.argmax(pre, -1).astype(jnp.int32)[:, None]
            dec, _ = step(params, state, cache, {
                "tokens": nxt, "n_tokens": jnp.ones_like(n),
                "adapter_ids": ids})
        logits[name] = (pre, dec)
    bad = []
    for i, step_name in enumerate(("prefill", "decode")):
        kern, dense, ref32 = (logits[k][i] for k in
                              ("kernel", "dense", "float32"))
        agree = float(jnp.mean(jnp.argmax(kern, -1) == jnp.argmax(dense, -1)))
        e_dense = rel_err(dense, ref32)
        print(f"  {step_name} logits: dense vs float32 {e_dense:.3e}, kernel "
              f"vs float32 {rel_err(kern, ref32):.3e}, argmax agreement "
              f"kernel/dense {agree:.3f}")
        if not check(f"{step_name} logits kernel vs dense",
                     rel_err(kern, dense), LOGITS_BOUND_FACTOR * e_dense):
            bad.append(step_name)
    if bad:
        raise AssertionError(f"kernel serving path off the dense path: {bad}")


# -- four chips: the sharded round ------------------------------------------


class SpanRecordingRunner(ShardedCohortRunner):
    """The sharded cohort runner, recording the devices each trained cohort
    block spans."""

    def __init__(self, mesh=None):
        super().__init__(mesh=mesh)
        self.spans = []

    def _train_fn(self, ctx):
        fn = super()._train_fn(ctx)

        def train(params, stacked, batch):
            out = fn(params, stacked, batch)
            self.spans += [len(x.sharding.device_set)
                           for x in jax.tree.leaves(out)]
            return out

        return train


def dense_updates(gs):
    """{path: (L, m, n) fp32} products (scale·B_g) A_g per layer: the global
    update itself, free of the sign and rotation freedom of the factors."""
    out = {}
    for path in adapter_leaf_paths(gs.global_adapters):
        B, A = fold_scale(get_path(gs.global_adapters, path))
        B, A = np.asarray(B, np.float32), np.asarray(A, np.float32)
        out[path] = B @ A
    return out


def sharded_round(cfg, *, seed: int, chips: int, **sizes) -> None:
    devices = jax.devices()[:chips]
    runner = SpanRecordingRunner(mesh=make_fed_mesh(chips, devices=devices))
    agg = ShardedFloristAggregator(tau=TAU, svd_method="gram",
                                   mesh=Mesh(np.asarray(devices), ("model",)))
    results = {}
    for name, tr in (
            ("sharded", make_trainer(cfg, seed=seed, aggregator=agg,
                                     runner=runner, **sizes)),
            ("single", make_trainer(
                cfg, seed=seed, runner="cohort",
                aggregator=FloristAggregator(tau=TAU, svd_method="gram"),
                **sizes))):
        t0 = time.perf_counter()
        rec = tr.run_round(0)
        print(f"  {name}: eval_loss {rec.eval_loss:.4f} ranks "
              f"{tr.aggregator.client_ranks} [bring-up] "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        if not np.isfinite(rec.eval_loss):
            raise AssertionError(f"{name}: eval loss {rec.eval_loss}")
        assert_finite_tree(tr.global_state.global_adapters, name)
        results[name] = (rec, tr.global_state)

    print(f"  cohort blocks' output arrays span {sorted(set(runner.spans))} "
          f"devices")
    if not runner.spans or set(runner.spans) != {chips}:
        raise AssertionError(f"sharded cohort arrays do not span {chips} "
                             f"devices: {sorted(set(runner.spans))}")
    (rs, gs_s), (r1, gs_1) = results["sharded"], results["single"]
    print_ranks(gs_s)
    if gs_s.ranks != gs_1.ranks:
        raise AssertionError(f"kept ranks differ: sharded {gs_s.ranks} vs "
                             f"single {gs_1.ranks}")
    bad = []
    worst_s = max(
        float(np.abs(np.asarray(gs_s.spectra[path][l][:p])
                     - np.asarray(gs_1.spectra[path][l][:p])).max()
              / np.asarray(gs_1.spectra[path][l])[0])
        for path in gs_1.spectra for l, p in enumerate(gs_1.ranks[path]))
    if not check("kept spectra sharded vs single", worst_s, TOL_SHARDED):
        bad.append(f"spectra {worst_s:.2e}")
    du_s, du_1 = dense_updates(gs_s), dense_updates(gs_1)
    worst_u = max(rel_err(du_s[p], du_1[p]) for p in du_1)
    if not check("global dW sharded vs single", worst_u, TOL_SHARDED):
        bad.append(f"global update {worst_u:.2e}")
    loss_err = abs(rs.eval_loss - r1.eval_loss) / abs(r1.eval_loss)
    if not check("eval loss sharded vs single", loss_err, TOL_SHARDED):
        bad.append(f"eval loss {loss_err:.2e}")
    if bad:
        raise AssertionError(f"sharded round off the single-device round: "
                             f"{bad}")


# -- entry point ------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded round on a 4-chip mesh "
                         "against the single-device round")
    args = ap.parse_args(argv)
    print(f"[bring-up] compile cache: {enable_compile_cache()}")

    devices = jax.devices()
    dev = devices[0]
    print(f"== phase: device\n  jax.devices(): {devices}")
    print(f"  platform {dev.platform}  device_kind {dev.device_kind}  "
          f"count {len(devices)}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); this check "
              "has no CPU fallback", file=sys.stderr)
        return 1
    chips = 4 if args.four_chips else 1
    if len(devices) < chips:
        print(f"chip_smoke: --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    cfg = get_config(MODEL)
    log = PhaseLog()
    if args.four_chips:
        # each chip trains one client of 2 x 256 tokens per step; the
        # single-device side vmaps the whole cohort (at most 4 clients)
        with log.phase("sharded round (4 chips) vs single device"):
            sharded_round(cfg, seed=args.seed, chips=chips,
                          ranks=((16, 5), (64, 5)), sample=4, batch_size=2,
                          local_steps=2, seq_len=256)
    else:
        mla, rwkv = get_config("deepseek-v3-671b"), get_config("rwkv6-1.6b")
        with log.phase("kernels"):
            check_kernels(kernel_cases(
                args.seed, d=cfg.d_model, H=cfg.num_heads,
                K=cfg.num_kv_heads, hd=cfg.head_dim, slots=8, chunk=8,
                cap=512, batch=4, seq=512, rank=64,
                mla=(mla.num_heads, mla.kv_lora_rank, mla.qk_rope_head_dim,
                     (mla.qk_nope_head_dim + mla.qk_rope_head_dim) ** -0.5),
                rwkv=(rwkv.d_model // rwkv.rwkv_head_dim,
                      rwkv.rwkv_head_dim)))
        with log.phase("federated round"):
            # the paper's 40/20/20/10/10 rank mix over 4..64, at 10 clients
            trainer = federated_round(
                cfg, seed=args.seed, rounds=2,
                ranks=((4, 4), (8, 2), (16, 2), (32, 1), (64, 1)), sample=4,
                batch_size=4, local_steps=3, seq_len=512)
        with log.phase("serving"):
            serve(cfg, trainer, seed=args.seed, slots=8, prompts=8,
                  prompt_len=128, new_tokens=32)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
