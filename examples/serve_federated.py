"""FLoRIST live round->deploy loop: federate, hot-swap, serve — concurrently.

The paper's output is not a one-shot artifact: every federated round produces
a NEW global adapter, and a deployment keeps serving while training continues.
This example runs that loop for real.  A single :class:`ServeEngine` stays up
the whole time, mounted on an :class:`AdapterRegistry`; after each round the
fresh ``global_adapters`` tree is published with ``registry.swap`` (an atomic
version bump: new pages, new id, name repointed) while requests admitted
against the PREVIOUS version keep decoding in their slots untouched.  Requests
submitted after the swap resolve to the new version, so for a few engine steps
both generations of the adapter serve side by side in one batch — and the
jitted step never retraces, because registry churn only rewrites fixed-shape
device pools.

With ``--mesh N`` the engine decodes tensor-parallel on a ``(data=1,
model=N)`` mesh — same tokens, same trace counts, the registry's paged
pools sharded along with the base weights.  On a CPU host export
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first (the flag is
read once, at backend init).

  PYTHONPATH=src python examples/serve_federated.py [--rounds 2] \
      [--requests-per-round 4] [--batch-slots 4] [--temperature 0.0] \
      [--mesh 0]
"""
import argparse

import numpy as np

from repro.common.compile_cache import enable_compile_cache
from repro.common.config import FedConfig, LoRAConfig, ModelConfig, OptimConfig
from repro.core.federated import FederatedTrainer
from repro.serve.adapters import AdapterRegistry
from repro.serve.engine import SamplingParams, ServeEngine


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--requests-per-round", type=int, default=2)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-tokens", type=int, default=12)
    ap.add_argument("--overlap-steps", type=int, default=3,
                    help="engine steps run between publish and the next "
                         "round, so old/new adapter versions share a batch")
    ap.add_argument("--decode-impl", default="streamed",
                    choices=["dense", "streamed", "kernel"],
                    help="serving attention interior (streamed = "
                         "ring-flash-decode hot loop)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="model-parallel devices for the serve mesh "
                         "(0 = no mesh, single device)")
    args = ap.parse_args()

    cfg = ModelConfig(name="serve-fed-tiny", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                      d_ff=128, vocab_size=256, dtype="float32")
    fed = FedConfig(num_clients=8, clients_per_round=4, method="florist",
                    tau=0.9, homogeneous_rank=8, seed=0)
    trainer = FederatedTrainer(cfg, fed, LoRAConfig(rank=8, alpha=8.0),
                               OptimConfig(lr=3e-3), batch_size=8,
                               local_steps=2, seq_len=32)

    # One engine, up for the whole run — even before the first round lands
    # (every slot starts on base id 0).  The registry's paged pools are the
    # deploy surface; trainer rounds just publish into them.
    registry = AdapterRegistry(trainer.A_init_full, page_rank=4,
                               num_pages=16, max_adapters=8, max_rank=8)
    mesh = None
    if args.mesh:
        from repro.topology import make_serve_mesh
        mesh = make_serve_mesh(args.mesh)
    eng = ServeEngine(cfg, trainer.params, batch_slots=args.batch_slots,
                      capacity=64, seed=0, decode_impl=args.decode_impl,
                      registry=registry, mesh=mesh)
    rng = np.random.default_rng(0)
    sp = SamplingParams(temperature=args.temperature, top_k=8,
                        max_tokens=args.max_tokens)

    def submit_wave(n, adapter_id):
        return {eng.submit(rng.integers(1, cfg.vocab_size,
                                        rng.integers(3, 9)).tolist(),
                           sp, adapter_id=adapter_id): adapter_id
                for _ in range(n)}

    served_by = {}   # uid -> adapter id that served it
    outputs = {}     # uid -> generated tokens
    print(f"== live round->deploy loop: {cfg.name}, {args.rounds} rounds ==")
    for rnd in range(args.rounds):
        rec = trainer.run_round(rnd)
        # Publish this round's aggregate.  Round 0 registers the name;
        # later rounds swap — in-flight rows keep their old id's pages.
        if rnd == 0:
            new_id = registry.register("global", trainer.global_state.global_adapters)
        else:
            new_id = registry.swap("global", trainer.global_state.global_adapters)
        print(f"round {rnd}: eval_loss={rec.eval_loss:.4f} "
              f"download_rank={rec.download_rank:.0f} -> published id {new_id}"
              f" (live ids: {registry.live_ids})")

        served_by.update(submit_wave(args.requests_per_round, new_id))
        # Advance without draining: rows from the previous round's version
        # decode next to rows on the one just published.
        done = eng.run_steps(args.overlap_steps)
        outputs.update(done)
        in_flight = sorted({served_by[s.uid] for s in eng.slots
                            if s is not None})
        print(f"         batch now mixes adapter ids {in_flight} in flight")

    outputs.update(eng.run())
    for uid in sorted(outputs):
        print(f"  req {uid} [adapter id {served_by[uid]}]: {outputs[uid]}")
    by_id = {i: sum(1 for a in served_by.values() if a == i)
             for i in sorted(set(served_by.values()))}
    print(f"served {len(outputs)} requests across adapter versions {by_id} "
          f"over {args.batch_slots} slots")
    print(f"jitted step traces across {args.rounds} publishes: "
          f"{eng.trace_counts} (hot-swap never recompiles)")


if __name__ == "__main__":
    main()
