"""Compile each cell's executables for a described TPU v5e at their real
sizes, without a chip, and print what the compiler says of their memory.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_cells.py [--workload W]

Round cells: the client train step at each rank of the mix (the program
the sequential runner steps through; a cohort runner's vmapped program is
not compiled here), the eval step, and the FLoRIST finalize core for the
round's stack width.  Each is lowered from shapes alone (the
weights are never made) as the program builds it with its defaults, and
compiled for one chip of a described ``v5e:2x2``; its ``memory_analysis``
is printed beside the weights' bytes, to size batch and depth so that a
cell fits one chip's 16 GB.  A compile that passes is not a chip run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]


def _gib(n):
    return f"{n / 2**30:.2f} GiB"


def report(name, compiled, weights_bytes):
    m = compiled.memory_analysis()
    temp = getattr(m, "temp_size_in_bytes", 0)
    args = getattr(m, "argument_size_in_bytes", 0)
    out = getattr(m, "output_size_in_bytes", 0)
    print(json.dumps({"executable": name, "temp": _gib(temp),
                      "arguments": _gib(args), "outputs": _gib(out),
                      "weights": _gib(weights_bytes)}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", default=[])
    ap.add_argument("--config", help="a configuration not yet in a cell")
    ap.add_argument("--traffic", help="its traffic mix")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import model, traffic
    from repro.common.config import OptimConfig
    from repro.optim.adamw import adamw_init
    from repro.train.loss import bounded_loss_chunk
    from repro.train.step import make_eval_step, make_train_step

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                           sharding=one), tree)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    if args.config:
        cells = [{"name": f"{args.traffic}.{args.config}", "config": args.config,
                  "traffic": args.traffic}]
        args.workload = []
    for cell in cells:
        if args.workload and cell["name"] not in args.workload:
            continue
        c = model.load_config(cell["config"])
        t = traffic.load_traffic(cell["traffic"])
        fam = model.family(c)
        mc = fam.program_config(c)
        w = sds(jax.eval_shape(lambda: model.make_weights(c, 0)))
        wb = fam.Dims.from_config(c).weight_bytes
        print(f"== {cell['name']}", flush=True)
        if t["driver"] == "round":
            targets = tuple(t["targets"])

            def adapters(rank):
                return sds(jax.eval_shape(lambda: model.lora_init(
                    w, targets, rank, jax.random.PRNGKey(0))))

            optim = OptimConfig(**{k: tuple(v) if k == "betas" else v
                                   for k, v in t["optim"].items()})
            step = jax.jit(make_train_step(mc, optim, remat=False, loss_chunk=64))
            batch = sds({"tokens": jnp.zeros((t["batch"], t["seq_len"]), jnp.int32),
                         "loss_mask": jnp.zeros((t["batch"], t["seq_len"]), jnp.float32)})
            for r in sorted({r for r, _ in t["clients"]}):
                ad = adapters(r)
                opt = sds(jax.eval_shape(adamw_init, ad))
                report(f"train_step rank {r}",
                       step.lower(w, ad, opt, batch).compile(), wb)
            rows, sl = t["eval_rows"], t["eval_seq_len"]
            ev = jax.jit(make_eval_step(mc, bounded_loss_chunk(rows, sl, c["vocab_size"])))
            eb = sds({"tokens": jnp.zeros((rows, sl), jnp.int32),
                      "loss_mask": jnp.zeros((rows, sl), jnp.float32)})
            report("eval_step", ev.lower(w, None, eb).compile(), wb)
            from repro.core.svd import florist_core_batched
            width = sum(r * n for r, n in t["clients"])
            # (d_out, d_in) -> layers of every leaf of that shape
            stacks = {}
            for A, B, _ in model.adapter_leaves(adapters(1)).values():
                mn = (B.shape[-2], A.shape[-1])
                stacks[mn] = stacks.get(mn, 0) + A.shape[0]
            for (m_, n_), GL in sorted(stacks.items()):
                Bs = jax.ShapeDtypeStruct((GL, m_, width), jnp.float32, sharding=one)
                As = jax.ShapeDtypeStruct((GL, width, n_), jnp.float32, sharding=one)
                fn = jax.jit(lambda B, A: florist_core_batched(B, A, t["tau"], "svd", 0))
                report(f"finalize core ({GL}, {m_}, {width})",
                       fn.lower(Bs, As).compile(), 0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
