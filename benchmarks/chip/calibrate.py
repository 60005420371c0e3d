"""Readings for setting a cell's correctness limits, in one process.

    python3 benchmarks/chip/calibrate.py --workload <name> \
        --seeds 1,2,3 [--control 4,5,6] [--fault half_batch=7,8,9] \
        [--out readings.jsonl]

For each seed it builds the cell as a run does (its set-up) and prints the numbers that decide
``correct``: for the program (``--seeds``), for the control, the plain
reference computed in the precision below the configuration's in the
program's place (``--control``), and for each planted fault (``--fault``).
The limits in ``limits/<workload>.json`` are set from these readings: above
the program's largest, below the smallest the control gives.  The
benchmark's own runs never run the control or a fault.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]


def _seeds(s: str):
    return [int(x) for x in s.split(",") if x]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", action="append", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax

    import run
    from bench import model, spans as sp, traffic

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = {w["name"]: w for w in json.load(f)["workloads"]}[args.workload]
    c = model.load_config(cell["config"])
    t = traffic.load_traffic(cell["traffic"])
    driver = run.load_module("drivers", t["driver"])
    jobs = [("program", s, None) for s in _seeds(args.seeds)]
    jobs += [("control", s, None) for s in _seeds(args.control)]
    for spec in args.fault:
        name, seeds = spec.split("=")
        jobs += [("fault:" + name, s, name) for s in _seeds(seeds)]
    out = open(args.out, "a") if args.out else None
    dev = jax.devices()[0]
    for kind, seed, fault in jobs:
        t0 = time.perf_counter()
        cell_run = driver.setup(c, t, seed, sp.Spans(), 0.0,
                                jax.devices()[:cell["chips"]], fault=fault)
        t1 = time.perf_counter()
        observed = (cell_run.control_observed() if kind == "control"
                    else cell_run.program_observed())
        cell_run.release()
        readings = cell_run.readings(observed)
        line = {"workload": args.workload, "kind": kind, "seed": seed,
                "readings": readings, "setup_s": t1 - t0,
                "total_s": time.perf_counter() - t0,
                "device": f"{dev.platform} {dev.device_kind}"}
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()
        del cell_run, observed
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
