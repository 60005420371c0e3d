"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``, whose ``model_type`` names its family
``bench/families/<model_type>.py`` and whose ``reference`` its plain
reference ``references/<reference>.py``) and a traffic mix
(``traffic/<mix>.json``); the mix names its driver
(``drivers/<driver>.py``), and each per-layer metric has its reader
(``metrics/<metric>.py``).  Correctness limits are in
``limits/<workload>.json``, one for each reading the driver makes.  The
driver gets the first ``chips`` devices.  A cell is added by adding such
files and entries; this file does not change.

The run: device check (a TPU with enough chips, else exit 3 with no
result), the persistent compile cache, set-up by the driver (weights,
inputs, warm-up of every shape the window uses), the window of
``--seconds``, the device memory peak, then the program's state is freed
and the reference decides ``correct``.  With ``--trace 1`` the window runs
under the profiler and the per-layer metrics are read from the trace and
the harness's host spans; otherwise the end-to-end metrics are reported.
The last line of standard output is one JSON object.
"""
from __future__ import annotations

import os
import sys
import time


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux), or now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


T_START = _process_start()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "src"), HERE]

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402


def fail(msg: str, code: int = 3):
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, workload: str):
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


class CompileLog:
    """Counts backend compiles and persistent-cache events (a copy of the
    repository's bring-up ``PhaseLog`` listener)."""

    def __init__(self):
        import jax
        self.counts = {}
        self.secs = {}
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if "compil" in event:
            self.counts[event] = self.counts.get(event, 0) + 1
            self.secs[event] = self.secs.get(event, 0.0) + secs

    def _evt(self, event, **_):
        if "compil" in event:
            self.counts[event] = self.counts.get(event, 0) + 1

    def snapshot(self):
        return dict(self.counts), dict(self.secs)

    @staticmethod
    def delta(a, b):
        return {k: b[0].get(k, 0) - a[0].get(k, 0) for k in b[0]
                if b[0].get(k, 0) != a[0].get(k, 0)}

    def backend_compiles(self, snap) -> int:
        return snap[0].get("/jax/core/compile/backend_compile_duration", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"unknown workload {args.workload!r}; known: {sorted(cells)}", 2)
    cell = cells[args.workload]

    from repro.common.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found {dev.platform}; the benchmark has no CPU "
             "fallback")
    if len(devices) < cell["chips"]:
        fail(f"{args.workload} needs {cell['chips']} chips, JAX found "
             f"{len(devices)}")
    from bench import compare as cmp
    from bench import model, traffic
    result = run_cell(bench, cell, model.load_config(cell["config"]),
                      traffic.load_traffic(cell["traffic"]),
                      cmp.load_limits(cell["name"]), args, devices, cache)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(bench, cell, c, t, limits, args, devices, cache,
             clock=time.perf_counter) -> dict:
    """Everything after the device check, returning the result object;
    the tests call it on the CPU with small files of their own."""
    import jax

    from bench import compare as cmp
    from bench import peaks, spans as sp, trace as tr

    dev = devices[0]
    used = devices[:cell["chips"]]
    pk = peaks.peaks_for(dev.device_kind) if dev.platform == "tpu" else None
    e2e, layer = cell_metrics(bench, cell["name"])
    log = CompileLog()
    print(f"[bench] {cell['name']} seed {args.seed} on {dev.platform} "
          f"{dev.device_kind} x{len(devices)}; compile cache {cache}",
          file=sys.stderr, flush=True)

    driver = load_module("drivers", t["driver"])
    spans = sp.Spans()
    snap0 = log.snapshot()
    run = driver.setup(c, t, args.seed, spans, args.seconds, used)
    snap1 = log.snapshot()
    made = set(run.readings_made)
    if made != set(limits):
        raise ValueError(
            f"limits/{cell['name']}.json names {sorted(limits)}; this cell's "
            f"runner makes {sorted(made)} (no limit for "
            f"{sorted(made - set(limits))}, no reading for "
            f"{sorted(set(limits) - made)})")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    if trace_dir:
        # host spans only: the Python tracer would add its own cost to
        # every host call in the window
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.time() - T_START
    with spans.span("window"):
        t0 = clock()
        res = run.window(args.seconds, clock)
        t1 = clock()
    if trace_dir:
        jax.profiler.stop_trace()
    snap2 = log.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    print(f"[bench] set-up {setup_s:.3f} s; compile events in set-up "
          f"{log.delta(snap0, snap1)}, in the window {log.delta(snap1, snap2)}",
          file=sys.stderr, flush=True)
    print(f"[bench] peak_bytes_in_use {peak}; compiled memory "
          f"{run.memory_report()}", file=sys.stderr, flush=True)
    window_compiles = (log.backend_compiles(snap2)
                       - log.backend_compiles(snap1))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {}
    breakdown = None
    if trace_dir:
        try:
            events = tr.load_events(tr.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        summary = tr.reduce_events(events)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = dict(run.layer_context(), trace=summary, spans=spans,
                   window=(t0, t1), peaks=pk, chips=len(used))
        for m in layer:
            v = load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = {"device_ops": [[n, s] for n, s in summary.top_ops],
                     "idle_gaps": [[n, s] for n, s in summary.idle_gaps]}
        print(f"[bench] trace: busy {summary.busy_s:.6f} s of "
              f"{summary.window_s:.6f} s; executables {summary.executables}",
              file=sys.stderr, flush=True)
    else:
        for m in e2e:
            if m["name"] == "setup_s":
                out["setup_s"] = {"value": setup_s, "unit": m["unit"]}
            elif m["name"] in res["metrics"]:
                out[m["name"]] = {"value": res["metrics"][m["name"]],
                                  "unit": m["unit"]}
    info = dict(res.get("info", {}), window_backend_compiles=window_compiles)
    print(f"[bench] window {t1 - t0:.3f} s: {info}", file=sys.stderr, flush=True)

    run.release()
    readings = run.check()
    checks = [cmp.Check(k, v, limits.get(k)) for k, v in readings.items()]
    correct = cmp.verdict(checks)
    for ch in checks:
        print(f"check {ch.name} {ch.value!r} limit {ch.limit!r} "
              f"{'ok' if ch.ok else 'FAIL'}", file=sys.stderr, flush=True)
    result = {"correct": correct, "attempted": res["attempted"],
              "failed": res["failed"], "metrics": out, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {ch.name: {"value": ch.value, "limit": ch.limit}
                        for ch in checks}
    return result


if __name__ == "__main__":
    sys.exit(main())
