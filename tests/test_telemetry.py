"""The program's spans and compile counter (``repro.common.telemetry``)."""
import collections
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.common import telemetry
from repro.common.config import FedConfig, LoRAConfig, ModelConfig, OptimConfig
from repro.core.federated import FederatedTrainer

CFG = ModelConfig(name="tele-tiny", family="dense", num_layers=2, d_model=64,
                  num_heads=4, num_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=256, dtype="float32")


def _since(lo):
    return telemetry.records(lo=lo)


def test_nesting_parents_rounds_and_self_time():
    lo = time.perf_counter()
    with telemetry.span("t.round", round=7):
        with telemetry.span("t.outer", client=3) as outer:
            time.sleep(0.01)
            with telemetry.span("t.inner"):
                time.sleep(0.02)
            outer.set(bytes=12)
    with telemetry.span("t.free"):
        pass
    by = {r.name: r for r in _since(lo)}
    assert by["t.round"].parent is None and by["t.round"].round == 7
    assert by["t.outer"].parent == "t.round" and by["t.outer"].round == 7
    assert by["t.inner"].parent == "t.outer" and by["t.inner"].round == 7
    assert by["t.free"].parent is None and by["t.free"].round is None
    assert by["t.outer"].attrs == {"client": 3, "bytes": 12}
    assert by["t.round"].start <= by["t.outer"].start \
        <= by["t.inner"].start < by["t.inner"].end <= by["t.outer"].end
    assert outer.seconds == pytest.approx(
        by["t.outer"].end - by["t.outer"].start)
    n, secs = telemetry.total("t.outer", lo)
    own = telemetry.self_time("t.outer", lo)
    inner = by["t.inner"].end - by["t.inner"].start
    assert n == 1 and own == pytest.approx(secs - inner)
    assert 0.01 <= own < secs and inner >= 0.02


def test_spans_close_on_exceptions():
    lo = time.perf_counter()
    with pytest.raises(KeyError):
        with telemetry.span("t.raises"):
            raise KeyError("x")
    with telemetry.span("t.after"):
        pass
    by = {r.name: r for r in _since(lo)}
    assert by["t.after"].parent is None


def test_window_filter_and_dropped_count():
    rec = telemetry.Recorder(maxlen=4)
    marks = []
    for i in range(6):
        marks.append(time.perf_counter())
        with rec.span("w", i=i):
            pass
    assert rec.dropped == 2 and len(rec.spans) == 4
    assert [r.attrs["i"] for r in rec.records()] == [2, 3, 4, 5]
    # [lo, hi): a span starting at hi is out, one starting at lo is in
    assert rec.total("w", marks[3], marks[5])[0] == 2
    assert rec.total("w", marks[3])[0] == 3
    assert rec.total("other", marks[3]) == (0, 0.0)
    # records from before marks[2] were dropped; from marks[2] on none was
    assert not rec.complete(marks[0]) and not rec.complete(marks[1])
    assert rec.complete(marks[2])


def test_compile_charged_to_the_innermost_span():
    @jax.jit
    def fresh(x):
        return jnp.sin(x) * 3.0 + 1.0

    x = jnp.arange(5.0)
    lo = time.perf_counter()
    with telemetry.span("t.compile.outer"):
        with telemetry.span("t.compile.inner"):
            fresh(x).block_until_ready()
    mid = time.perf_counter()
    got = telemetry.compiles(lo, mid)
    assert got["t.compile.inner"][0] == 1 and got["t.compile.inner"][1] > 0
    assert "t.compile.outer" not in got
    with telemetry.span("t.compile.again"):
        fresh(x).block_until_ready()
    assert telemetry.compiles(mid, time.perf_counter()) == {}


def test_span_in_a_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.span("t.traced", client=1):
            jnp.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)
    data = jax.profiler.ProfileData.from_file(path[0])
    names = [(plane.name, e.name) for plane in data.planes
             for line in plane.lines for e in line.events]
    assert any(p.startswith("/host:") and n == "repro.t.traced"
               for p, n in names)


def test_round_spans():
    """One two-client round records the layer spans with their parents."""
    fed = FedConfig(num_clients=2, clients_per_round=2, method="florist",
                    tau=0.9, heterogeneous=True,
                    rank_distribution=((4, 1), (8, 1)), seed=0)
    tr = FederatedTrainer(CFG, fed, LoRAConfig(rank=8, alpha=8.0),
                          OptimConfig(lr=3e-3), batch_size=4, local_steps=2,
                          seq_len=32)
    lo = time.perf_counter()
    rec = tr.run_round(0)
    spans = _since(lo)
    count = collections.Counter(r.name for r in spans)
    parent = {r.name: r.parent for r in spans}
    assert count["round"] == 1
    for name in ("wire.up", "gate", "client.batches", "client.train"):
        assert count[name] == 2, name
    assert count["client.init"] == 2          # one per distinct rank
    for name in ("finalize", "finalize.core", "finalize.wait",
                 "finalize.build", "wire.down", "merge", "eval"):
        assert count[name] == 1, name
    for name in ("client.init", "client.batches", "client.train", "wire.up",
                 "gate", "finalize", "wire.down", "merge", "eval"):
        assert parent[name] == "round", name
    for name in ("finalize.core", "finalize.wait", "finalize.build"):
        assert parent[name] == "finalize", name
    assert {r.round for r in spans} == {0}
    assert sorted(r.attrs["client"] for r in spans if r.name == "gate") \
        == [0, 1]
    up = [r.attrs["bytes"] for r in spans if r.name == "wire.up"]
    down = [r.attrs["bytes"] for r in spans if r.name == "wire.down"]
    assert sum(up) == rec.upload_bytes and down == [rec.download_bytes]
    (rnd,) = [r for r in spans if r.name == "round"]
    assert rec.wall_secs == pytest.approx(rnd.end - rnd.start)
    # the first round compiles its train and eval steps inside the spans
    compiled = telemetry.compiles(lo, time.perf_counter())
    assert compiled["client.train"][0] >= 1 and compiled["eval"][0] >= 1
