"""The trace reduction on recorded events: the busy union, device time
per executable, and the attribution of idle gaps to host spans."""
import json
import os

import pytest

from bench import trace as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(kind, name, s, e, dev=0):
    return (kind, dev if kind != "span" else -1, name, s * 1e6, e * 1e6)


def test_busy_union_executables_and_gaps():
    events = [
        ev("span", "window", 0, 100),
        ev("span", "wire", 30, 45),
        ev("span", "finalize", 70, 90),
        ev("module", "jit_train_step(11)", 0, 30),
        ev("module", "jit_train_step(11)", 45, 70),
        ev("module", "jit_eval_step(3)", 90, 110),   # runs past the window
        ev("op", "%fusion.1 = f32[] fusion()", 0, 20),
        ev("op", "%fusion.2 = f32[] fusion()", 10, 30),   # overlaps the first
        ev("op", "%convolution.5 = bf16[] convolution()", 45, 70),
        ev("op", "%fusion.9 = f32[] fusion()", 90, 110),
        ev("op", "%fusion.3 = f32[] fusion()", -10, -5),  # before the window
    ]
    s = tr.reduce_events(events)
    assert s.window_s == pytest.approx(0.1)
    # busy: [0, 30] + [45, 70] + [90, 100] (clipped) = 65 ms
    assert s.busy_s == pytest.approx(0.065)
    assert s.devices == 1
    assert s.device_seconds("jit_train_step") == (2, pytest.approx(0.055))
    assert s.device_seconds("jit_eval_step") == (1, pytest.approx(0.02))
    assert s.device_seconds("jit_step") == (0, 0.0)
    ops = dict(s.top_ops)
    # fusion.2 overlaps fusion.1 from 10 ms: that time counts once
    assert ops["fusion"] == pytest.approx(0.010 + 0.020 + 0.010)
    assert ops["convolution"] == pytest.approx(0.025)
    # idle gaps: [30, 45] under "wire", [70, 90] under "finalize"
    assert s.idle_gaps == [("finalize", pytest.approx(0.02)),
                           ("wire", pytest.approx(0.015))]
    assert s.spans == {"wire": [pytest.approx(0.015)],
                       "finalize": [pytest.approx(0.02)]}


def test_busy_is_averaged_over_devices_and_gaps_read_device_0():
    events = [ev("span", "window", 0, 10),
              ev("op", "%a = f32[] add()", 0, 10, dev=0),
              ev("op", "%a = f32[] add()", 0, 5, dev=1)]
    s = tr.reduce_events(events)
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.0075)
    assert s.idle_gaps == []


def test_nested_operations_count_their_own_time():
    events = [ev("span", "window", 0, 100),
              ev("op", "%while.1 = () while()", 0, 80),
              ev("op", "%fusion.1 = f32[] fusion()", 10, 30),
              ev("op", "%while.2 = () while()", 40, 70),
              ev("op", "%fusion.2 = f32[] fusion()", 45, 65),
              ev("op", "%copy.1 = f32[] copy()", 85, 95)]
    s = tr.reduce_events(events)
    ops = dict(s.top_ops)
    assert ops["while"] == pytest.approx(0.030 + 0.010)
    assert ops["fusion"] == pytest.approx(0.040)
    assert ops["copy"] == pytest.approx(0.010)
    assert sum(ops.values()) == pytest.approx(s.busy_s)


def test_one_window_span_is_required():
    with pytest.raises(ValueError):
        tr.reduce_events([ev("op", "%a = f32[] add()", 0, 1)])


def test_names():
    assert tr.executable_name("jit_train_step(1234567)") == "jit_train_step"
    assert tr.op_name("%convolution_reduce_fusion.12 = bf16[] fusion(x)") == \
        "convolution_reduce_fusion"
    assert tr.op_name("%copy-start.3 = (bf16[2]) copy-start(x)") == "copy-start"


def test_recorded_chip_trace():
    """Events recorded by ``load_events`` from a traced run on a TPU v5e
    (two jitted programs, three iterations, host spans around them)."""
    path = os.path.join(DATA, "trace_v5e_small.json")
    if not os.path.exists(path):
        pytest.skip("no recorded chip trace in tests/data")
    with open(path) as f:
        rec = json.load(f)
    s = tr.reduce_events([tuple(e) for e in rec["events"]])
    assert s.devices == 1
    assert 0 < s.busy_s < s.window_s
    for name, (runs, secs) in rec["expect"]["executables"].items():
        assert s.device_seconds(name) == (runs, pytest.approx(secs, rel=1e-6))
    assert s.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-6)
    assert [n for n, _ in s.idle_gaps[:2]] == rec["expect"]["top_gap_spans"]
