"""CPU rehearsals: the command refuses a CPU and a bare checkout, and each
driver runs end to end at a tiny size through the harness's own path
(``run.run_cell``, everything after the look for a chip)."""
import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

import run
from bench import compare

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
DATA = os.path.join(HERE, "data")


def data(name):
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Args:
    def __init__(self, seed, seconds, trace):
        self.seed, self.seconds, self.trace = seed, seconds, trace


CELLS = {"round": ("round.qwen2-0.5b.silo10", "tiny_round", 1.0)}


def rehearse(kind, seed, trace=0, fault=None, monkeypatch=None):
    """One run of the tiny cell of ``kind`` under the real cell's name,
    metrics and limits; ``fault`` is planted in the timed path."""
    name, traffic, seconds = CELLS[kind]
    if fault is not None:
        real = run.load_module

        def load(kind_, mod):
            m = real(kind_, mod)
            if kind_ == "drivers":
                setup = m.setup
                m.setup = lambda *a, **k: setup(*a, fault=fault, **k)
            return m

        monkeypatch.setattr(run, "load_module", load)
    cell = {"name": name, "config": "tiny", "traffic": traffic, "chips": 1}
    return run.run_cell(bench(), cell, data("tiny"), data(traffic),
                        compare.load_limits(name), Args(seed, seconds, trace),
                        jax.devices(), "none")


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "round.qwen2-0.5b.silo10", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_a_bare_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py", "--workload",
                        "round.qwen2-0.5b.silo10", "--seed", "1", "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_driver_end_to_end(kind):
    res = rehearse(kind, seed=2**33 + 17)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    e2e, _ = run.cell_metrics(bench(), CELLS[kind][0])
    assert sorted(res["metrics"]) == sorted(m["name"] for m in e2e)
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_driver_traced(kind):
    res = rehearse(kind, seed=23, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    _, layer = run.cell_metrics(bench(), CELLS[kind][0])
    # the CPU has no device trace: only host-clock metrics can be read
    assert set(res["metrics"]) <= {m["name"] for m in layer}
