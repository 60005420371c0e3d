"""CPU rehearsals: the command refuses a CPU and a bare checkout, and each
driver runs end to end at a tiny size through the harness's own path
(``run.run_cell``, everything after the look for a chip), under each
deployment a round cell can name (runner, method, chips)."""
import json
import os
import shutil
import subprocess
import sys
from unittest import mock

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


def rehearse(kind, seed, trace=0, fault=None, traffic=None, limits=None,
             chips=1):
    """One run of the tiny cell of ``kind`` under the real cell's name and
    metrics; ``traffic`` and ``limits`` default to the tiny mix and the real
    cell's limits, and ``fault`` is planted in the timed path."""
    name, mix, seconds = CELLS[kind]
    real = run.load_module

    def load(kind_, mod):
        m = real(kind_, mod)
        if fault is not None and kind_ == "drivers":
            setup = m.setup
            m.setup = lambda *a, **k: setup(*a, fault=fault, **k)
        return m

    cell = {"name": name, "config": "tiny", "traffic": mix, "chips": chips}
    with mock.patch.object(run, "load_module", load):
        return run.run_cell(
            bench(), cell, data("tiny"), traffic or data(mix),
            limits if limits is not None else compare.load_limits(name),
            Args(seed, seconds, trace), jax.devices(), "none")


#: (runner, method, chips): the deployments a round cell can name
DEPLOYMENTS = [("sequential", "florist", 1), ("cohort", "florist", 1),
               ("sharded_cohort", "florist_sharded", 4)]


def deployment_runs(runner, method, chips, seed, faults=()):
    """The tiny round deployed so, under its own tiny limits: one clean run,
    then one for each planted fault; each as (correct, checks)."""
    t = dict(data("tiny_round"), runner=runner, method=method)
    limits = data(f"tiny_limits.{runner}")["limits"]
    out = []
    for fault in (None,) + tuple(faults):
        res = rehearse("round", seed, fault=fault, traffic=t, limits=limits,
                       chips=chips)
        out.append((res["correct"], res["checks"]))
    return out


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


@pytest.mark.parametrize("runner,method,chips", DEPLOYMENTS)
def test_deployment_end_to_end(runner, method, chips):
    """Each deployment ends correct; on four devices (in a process of its
    own: the flag that makes them cannot be set in this one) every planted
    fault fails the check."""
    from bench import faults

    seed = 2**31 + 7
    if chips == 1:
        runs = deployment_runs(runner, method, chips, seed)
    else:
        planted = faults.ROUND_FAULTS + faults.MESH_FAULTS
        code = ("import json, test_rehearsal as r; print(json.dumps("
                f"r.deployment_runs({runner!r}, {method!r}, {chips}, {seed}, "
                f"{planted!r})))")
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}",
                   PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"),
                                               CHIP, HERE]))
        p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-4000:]
        runs = json.loads(p.stdout.strip().splitlines()[-1])
        assert len(runs) == 1 + len(planted)
        for fault, (correct, checks) in zip(planted, runs[1:]):
            assert correct is False, (fault, checks)
    correct, checks = runs[0]
    assert correct is True, checks
    assert set(checks) == set(data(f"tiny_limits.{runner}")["limits"])


@pytest.mark.parametrize("runner,limits_of", [("sequential", "cohort"),
                                              ("cohort", "sequential")])
def test_set_up_fails_where_limits_and_readings_differ(runner, limits_of):
    """A cell cannot lose a check in silence: a limits file that leaves out
    a reading the runner makes, or names one it cannot make, stops set-up."""
    t = dict(data("tiny_round"), runner=runner, method="florist")
    with pytest.raises(ValueError, match="this cell's runner makes"):
        rehearse("round", 3, traffic=t,
                 limits=data(f"tiny_limits.{limits_of}")["limits"])
