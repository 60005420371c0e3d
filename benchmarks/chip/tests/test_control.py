"""The control: the plain reference computed in the precision below the
configuration's (fp8 for the bf16 model) put in the program's place must
come out not correct under each cell's limits, while the program itself
passes.  At a tiny size on the CPU; the chip readings at the cells' own
sizes are in PERF.md."""
import jax
import pytest

from bench import compare
from bench import spans as sp
from test_rehearsal import CELLS, data

import run


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_control_fails_the_limits(kind):
    name, traffic, seconds = CELLS[kind]
    t = data(traffic)
    driver = run.load_module("drivers", t["driver"])
    cell = driver.setup(data("tiny"), t, 41, sp.Spans(), seconds,
                        jax.devices()[:1])
    cell.release()
    limits = compare.load_limits(name)
    program = cell.readings(cell.program_observed())
    control = cell.readings(cell.control_observed())
    assert compare.verdict(compare.Check(k, v, limits.get(k))
                           for k, v in program.items())
    assert not compare.verdict(compare.Check(k, v, limits.get(k))
                               for k, v in control.items()), control
