"""The readers of the program's spans and compile counter report a finite
number in a traced CPU rehearsal of the round cell."""
import math

import run
from test_rehearsal import CELLS, bench, rehearse

PROGRAM = ("client_host_ms", "wire_ms", "gate_ms", "finalize_host_ms",
           "finalize_wait_ms", "eval_ms", "compile_ms.round")


def test_program_span_metrics_reported():
    _, layer = run.cell_metrics(bench(), CELLS["round"][0])
    assert set(PROGRAM) <= {m["name"] for m in layer}
    res = rehearse("round", seed=2**32 + 5, trace=1)
    assert res["correct"] is True, res["checks"]
    got = res["metrics"]
    for name in PROGRAM:
        v = got[name]["value"]
        assert math.isfinite(v), name
        assert v >= 0 if name == "compile_ms.round" else v > 0, name
    # the program's finalize runs inside the harness's span around the
    # same call
    assert got["finalize_host_ms"]["value"] + got["finalize_wait_ms"]["value"] \
        <= got["finalize_ms"]["value"] * (1 + 1e-9)
