"""The abstract contract checker: full-matrix run on the repo's own
registrations (zero FLOPs, bounded wall-clock), fixture fidelity, and
fail-loud detection of seeded violations (fp64 upcast, host callback,
kernel/twin drift, non-divisible pspec)."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import fixtures as FX
from repro.analysis.contracts import (jaxpr_violations, pspec_violations,
                                      run_all, run_case)
from repro.analysis.registry import (Case, ContractCase, _Entry,
                                     contract_entries, load_registrations)


# -- the repo's own contracts -------------------------------------------------

def test_registry_covers_major_entrypoints():
    names = set(load_registrations())
    expected = {"train.step", "serve.step", "serve.engine_step",
                "serve.decode_burst", "agg.florist_finalize", "agg.thin_svd",
                "agg.sharded_florist", "kernel.ring_decode",
                "kernel.mla_ring_decode", "kernel.bgmv", "kernel.wkv6",
                "kernel.flash_attention", "kernel.lora_matmul",
                "kernel.adapter_gram"}
    assert expected <= names, expected - names
    assert len(names) >= 8


def test_full_matrix_passes_within_budget():
    """Every registered contract across {dense,streamed,kernel} x mesh
    {1,2} passes abstractly in well under a minute of CPU."""
    t0 = time.perf_counter()
    results = run_all()
    elapsed = time.perf_counter() - t0
    failed = [r for r in results if r.status == "fail"]
    assert not failed, "\n".join(
        f"{r.contract} {r.case}: {r.errors}" for r in failed)
    ran = [r for r in results if r.status == "ok"]
    assert len(ran) >= 60, len(ran)
    impls = {r.case.split("/")[1] for r in ran}
    meshes = {r.case.split("/")[2] for r in ran}
    assert impls == {"dense", "streamed", "kernel"}
    assert meshes == {"mesh1", "mesh2"}
    assert elapsed < 60, f"contract matrix took {elapsed:.1f}s"


def test_engine_state_fixture_matches_engine():
    """The aval mirror in fixtures must stay in lockstep with
    ``ServeEngine.__init__`` — drift would silently weaken the engine
    fixed-point contracts."""
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine
    cfg = FX.tiny_config("gqa")
    params = T.init(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=FX.BATCH_SLOTS,
                      capacity=FX.CAPACITY, max_tokens_cap=FX.OUT_CAP,
                      prefill_chunk=FX.CHUNK)
    assert FX.avals_equal(eng._state, FX.engine_state()), \
        "fixtures.engine_state drifted from ServeEngine.__init__"


# -- seeded violations --------------------------------------------------------

def _entry(name, build, **axes):
    axes.setdefault("families", ("gqa",))
    axes.setdefault("decode_impls", ("dense",))
    axes.setdefault("mesh_sizes", (1,))
    return _Entry(name, build, axes["families"], axes["decode_impls"],
                  axes["mesh_sizes"])


_SEEDED = iter(range(10 ** 6))


def _run_one(build):
    # unique name per seeded contract: the checker memoizes traces by
    # (contract, family, impl), exactly like real registrations
    return run_case(_entry(f"seeded-{next(_SEEDED)}", build),
                    Case("gqa", "dense", 1))


def test_detects_fp64_upcast():
    def build(case):
        def bad(x):
            return x.astype(jnp.float64) + 1.0
        return ContractCase(bad, (FX.sds((4,), "float32"),))

    res = _run_one(build)
    assert res.status == "fail"
    assert any("float64" in e for e in res.errors), res.errors


def test_detects_host_callback():
    import numpy as np

    def build(case):
        def bad(x):
            return jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return ContractCase(bad, (FX.sds((4,), "float32"),))

    res = _run_one(build)
    assert res.status == "fail"
    assert any("callback" in e for e in res.errors), res.errors


def test_detects_twin_aval_drift():
    def build(case):
        args = (FX.sds((4, 8), "float32"),)
        return ContractCase(lambda x: x.sum(0), args,
                            twin=(lambda x: x.sum(1), args))

    res = _run_one(build)
    assert res.status == "fail"
    assert any("twin" in e for e in res.errors), res.errors


def test_detects_retrace_hazard_via_out_check():
    """A step whose output avals drift from its inputs retraces every
    call — the fixed-point out_check is the abstract retrace detector."""
    def build(case):
        state = FX.sds((4,), "float32")

        def grows(s):
            return jnp.concatenate([s, s])      # aval drift: (4,) -> (8,)

        def out_check(out, _case):
            assert FX.avals_equal(out, state), "state avals drift"

        return ContractCase(grows, (state,), out_check=out_check)

    res = _run_one(build)
    assert res.status == "fail"
    assert any("drift" in e for e in res.errors), res.errors


def test_detects_nondivisible_pspec():
    from jax.sharding import PartitionSpec as P
    mesh = FX.abstract_mesh(2)
    # 7 does not divide by the model axis (2)
    errs = pspec_violations({"w": FX.sds((4, 7), "float32")},
                            {"w": P(None, "model")}, mesh)
    assert errs and "not divisible" in errs[0]
    # divisible shard + replicated leaf are clean
    assert pspec_violations({"w": FX.sds((4, 8), "float32")},
                            {"w": P(None, "model")}, mesh) == []
    assert pspec_violations({"w": FX.sds((4, 7), "float32")},
                            {"w": P()}, mesh) == []


def test_pspec_unknown_axis_and_rank_overflow():
    from jax.sharding import PartitionSpec as P
    mesh = FX.abstract_mesh(2)
    errs = pspec_violations({"w": FX.sds((4,), "float32")},
                            {"w": P("bogus")}, mesh)
    assert errs and "unknown mesh axis" in errs[0]
    errs = pspec_violations({"w": FX.sds((4,), "float32")},
                            {"w": P("data", "model")}, mesh)
    assert errs and "more axes than array rank" in errs[0]


def test_clean_jaxpr_has_no_violations():
    def fine(x):
        return jnp.sin(x) * 2.0

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(fine)(FX.sds((4,), "float32"))
    assert jaxpr_violations(closed) == []


def test_f64_ban_sees_through_nesting():
    """The jaxpr walker must reach pjit/scan sub-jaxprs."""
    def bad(x):
        def body(c, v):
            return c, v.astype(jnp.float64)
        return jax.lax.scan(body, 0.0, x)[1]

    with jax.enable_x64(True):
        closed = jax.make_jaxpr(bad)(FX.sds((4,), "float32"))
    assert any("float64" in v for v in jaxpr_violations(closed))


def test_build_exception_is_a_failure_not_a_crash():
    def build(case):
        raise RuntimeError("boom")

    res = _run_one(build)
    assert res.status == "fail"
    assert "RuntimeError" in res.errors[0]


def test_case_skip_when_build_returns_none():
    res = _run_one(lambda case: None)
    assert res.status == "skip" and res.errors == []


# -- CLI ----------------------------------------------------------------------

def test_cli_select_and_exit_code():
    from repro.analysis.contracts import main
    assert main(["--select", "agg.thin_svd"]) == 0
    with pytest.raises(SystemExit):
        main(["--no-such-flag"])


def test_abstract_mesh_axis_size():
    """axis_size reads name->size off ``mesh.shape``, so device-free
    AbstractMesh widths validate on a 1-device host."""
    from repro.topology import axis_size
    mesh = FX.abstract_mesh(4)
    assert axis_size(mesh, "model") == 4
    assert axis_size(mesh, "data") == 1
    assert axis_size(mesh, "absent") == 1
    real = jax.make_mesh((1, 1), ("data", "model"))
    assert axis_size(real, "model") == 1


def test_contract_entries_respect_matrix_slices():
    load_registrations()
    entries = contract_entries()
    kernel_cases = entries["kernel.ring_decode"].cases()
    assert all(c.mesh == 1 for c in kernel_cases)
    engine_cases = entries["serve.engine_step"].cases()
    assert {c.decode_impl for c in engine_cases} == \
        {"dense", "streamed", "kernel"}
    assert {c.mesh for c in engine_cases} == {1, 2}
