"""Abstract contract checker: run every registered contract across its
config-matrix slice with ``jax.eval_shape`` / ``jax.make_jaxpr`` only.

Zero FLOPs execute — each case traces the entrypoint abstractly and then
asserts:

* the contract's declared output invariant (``out_check``), e.g. the
  engine step's fixed point: output cache/state avals identical to the
  inputs (the property that makes the decode hot loop retrace-free);
* the kernel ↔ XLA-twin aval identity (``twin``);
* partition specs fit their arrays and divide evenly at the case's mesh
  width, validated on a device-free ``AbstractMesh``;
* jaxpr-level bans: no float64 anywhere in the traced computation (the
  jaxpr is traced under ``enable_x64`` so silent canonicalization cannot
  mask an upcast) and no host callbacks in the hot path.

CLI (used by the CI ``analysis`` job)::

    python -m repro.analysis.contracts [--select SUBSTR] [--list] [--json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax.sharding import PartitionSpec

from repro.analysis.registry import (Case, ContractCase, _Entry,
                                     contract_entries, load_registrations)

#: callback primitives banned from jitted hot paths (each one is a host
#: round-trip per dispatch)
BANNED_CALLBACK_PRIMS = frozenset(
    {"pure_callback", "io_callback", "debug_callback", "callback"})


# -- jaxpr walking -----------------------------------------------------------

def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and its nested sub-jaxprs (pjit bodies, scan
    bodies, cond branches, custom_vjp calls, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_eqns(sub)


def _sub_jaxprs(params: Dict[str, Any]):
    for val in params.values():
        for v in (val if isinstance(val, (list, tuple)) else (val,)):
            inner = getattr(v, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield inner          # ClosedJaxpr
            elif hasattr(v, "eqns"):
                yield v              # raw Jaxpr


def jaxpr_violations(closed, *, forbid_f64: bool = True,
                     forbid_callbacks: bool = True) -> List[str]:
    """Scan a ClosedJaxpr for banned float64 values and callback prims.

    f64 is judged on eqn *outputs* and consts only: weak-typed python
    float literals trace as scalar ``f64[]`` operands under x64 and get
    converted straight down to f32 — those are benign and ignored.
    """
    out: List[str] = []
    if forbid_f64:
        for cv in closed.consts:
            if getattr(jnp.asarray(cv), "dtype", None) == jnp.float64:
                out.append("float64 constant captured in jaxpr")
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if forbid_callbacks and name in BANNED_CALLBACK_PRIMS:
            out.append(f"banned callback primitive {name!r} in jaxpr")
        if forbid_f64:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                dt = getattr(aval, "dtype", None)
                if dt == jnp.float64:
                    out.append(
                        f"float64 value {aval.str_short()} produced by "
                        f"{name!r} (fp32-explicit repo: no f64 upcasts)")
    return out


# -- pspec validation --------------------------------------------------------

def _spec_axes(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, tuple) else (entry,)


def pspec_violations(tree: Any, specs: Any, mesh) -> List[str]:
    """Check a (arrays, PartitionSpecs) pair against a mesh's axis sizes.

    ``mesh`` only needs ``.shape`` (name -> size), so an ``AbstractMesh``
    works — specs validate at mesh widths the host cannot build."""
    sizes = dict(mesh.shape)
    out: List[str] = []

    def leaf_path(path) -> str:
        return jtu.keystr(path) or "<root>"

    def check(path, arr, spec):
        if spec is None:
            return
        if not isinstance(spec, PartitionSpec):
            out.append(f"{leaf_path(path)}: spec {spec!r} is not a "
                       "PartitionSpec")
            return
        shape = tuple(arr.shape)
        if len(spec) > len(shape):
            out.append(f"{leaf_path(path)}: spec {spec} has more axes than "
                       f"array rank {len(shape)}")
            return
        for dim, entry in enumerate(spec):
            prod = 1
            for name in _spec_axes(entry):
                if name not in sizes:
                    out.append(f"{leaf_path(path)}: unknown mesh axis "
                               f"{name!r} in {spec}")
                    continue
                prod *= sizes[name]
            if prod > 1 and shape[dim] % prod:
                out.append(
                    f"{leaf_path(path)}: dim {dim} of shape {shape} not "
                    f"divisible by mesh extent {prod} ({spec})")

    jtu.tree_map_with_path(check, tree, specs,
                           is_leaf=lambda x: x is None)
    return out


# -- the runner --------------------------------------------------------------

@dataclasses.dataclass
class CaseResult:
    contract: str
    case: str
    status: str                      # "ok" | "skip" | "fail"
    errors: List[str]
    seconds: float

    def line(self) -> str:
        mark = {"ok": "ok", "skip": "-", "fail": "FAIL"}[self.status]
        return f"{self.contract:28s} {self.case:22s} {mark:4s} " \
               f"{self.seconds:5.2f}s"


#: abstract-eval results shared across mesh sizes: tracing is independent
#: of the mesh (only pspec validation varies), so each (contract, family,
#: impl) traces once
_TRACE_CACHE: Dict[Tuple[str, str, str], Tuple[Any, List[str]]] = {}


def _trace(name: str, case: Case, cc: ContractCase):
    key = (name, case.family, case.decode_impl)
    hit = _TRACE_CACHE.get(key)
    if hit is not None:
        return hit
    out = jax.eval_shape(cc.fn, *cc.args)
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(cc.fn)(*cc.args)
    bans = jaxpr_violations(closed, forbid_f64=cc.forbid_f64,
                            forbid_callbacks=cc.forbid_callbacks)
    if cc.twin is not None:
        twin_fn, twin_args = cc.twin
        twin_out = jax.eval_shape(twin_fn, *twin_args)
        from repro.analysis.fixtures import avals_equal
        if not avals_equal(out, twin_out):
            bans.append(
                "kernel/twin aval mismatch: "
                f"{jtu.tree_map(lambda x: (tuple(x.shape), str(x.dtype)), out)}"
                " vs "
                f"{jtu.tree_map(lambda x: (tuple(x.shape), str(x.dtype)), twin_out)}")
    _TRACE_CACHE[key] = (out, bans)
    return out, bans


def run_case(entry: _Entry, case: Case) -> CaseResult:
    t0 = time.perf_counter()
    try:
        cc = entry.build(case)
        if cc is None:
            return CaseResult(entry.name, case.label(), "skip", [],
                              time.perf_counter() - t0)
        out, bans = _trace(entry.name, case, cc)
        errors = list(bans)
        if cc.out_check is not None:
            try:
                cc.out_check(out, case)
            except AssertionError as e:
                errors.append(f"out_check failed: {e}")
        if cc.pspec_tree is not None:
            if cc.mesh is None:
                errors.append("pspec_tree given without a mesh")
            else:
                errors.extend(pspec_violations(*cc.pspec_tree, cc.mesh))
    except Exception as e:            # build/trace blew up — that IS a failure
        errors = [f"{type(e).__name__}: {e}"]
    status = "fail" if errors else "ok"
    return CaseResult(entry.name, case.label(), status, errors,
                      time.perf_counter() - t0)


def run_all(select: Optional[str] = None) -> List[CaseResult]:
    load_registrations()
    results = []
    for name, entry in sorted(contract_entries().items()):
        if select and select not in name:
            continue
        for case in entry.cases():
            results.append(run_case(entry, case))
    return results


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.contracts",
        description="Abstract (zero-FLOP) contract checker.")
    p.add_argument("--select", help="substring filter on contract names")
    p.add_argument("--list", action="store_true",
                   help="list registered contracts and exit")
    p.add_argument("--json", action="store_true", dest="as_json")
    args = p.parse_args(argv)

    if args.list:
        for name in load_registrations():
            print(name)
        return 0

    t0 = time.perf_counter()
    results = run_all(args.select)
    failed = [r for r in results if r.status == "fail"]
    if args.as_json:
        print(json.dumps([dataclasses.asdict(r) for r in results], indent=2))
    else:
        for r in results:
            print(r.line())
            for err in r.errors:
                print(f"    {err}")
        ok = sum(r.status == "ok" for r in results)
        skipped = sum(r.status == "skip" for r in results)
        print(f"{ok} ok, {skipped} skipped, {len(failed)} failed "
              f"in {time.perf_counter() - t0:.1f}s "
              f"({len(contract_entries())} contracts)")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
