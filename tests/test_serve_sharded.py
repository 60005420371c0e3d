"""Mesh-sharded serving decode: single-device parity and the topology layer.

The sharded engine's contract is *bit-identity*: a ``ServeEngine`` built
with a ``(data=1, model=N)`` mesh must produce exactly the tokens of the
mesh-less engine — greedy decode bit-identical, sampled decode seed-stable
— with the SAME trace counts (the shardings install at init, so the hot
loop never retraces).

The device-parametrized tests need forced host devices, which must be in
``XLA_FLAGS`` before backend init and therefore cannot be set by
``tests/conftest.py`` (smoke tests need the single real device).  They
skip on a 1-device host; ``test_eight_device_driver`` re-runs this file in
a subprocess with ``--xla_force_host_platform_device_count=8`` so the
default suite still exercises them.  The topology-shim import-surface
tests run everywhere.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.common.config import ModelConfig
from repro.models import transformer as T
from repro.serve.engine import SamplingParams, ServeEngine
from repro.topology import make_serve_mesh

NDEV = len(jax.devices())
multidevice = pytest.mark.skipif(
    NDEV < 8, reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

# head counts divide every mesh size in {1, 2, 4, 8}
TINY = ModelConfig(name="shard-tiny", family="dense", num_layers=2,
                   d_model=64, num_heads=8, num_kv_heads=4, head_dim=8,
                   d_ff=128, vocab_size=128, dtype="float32")

GREEDY = SamplingParams(max_tokens=5)
SAMPLED = SamplingParams(temperature=0.8, top_k=20, max_tokens=5)


@pytest.fixture(scope="module")
def tiny_params():
    return T.init(TINY, jax.random.PRNGKey(0))


def _run(cfg, params, mesh_size, sp, decode_impl="streamed", registry=None,
         adapter_ids=None, steps=12, kv_dtype=None):
    """Build an engine (mesh-less when ``mesh_size`` is None), serve one
    4-slot workload with run_steps, return (uid->tokens, trace_counts)."""
    mesh = None if mesh_size is None else make_serve_mesh(mesh_size)
    eng = ServeEngine(cfg, params, batch_slots=4, capacity=32,
                      prefill_chunk=4, decode_impl=decode_impl,
                      registry=registry, seed=0, mesh=mesh,
                      kv_dtype=kv_dtype)
    rng = np.random.default_rng(3)
    for r in range(4):
        prompt = rng.integers(1, cfg.vocab_size, 4).tolist()
        kw = {"adapter_id": adapter_ids[r]} if adapter_ids else {}
        eng.submit(prompt, sp, **kw)
    out = eng.run_steps(steps)
    assert len(out) == 4, f"requests incomplete after {steps} steps: {out}"
    return out, dict(eng.trace_counts)


@multidevice
@pytest.mark.parametrize("impl", ["dense", "streamed"])
@pytest.mark.parametrize("mesh_size", [1, 2, 4, 8])
def test_greedy_parity_and_zero_retrace(tiny_params, mesh_size, impl):
    ref, ref_traces = _run(TINY, tiny_params, None, GREEDY, impl)
    got, traces = _run(TINY, tiny_params, mesh_size, GREEDY, impl)
    assert got == ref
    # same executables, no extra compiles from the sharded lowering
    assert traces == ref_traces


@multidevice
@pytest.mark.parametrize("mesh_size", [2, 8])
def test_sampled_seed_stable(tiny_params, mesh_size):
    ref, _ = _run(TINY, tiny_params, None, SAMPLED)
    got, _ = _run(TINY, tiny_params, mesh_size, SAMPLED)
    assert got == ref


@multidevice
@pytest.mark.parametrize("mesh_size", [2, 8])
def test_multitenant_mixed_ranks_parity(tiny_params, mesh_size):
    """Heterogeneous-rank adapters through the paged registry: the pool
    shardings must reproduce per-slot outputs bit-for-bit."""
    from repro.configs import lora_targets
    from repro.peft.lora import init_lora
    from repro.serve.adapters import AdapterRegistry

    key = jax.random.PRNGKey(7)

    def rand_adapter(rank, seed):
        ad = init_lora(tiny_params, lora_targets(TINY), rank, 8.0,
                       jax.random.fold_in(key, seed))
        return jax.tree_util.tree_map_with_path(
            lambda p, x: (jax.random.normal(
                jax.random.fold_in(key, abs(hash(str(p))) % 2**30), x.shape)
                * 0.05 if getattr(p[-1], "key", None) == "B" else x), ad)

    def build():
        template = init_lora(tiny_params, lora_targets(TINY), 4, 8.0, key)
        reg = AdapterRegistry(template, page_rank=4, num_pages=16,
                              max_adapters=8, max_rank=8)
        ids = [reg.register(f"t{r}", rand_adapter(r, r)) for r in (4, 7, 3)]
        return reg, [0] + ids            # base id 0 + three live adapters

    reg0, ids0 = build()
    ref, _ = _run(TINY, tiny_params, None, GREEDY, registry=reg0,
                  adapter_ids=ids0)
    reg1, ids1 = build()
    got, _ = _run(TINY, tiny_params, mesh_size, GREEDY, registry=reg1,
                  adapter_ids=ids1)
    assert got == ref


@multidevice
@pytest.mark.parametrize("mesh_size", [2, 8])
def test_int8_cache_parity(tiny_params, mesh_size):
    """Quantized ring caches add per-token scale leaves (k_scale/v_scale)
    that shard with their heads; parity must hold bit-for-bit too."""
    import jax.numpy as jnp
    ref, _ = _run(TINY, tiny_params, None, GREEDY, kv_dtype=jnp.int8)
    got, _ = _run(TINY, tiny_params, mesh_size, GREEDY, kv_dtype=jnp.int8)
    assert got == ref


@multidevice
def test_kernel_impl_parity(tiny_params):
    """Pallas ring-decode (interpret mode off-TPU) under shard_map over the
    kv-head axis matches the mesh-less kernel engine."""
    ref, _ = _run(TINY, tiny_params, None, GREEDY, decode_impl="kernel")
    got, _ = _run(TINY, tiny_params, 2, GREEDY, decode_impl="kernel")
    assert got == ref


@multidevice
@pytest.mark.parametrize("impl", ["dense", "streamed"])
def test_mla_parity(impl):
    """MLA decode (compressed latents replicated, query heads sharded)
    through the deepseek smoke config — MoE layers included."""
    from repro.configs import get_smoke_config
    cfg = get_smoke_config("deepseek-v3-671b")
    params = T.init(cfg, jax.random.PRNGKey(0))
    ref, _ = _run(cfg, params, None, GREEDY, impl)
    got, _ = _run(cfg, params, 2, GREEDY, impl)
    assert got == ref


@pytest.mark.skipif(NDEV >= 8, reason="already on a multi-device host")
def test_eight_device_driver():
    """Re-run this file on 8 forced host devices in a subprocess (the only
    way to get them: XLA reads the flag once, at backend init)."""
    from repro.common.xla_env import merge_flags
    env = dict(os.environ)
    env["XLA_FLAGS"] = merge_flags(
        os.environ.get("XLA_FLAGS", ""),
        "--xla_force_host_platform_device_count=8")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q",
         "-p", "no:cacheprovider", os.path.abspath(__file__)],
        env=env, capture_output=True, text=True, timeout=1500)
    if proc.returncode != 0:
        pytest.fail("sharded suite failed under 8 forced devices:\n"
                    + proc.stdout[-4000:] + proc.stderr[-2000:])


# -- topology layer import surface (device-count independent) ----------------

def test_launch_shims_reexport_topology():
    # the shims are deprecated (DeprecationWarning on import) but their
    # re-export surface must stay intact for external callers
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        import repro.launch.mesh as lm
        import repro.launch.sharding as ls
    from repro import topology as topo
    assert lm.make_production_mesh is topo.make_production_mesh
    assert lm.make_host_mesh is topo.make_host_mesh
    assert lm.axis_size is topo.axis_size
    assert ls.param_pspec is topo.param_pspec
    assert ls.params_pspecs is topo.params_pspecs
    assert ls.batch_pspecs is topo.batch_pspecs
    assert ls.cache_pspecs is topo.cache_pspecs
    assert ls.to_shardings is topo.to_shardings
    assert ls.ZERO3_THRESHOLD == topo.ZERO3_THRESHOLD


def test_launch_shims_warn_deprecation():
    import importlib

    import repro.launch.mesh as lm
    import repro.launch.sharding as ls
    with pytest.warns(DeprecationWarning, match="repro.launch.mesh"):
        importlib.reload(lm)
    with pytest.warns(DeprecationWarning, match="repro.launch.sharding"):
        importlib.reload(ls)


def test_cache_leaf_ranks_single_table():
    from repro import topology as topo
    from repro.serve import kvcache
    assert kvcache.CACHE_LEAF_RANKS is topo.CACHE_LEAF_RANKS


def test_shard_map_single_definition():
    """The shard_map wrapper has ONE definition; every
    consumer (federated aggregation + model layers + serve decode) binds
    the same object."""
    from repro.common import pjit_utils
    from repro.core import distributed
    from repro.models import attention_core, layers, moe
    assert distributed._shard_map is pjit_utils.shard_map
    assert layers._pjit_shard_map is pjit_utils.shard_map
    assert attention_core._pjit_shard_map is pjit_utils.shard_map
    assert moe._pjit_shard_map is pjit_utils.shard_map


def test_make_serve_mesh_shapes():
    from repro import topology as topo
    m = topo.make_serve_mesh(1)
    assert m.devices.shape == (1, 1) and m.axis_names == ("data", "model")
    with pytest.raises(ValueError):
        topo.make_serve_mesh(len(jax.devices()) + 1)
