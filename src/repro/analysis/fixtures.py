"""Abstract fixtures for the contract checker.

Everything here is built with ``jax.eval_shape`` or raw
``ShapeDtypeStruct``s — no device arrays are ever materialized, so the
checker stays zero-FLOP even for the full config matrix.

The per-family configs are the repo's own SMOKE variants (the same ones
the test suite traces), so a contract failure here reproduces with the
exact configs a developer already knows how to run.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import ShapeDtypeStruct
from jax.sharding import AbstractMesh

from repro.common.config import ModelConfig
from repro.configs import get_smoke_config, lora_targets
from repro.models import transformer as T

#: config-matrix family -> smoke architecture exercising it
FAMILY_SMOKE = {
    "gqa": "qwen3-4b",            # dense, GQA + qk_norm
    "mla": "deepseek-v3-671b",    # MLA latent cache + MoE blocks
    "moe": "granite-moe-1b-a400m",
    "ssm": "rwkv6-1.6b",          # attention-free recurrence
}

#: engine geometry shared by every serving contract
BATCH_SLOTS = 4
CAPACITY = 32
CHUNK = 4
OUT_CAP = 64


def sds(shape, dtype) -> ShapeDtypeStruct:
    return ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def tiny_config(family: str) -> ModelConfig:
    return get_smoke_config(FAMILY_SMOKE[family])


def chunk_width(cfg: ModelConfig) -> int:
    """SSM/RWKV decode is a single-token recurrence; attention families
    take whole chunks (mirrors ``ServeEngine.__init__``)."""
    return 1 if cfg.family in ("ssm", "hybrid") else CHUNK


def abstract_mesh(model: int) -> AbstractMesh:
    """A device-free serve-shaped mesh: pspec rules only read axis sizes,
    so divisibility validates at any mesh width on a 1-device host."""
    return AbstractMesh((1, model), ("data", "model"))


def abstract_fed_mesh(data: int) -> AbstractMesh:
    """A device-free fed-shaped mesh (data=N, model=1): the client-parallel
    cohort specs validate at any data width on a 1-device host."""
    return AbstractMesh((data, 1), ("data", "model"))


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(partial(T.init, cfg), sds((2,), jnp.uint32))


def abstract_cache(cfg: ModelConfig, batch: int = BATCH_SLOTS,
                   capacity: int = CAPACITY, kv_dtype=None):
    kv_dtype = kv_dtype or jnp.dtype(cfg.dtype)
    return jax.eval_shape(
        lambda: T.init_cache(cfg, batch, capacity, kv_dtype,
                             prefill_chunk=chunk_width(cfg)))


def abstract_adapters(cfg: ModelConfig, params: Any, rank: int = 4,
                      alpha: float = 8.0):
    from repro.peft.lora import init_lora
    return jax.eval_shape(
        lambda p, k: init_lora(p, lora_targets(cfg), rank, alpha, k),
        params, sds((2,), jnp.uint32))


def engine_state(batch: int = BATCH_SLOTS, capacity: int = CAPACITY,
                 out_cap: int = OUT_CAP) -> Dict[str, ShapeDtypeStruct]:
    """Aval mirror of the ``ServeEngine`` slot-state dict.

    Kept in lockstep with ``ServeEngine.__init__`` by
    ``test_analysis_contracts.py::test_engine_state_fixture_matches_engine``.
    """
    B = batch
    return {
        "active": sds((B,), jnp.bool_),
        "last_token": sds((B,), jnp.int32),
        "consumed": sds((B,), jnp.int32),
        "prompt_len": sds((B,), jnp.int32),
        "prompt_buf": sds((B, capacity), jnp.int32),
        "gen_count": sds((B,), jnp.int32),
        "out_buf": sds((B, out_cap), jnp.int32),
        "temperature": sds((B,), jnp.float32),
        "top_k": sds((B,), jnp.int32),
        "top_p": sds((B,), jnp.float32),
        "max_tokens": sds((B,), jnp.int32),
        "stop_token": sds((B,), jnp.int32),
        "keys": sds((B, 2), jnp.uint32),
        "adapter_ids": sds((B,), jnp.int32),
    }


def train_batch(cfg: ModelConfig, batch: int = 2, seq: int = 16):
    return {"tokens": sds((batch, seq), jnp.int32)}


def avals_equal(a: Any, b: Any) -> bool:
    """Same pytree structure AND identical shape/dtype at every leaf."""
    import jax.tree_util as jtu
    if jtu.tree_structure(a) != jtu.tree_structure(b):
        return False
    return jtu.tree_all(jtu.tree_map(
        lambda x, y: tuple(x.shape) == tuple(y.shape)
        and jnp.dtype(x.dtype) == jnp.dtype(y.dtype), a, b))
