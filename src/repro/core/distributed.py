"""Multi-pod / sharded server aggregation.

The FLoRIST server pipeline is embarrassingly parallel over (layer ×
projection).  This module maps it onto the production mesh with
``shard_map``: each device owns a slice of layers, runs the stacked-SVD +
core-SVD + threshold locally (jit-safe padded variant), and only the
per-layer kept-rank counters are exchanged (an ``all_gather`` of L int32s —
the *algorithm's* download traffic is the rank-p adapters themselves, which
stay sharded until broadcast).

This is the TPU-native replacement for the paper's single-server NumPy/Torch
aggregation loop (DESIGN.md §3): thin SVDs become Gram-matmuls + small eigh
per layer shard; no cross-device traffic during the math.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import telemetry
from repro.common.pjit_utils import shard_map as _shard_map

from repro.core.aggregators import AggResult, register_aggregator, set_path
from repro.core.aggregators.florist import FloristAggregator
from repro.core.svd import florist_core_delta_padded, florist_core_padded


def florist_aggregate_batched(B_stacks: jnp.ndarray, A_stacks: jnp.ndarray,
                              tau, svd_method: str = "svd",
                              max_rank: int = 0):
    """vmapped padded FLoRIST core over a layer axis (the same core the
    host-side batched pipeline jits via ``florist_core_batched``; un-jitted
    here because ``shard_map`` wraps it).

    B_stacks: (L, m, r), A_stacks: (L, r, n) — already weighted/stacked.
    Returns (B_g (L,m,r) zero-padded beyond p_l, A_g (L,r,n), spectra (L,r),
    ranks (L,) int32).
    """
    fn = partial(florist_core_padded, tau=tau, svd_method=svd_method,
                 max_rank=max_rank)
    return jax.vmap(lambda b, a: fn(b, a))(B_stacks, A_stacks)


def pad_layers(x: jnp.ndarray, mult: int) -> Tuple[jnp.ndarray, int]:
    L = x.shape[0]
    pad = (-L) % mult
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, L


def make_sharded_florist(mesh: Mesh, tau, svd_method: str = "gram",
                         max_rank: int = 0):
    """jit'd sharded aggregation: layers sharded over the 'model' axis.

    Returns fn(B_stacks (L,m,r), A_stacks (L,r,n)) ->
    (B_g, A_g, spectra, ranks) with L padded to the axis size internally.
    ``tau`` / ``max_rank`` semantics match the host pipeline exactly
    (including ``tau="auto"`` and the rank cap, applied inside the traced
    core so the kept columns are the capped truncation, not a post-hoc
    clamp).
    """
    n_shard = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    def local(bs, as_):
        # bs: (L/n, m, r) local slice
        bg, ag, sp, p = florist_aggregate_batched(bs, as_, tau, svd_method,
                                                  max_rank)
        return bg, ag, sp, p

    sharded = _shard_map(
        local, mesh=mesh,
        in_specs=(P("model"), P("model")),
        out_specs=(P("model"), P("model"), P("model"), P("model")),
    )

    @jax.jit
    def run(B_stacks, A_stacks):
        Bp, L = pad_layers(B_stacks, n_shard)
        Ap, _ = pad_layers(A_stacks, n_shard)
        # guard padded layers against singular zero matrices
        eye_bump = 1e-6
        Bp = Bp.at[L:].add(eye_bump) if Bp.shape[0] > L else Bp
        bg, ag, sp, p = sharded(Bp, Ap)
        return bg[:L], ag[:L], sp[:L], p[:L]

    return run


def make_sharded_florist_delta(mesh: Mesh, tau, svd_method: str = "gram",
                               max_rank: int = 0):
    """Layer-sharded delta-mode finalize: fn(M (L, m, n)) ->
    (B_g, A_g, spectra, ranks) — the streaming server's compact dense
    intermediate SVD'd in place, layers sharded over 'model'."""
    n_shard = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]

    def local(ms):
        fn = partial(florist_core_delta_padded, tau=tau,
                     svd_method=svd_method, max_rank=max_rank)
        return jax.vmap(fn)(ms)

    sharded = _shard_map(
        local, mesh=mesh,
        in_specs=(P("model"),),
        out_specs=(P("model"), P("model"), P("model"), P("model")),
    )

    @jax.jit
    def run(M):
        Mp, L = pad_layers(M, n_shard)
        eye_bump = 1e-6
        Mp = Mp.at[L:].add(eye_bump) if Mp.shape[0] > L else Mp
        bg, ag, sp, p = sharded(Mp)
        return bg[:L], ag[:L], sp[:L], p[:L]

    return run


@register_aggregator("florist_sharded")
class ShardedFloristAggregator(FloristAggregator):
    """FLoRIST with the finalize step mapped onto a device mesh.

    Streaming accumulation (``add_client``) is identical to the host-side
    ``florist`` strategy; ``finalize`` runs the layer-sharded jit'd pipeline
    instead of the per-layer Python loop.  Registered as
    ``"florist_sharded"`` — an example of a backend variant plugging into
    the aggregation registry without touching the trainer or the cost
    accounting (both are inherited).
    """

    def __init__(self, tau=0.9, svd_method: str = "gram",
                 mesh: Optional[Mesh] = None, max_rank: int = 0,
                 stream: str = "auto", flush_every: int = 64):
        if mesh is None:
            mesh = Mesh(np.asarray(jax.devices()), ("model",))
        self.mesh = mesh
        self._fn_cache: Dict = {}
        super().__init__(tau=tau, svd_method=svd_method, max_rank=max_rank,
                         stream=stream, flush_every=flush_every)

    def _finalize(self) -> AggResult:
        if "fn" not in self._fn_cache:
            self._fn_cache["fn"] = make_sharded_florist(
                self.mesh, tau=self.tau, svd_method=self.svd_method,
                max_rank=self.max_rank)
            self._fn_cache["delta"] = make_sharded_florist_delta(
                self.mesh, tau=self.tau, svd_method=self.svd_method,
                max_rank=self.max_rank)
        device: Dict[Tuple, Tuple] = {}
        with telemetry.span("finalize.core"):
            for path, inter in self._settle().items():
                if inter[0] == "stack":
                    device[path] = self._fn_cache["fn"](inter[1], inter[2])
                else:
                    device[path] = self._fn_cache["delta"](inter[1])
        # _materialize does the single device→host transfer + exact
        # truncation of the zero-padded columns
        return self._materialize(device)
