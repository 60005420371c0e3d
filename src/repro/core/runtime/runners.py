"""Client runners: who executes a round's local fine-tuning, and how.

A :class:`ClientRunner` consumes a :class:`~repro.core.runtime.schedulers.
RoundPlan` and trains every task against the round context ``ctx`` (the
:class:`~repro.core.federated.FederatedTrainer`: frozen ``params``,
``clients``, ``batch_size``, ``_client_init``), calling ``deliver(task,
trained_adapters, init_adapters)`` once per finished client so the server
can stream each update through the transport (where DP clipping/noising
happens against ``init_adapters``) into the aggregator and drop it.

* ``sequential`` — one client at a time, exactly the legacy ``run_round``
  loop (same batch rng ``default_rng(1000·rnd + k)``, same step order):
  bit-for-bit reproducible.
* ``cohort`` — the client-side analogue of the batched server pipeline:
  tasks are grouped into equal-(rank, steps) cohorts, their init adapters
  and pre-drawn batch schedules are stacked along a client axis, and each
  cohort trains in ONE jitted ``vmap``-of-``scan`` train-step call.  Ragged
  batch sizes are padded with zero-masked rows (mathematically inert under
  the masked CE), so cohort training is numerically equivalent to the
  sequential loop up to batched-matmul reassociation.
* ``sharded_cohort`` — ``cohort`` with the client axis additionally sharded
  over the fed mesh's ``data`` axis (specs from
  :func:`repro.topology.fed_pspecs`, consumed the same way the serving
  stack consumes ``serve_pspecs``): a 1024-client round becomes a handful
  of compiled sharded calls, each training ``block/N`` clients per device.

Runners *stream*: each cohort block is prepared, trained, and delivered
before the next is staged, so peak host memory is one cohort of client
state — not the whole round's (``peak_live_clients`` records the
high-water mark for the O(cohort) memory tests).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple, Type

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import telemetry
from repro.optim.adamw import adamw_init
from repro.train.step import make_train_step


class ClientRunner:
    """Local-training executor.  Subclasses implement :meth:`run`."""

    name: str = "?"

    def run(self, ctx, plan, deliver: Callable) -> None:
        """Train every task in ``plan``; call ``deliver(task, adapters,
        init_adapters)`` once per completed client, in a deterministic
        order."""
        raise NotImplementedError


_REGISTRY: Dict[str, Type[ClientRunner]] = {}


def register_runner(name: str):
    def deco(cls: Type[ClientRunner]) -> Type[ClientRunner]:
        _REGISTRY[name] = cls
        cls.name = name
        return cls
    return deco


def make_runner(spec: Any, **cfg) -> ClientRunner:
    if isinstance(spec, ClientRunner):
        return spec
    try:
        return _REGISTRY[spec](**cfg)
    except KeyError:
        raise ValueError(f"unknown runner {spec!r} "
                         f"(registered: {sorted(_REGISTRY)})") from None


def available_runners() -> List[str]:
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# shared plumbing
# ---------------------------------------------------------------------------


def _init_getter(ctx):
    """Per-plan client-init resolver: a task resumes from its dispatch-time
    snapshot (async) or the aggregator's client-init for the current global
    state.  ``Aggregator.client_init(global_state, rank, a_init)`` depends
    only on the task's *rank* (which a rank policy may have adapted away
    from the client's static profile), so equal-rank tasks share one
    computed tree instead of re-running the eager truncate/pad per client —
    at 1024 clients this is the difference between 4 and 1024 host-side
    tree builds."""
    cache: Dict[int, Dict] = {}

    def get(task) -> Dict:
        if task.init_adapters is not None:
            return task.init_adapters
        if task.rank not in cache:
            with telemetry.span("client.init", rank=task.rank):
                cache[task.rank] = ctx._client_init(task.client_id,
                                                    task.rank)
        return cache[task.rank]

    return get


def _batch_schedule(ctx, rnd: int, task) -> List[Dict[str, np.ndarray]]:
    """The exact batch sequence the legacy loop would draw for this task
    (same rng stream, same epoch re-permutation)."""
    data = ctx.clients[task.client_id]
    bs = min(ctx.batch_size, data.num_samples)
    brng = np.random.default_rng(1000 * rnd + task.client_id)
    batches: List[Dict[str, np.ndarray]] = []
    while len(batches) < task.steps:
        for batch in data.batches(bs, brng):
            batches.append(batch)
            if len(batches) >= task.steps:
                break
    return batches


# ---------------------------------------------------------------------------
# sequential (legacy-equivalent)
# ---------------------------------------------------------------------------


@register_runner("sequential")
class SequentialRunner(ClientRunner):
    """One jitted train-step call per (client, batch) — the legacy loop."""

    def run(self, ctx, plan, deliver: Callable) -> None:
        step = ctx._train_step()
        task_init = _init_getter(ctx)
        for task in plan.tasks:
            init_adapters = task_init(task)
            with telemetry.span("client.batches", client=task.client_id):
                batches = [{kk: jnp.asarray(v) for kk, v in batch.items()}
                           for batch in _batch_schedule(ctx, plan.round, task)]
            with telemetry.span("client.train", client=task.client_id):
                adapters = init_adapters
                opt_state = adamw_init(adapters)
                for jb in batches:
                    adapters, opt_state, _ = step(ctx.params, adapters,
                                                  opt_state, jb)
            deliver(task, adapters, init_adapters)


# ---------------------------------------------------------------------------
# cohort (vmapped) + sharded cohort (vmapped, client axis over the mesh)
# ---------------------------------------------------------------------------


def _cohort_train_fn(cfg, optim, loss_chunk: int, b_only: bool):
    """The un-jitted cohort trainer: vmap over the client axis of a scan
    over the local step axis.  ``fn(params, stacked_adapters, batches)``
    with batches ``{"tokens": (C, steps, B, T), "loss_mask": ...}`` returns
    the trained stacked adapters (an aval fixed point — asserted by the
    ``fed.cohort_step`` contract)."""
    step = make_train_step(cfg, optim, remat=False, loss_chunk=loss_chunk,
                           b_only=b_only)

    def one_client(params, adapters, batches):
        opt_state = adamw_init(adapters)

        def body(carry, batch):
            ad, opt = carry
            ad, opt, _ = step(params, ad, opt, batch)
            return (ad, opt), None

        (adapters, _), _ = jax.lax.scan(body, (adapters, opt_state), batches)
        return adapters

    return jax.vmap(one_client, in_axes=(None, 0, 0))


@functools.lru_cache(maxsize=None)
def _cached_cohort_train(cfg, optim, loss_chunk: int, b_only: bool):
    """Jitted cohort trainer.  jax.jit re-specializes per (cohort, rank,
    batch) shape, so every equal-shaped cohort reuses one compiled
    program."""
    return jax.jit(_cohort_train_fn(cfg, optim, loss_chunk, b_only))


@functools.lru_cache(maxsize=None)
def _cached_sharded_cohort_train(cfg, optim, loss_chunk: int, b_only: bool,
                                 mesh):
    """Jitted cohort trainer with the client axis sharded over ``data``.

    The fed specs are pytree *prefixes* (one spec per argument subtree,
    trailing dims replicated — see :func:`repro.topology.fed_pspecs`), so
    the wrapper is built once per (config, mesh) without concrete cohort
    trees; GSPMD then partitions every client-stacked leaf the same way.
    """
    from jax.sharding import NamedSharding

    from repro.topology import fed_pspecs

    specs = fed_pspecs(mesh)
    param_s = NamedSharding(mesh, specs["params"])
    cohort_s = NamedSharding(mesh, specs["cohort"])
    batch_s = NamedSharding(mesh, specs["batch"])
    return jax.jit(_cohort_train_fn(cfg, optim, loss_chunk, b_only),
                   in_shardings=(param_s, cohort_s, batch_s),
                   out_shardings=cohort_s)


def _group_cohorts(plan) -> Dict[Tuple[int, int], List]:
    """Tasks bucketed by (rank, steps) — each bucket trains in one
    compiled call (or a few fixed-size blocks of one)."""
    cohorts: Dict[Tuple[int, int], List] = {}
    for task in plan.tasks:
        cohorts.setdefault((task.rank, task.steps), []).append(task)
    return cohorts


def _stack_cohort(ctx, rnd: int, tasks: List, inits: List, pad_c: int):
    """Host-side prep for one cohort block: replay the sequential batch
    draws, zero-pad ragged batch sizes (padded rows carry ``loss_mask = 0``
    and contribute nothing to loss, gradient, or metric denominators),
    stack inits/batches along a new client axis, and pad the client axis to
    ``pad_c`` with inert replicas (zero mask ⇒ zero gradients).

    ``inits`` are the tasks' own init trees.  Returns ``(stacked_adapters,
    {"tokens", "loss_mask"})`` with the batches on the device."""
    steps = tasks[0].steps
    scheds = [_batch_schedule(ctx, rnd, t) for t in tasks]
    seq_len = scheds[0][0]["tokens"].shape[1]
    bs = ctx.batch_size                  # fixed batch axis: stable shape
    toks = np.zeros((pad_c, steps, bs, seq_len), np.int32)
    mask = np.zeros((pad_c, steps, bs, seq_len), np.float32)
    for ci, sched in enumerate(scheds):
        for si, b in enumerate(sched):
            toks[ci, si, : b["tokens"].shape[0]] = b["tokens"]
            mask[ci, si, : b["tokens"].shape[0]] = b["loss_mask"]
    padded = inits + [inits[0]] * (pad_c - len(tasks))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
    return stacked, {"tokens": jnp.asarray(toks),
                     "loss_mask": jnp.asarray(mask)}


@register_runner("cohort")
class CohortRunner(ClientRunner):
    """Equal-rank cohorts train in one compiled vmapped call each.

    Host-side prep (see :func:`_stack_cohort`) stages ONE cohort block at a
    time: stack → train → one device→host transfer per block.  The client
    axis is padded to the next power of two, so schedulers with varying
    arrival counts (``async``/``partial``) hit at most O(log K) compiled
    shapes instead of one per count.

    Delivery order is a subclass policy (``stream``): the plain cohort
    runner buffers trained results and delivers in *plan order*, keeping
    the aggregator's stack column order identical to ``sequential`` (for
    SVD-based methods a permuted stack yields the same ΔW but can rotate
    near-degenerate singular vectors, which factor-level equivalence tests
    would see); ``sharded_cohort`` streams cohort-grouped, delivering each
    block as it finishes so host memory stays O(block) at 1000+ clients.
    """

    #: deliver per finished block (True) or buffered in plan order (False)
    stream = False

    def __init__(self):
        self.peak_live_clients = 0

    def _pad(self, k_c: int, ctx) -> int:
        return 1 << (k_c - 1).bit_length()       # next power of two

    def _train_fn(self, ctx):
        return _cached_cohort_train(ctx.cfg, ctx.optim, 64,
                                    ctx.aggregator.trains_b_only)

    def _params(self, ctx):
        return ctx.params

    def _blocks(self, tasks: List) -> Iterator[List]:
        yield tasks

    def run(self, ctx, plan, deliver: Callable) -> None:
        task_init = _init_getter(ctx)
        train = self._train_fn(ctx)
        params = self._params(ctx)
        order = {id(t): i for i, t in enumerate(plan.tasks)}
        buffered: Dict[int, Tuple] = {}
        blocks = (block for tasks in _group_cohorts(plan).values()
                  for block in self._blocks(tasks))
        for b, block in enumerate(blocks):
            pad_c = self._pad(len(block), ctx)
            inits = [task_init(t) for t in block]
            with telemetry.span("client.batches", block=b):
                stacked, batch = _stack_cohort(ctx, plan.round, block,
                                               inits, pad_c)
            self.peak_live_clients = max(self.peak_live_clients, pad_c)
            with telemetry.span("client.train", block=b):
                out = train(params, stacked, batch)
                # ONE device→host transfer for the whole block; per-client
                # unstacking is then free numpy views (eager per-leaf
                # device slicing would cost a dispatch per (client, leaf))
                host_out = jax.device_get(out)
            for ci, task in enumerate(block):
                adapters = jax.tree.map(lambda x: x[ci], host_out)
                if self.stream:
                    deliver(task, adapters, inits[ci])
                else:
                    buffered[order[id(task)]] = (task, adapters, inits[ci])
        for i in sorted(buffered):
            deliver(*buffered[i])


@register_runner("sharded_cohort")
class ShardedCohortRunner(CohortRunner):
    """Cohort training with the client axis sharded over the fed mesh.

    Each (rank, steps) cohort is cut into blocks of ≤ ``block`` clients,
    the block's client axis is padded to a multiple of the ``data`` axis
    (on top of the power-of-two rounding that bounds compiled-shape count),
    and one sharded jitted call trains ``pad_c / N`` clients per device.
    Blocks *stream*: each is delivered (cohort-grouped order) and dropped
    before the next is staged, so a 1024-client round never holds more
    than ``block`` trained trees on the host.  Base params are replicated
    once per round via a cached ``device_put`` (flora merges swap
    ``ctx.params`` between rounds, hence the id key).
    """

    stream = True

    def __init__(self, mesh=None, block: int = 256):
        super().__init__()
        self._mesh = mesh
        self.block = int(block)
        self._params_cache: Dict[int, Any] = {}

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.topology import make_fed_mesh
            self._mesh = make_fed_mesh()
        return self._mesh

    def _pad(self, k_c: int, ctx) -> int:
        from repro.topology import axis_size
        data = axis_size(self.mesh, "data")
        pow2 = 1 << (k_c - 1).bit_length()
        return -(-pow2 // data) * data

    def _train_fn(self, ctx):
        return _cached_sharded_cohort_train(ctx.cfg, ctx.optim, 64,
                                            ctx.aggregator.trains_b_only,
                                            self.mesh)

    def _params(self, ctx):
        from jax.sharding import NamedSharding, PartitionSpec as P
        key = id(ctx.params)
        if key not in self._params_cache:
            self._params_cache.clear()   # params swapped (flora merge)
            self._params_cache[key] = jax.device_put(
                ctx.params, NamedSharding(self.mesh, P()))
        return self._params_cache[key]

    def _blocks(self, tasks: List) -> Iterator[List]:
        for i in range(0, len(tasks), self.block):
            yield tasks[i: i + self.block]


# ---------------------------------------------------------------------------
# contract: the sharded cohort step's aval fixed point + fed partitioning
# ---------------------------------------------------------------------------

from repro.analysis.registry import ContractCase, check_contract  # noqa: E402


@check_contract("fed.cohort_step")
def _contract_cohort_step(case):
    """Stacked adapter avals are a fixed point of the cohort train step
    (else the round loop retraces every cohort), and the client-stacked
    trees partition under the fed rules at the case's mesh width."""
    from repro.analysis import fixtures as FX
    from repro.common.config import OptimConfig
    from repro.topology import fed_client_pspecs
    from jax.sharding import PartitionSpec as P

    cfg = FX.tiny_config(case.family)
    params = FX.abstract_params(cfg)
    adapters = FX.abstract_adapters(cfg, params)
    C, steps, bs, seq = 4, 2, 2, 16
    stacked = jax.tree.map(
        lambda l: FX.sds((C,) + tuple(l.shape), l.dtype), adapters)
    batch = {"tokens": FX.sds((C, steps, bs, seq), jnp.int32),
             "loss_mask": FX.sds((C, steps, bs, seq), jnp.float32)}
    fn = _cohort_train_fn(cfg, OptimConfig(), 64, False)

    def out_check(out, _case):
        assert FX.avals_equal(out, stacked), "cohort adapter avals drift"

    mesh = FX.abstract_fed_mesh(case.mesh)
    specs = ({"params": params, "cohort": stacked, "batch": batch},
             {"params": jax.tree.map(lambda l: P(*([None] * l.ndim)), params),
              "cohort": fed_client_pspecs(mesh, stacked),
              "batch": fed_client_pspecs(mesh, batch)})
    return ContractCase(fn, (params, stacked, batch), out_check=out_check,
                        pspec_tree=specs, mesh=mesh)
