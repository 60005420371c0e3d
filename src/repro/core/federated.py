"""Federated fine-tuning orchestration (paper §4.1 setup).

Simulates the full loop: 100 clients with Dirichlet(0.5) non-IID data, 10
sampled per round, local LoRA fine-tuning, server aggregation and
global-model evaluation — composed from four pluggable seams
(:mod:`repro.core.runtime`):

* a **RoundScheduler** decides who participates (``scheduler=``: ``sync``
  reproduces the paper's sample-K-wait-for-all semantics bit-for-bit;
  ``partial`` injects dropouts/stragglers with per-client step budgets;
  ``async`` buffers staleness-discounted arrivals; ``sampled`` draws a
  seed-deterministic participation fraction of the full population);
* a **RankPolicy** (``rank_policy=``: ``static`` / ``resource``) may then
  adapt each task's LoRA rank to a declared client resource profile
  (AFLoRA-style) before training starts;
* a **ClientRunner** executes local fine-tuning (``runner=``:
  ``sequential`` is the legacy one-client-at-a-time loop; ``cohort``
  trains each equal-rank cohort in one jitted vmapped train-step call;
  ``sharded_cohort`` additionally shards the cohort's client axis over the
  fed mesh's ``data`` axis — 1024 clients in a handful of compiled calls);
* a **Transport** puts every exchanged adapter tree on a measured wire
  (``transport=`` codec: ``fp32`` exact / ``bf16`` / ``int8``), so each
  :class:`RoundRecord` carries real serialized ``upload_bytes`` /
  ``download_bytes`` next to the analytic parameter counts — with
  ``dp_clip``/``dp_sigma`` set, uploads are clipped/noised on the wire
  (local DP) before encoding, whatever the codec;
* an **Aggregator** owns the method semantics (client re-init, frozen-A
  composition, base merging, truncation, cost formulas) — pass
  ``aggregator=`` for a custom strategy, otherwise one is built from
  ``fed.method`` via the registry.

The server side is **streaming**: each delivered client update is folded
into the aggregator's running accumulators (``add_client``) and dropped
before the next arrives, so peak server memory per round is one client's
adapters plus the O(Σ r_k) per-leaf accumulators — never all K sampled
adapter trees at once.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.common import telemetry
from repro.common.config import FedConfig, LoRAConfig, ModelConfig, OptimConfig
from repro.core.aggregators import (AggResult, Aggregator, accepted_config,
                                    make_aggregator)
from repro.core.runtime import (ClientRunner, DeadClientError, RankPolicy,
                                RoundScheduler, ServerCrash, Transport,
                                ValidationGate, make_rank_policy, make_runner,
                                make_scheduler, make_transport,
                                make_validator)
from repro.data.synthetic import ClientDataset, make_eval_data, make_federated_data
from repro.models import transformer as T
from repro.peft.lora import init_lora, merge_lora
from repro.train.loss import bounded_loss_chunk
from repro.train.step import make_eval_step, make_train_step


# jit'd step factories shared across trainer instances: configs are frozen
# (hashable) dataclasses, and jax.jit re-specializes per input shape, so a
# sweep over τ / methods / seeds compiles each (config, shapes) program once
# instead of once per FederatedTrainer.
@functools.lru_cache(maxsize=None)
def _cached_train_step(cfg: ModelConfig, optim: OptimConfig, loss_chunk: int,
                       b_only: bool):
    return jax.jit(make_train_step(cfg, optim, remat=False,
                                   loss_chunk=loss_chunk, b_only=b_only))


@functools.lru_cache(maxsize=None)
def _cached_eval_step(cfg: ModelConfig, loss_chunk: int):
    return jax.jit(make_eval_step(cfg, loss_chunk=loss_chunk))


@dataclasses.dataclass
class RoundRecord:
    round: int
    eval_loss: float
    eval_acc: float
    upload_params: int
    download_params: int
    download_rank: float
    global_rank_total: int
    upload_bytes: int = 0        # measured serialized uplink (all clients)
    download_bytes: int = 0      # measured serialized downlink (all clients)
    wall_secs: float = 0.0       # wall-clock of the whole round (its span)
    # -- fault-tolerance counters (PR 10) -----------------------------------
    retries: int = 0             # uplink re-sends after verification failure
    dead_clients: int = 0        # dropped uploads + retry-exhausted clients
    rejected: int = 0            # gate rejections (non-finite/shape/dup)
    quarantined: int = 0         # norm-outlier quarantines (full mode)
    quorum_met: bool = True      # round reached min_clients accepted updates
    resumes: int = 0             # 1 on the first round after --resume
    sim_secs: float = 0.0        # simulated time (backoff + slow clients)


class FederatedTrainer:
    """Thin composition of runner + scheduler + aggregator + transport.

    ``runner`` / ``scheduler`` / ``transport`` accept either a registered
    name (``"sequential"``, ``"sync"``, codec ``"fp32"``, ...) or an
    instance, so behaviours can be configured or injected.  The defaults
    reproduce the pre-runtime ``run_round`` bit-for-bit.
    """

    def __init__(self, cfg: ModelConfig, fed: FedConfig, lora: LoRAConfig,
                 optim: OptimConfig, clients: Optional[List[ClientDataset]] = None,
                 eval_data: Optional[Dict] = None, batch_size: int = 8,
                 local_steps: int = 4, seq_len: int = 64, svd_method: str = "svd",
                 targets: Optional[tuple] = None,
                 dp_clip: float = 0.0, dp_sigma: float = 0.0,
                 aggregator: Optional[Aggregator] = None,
                 runner: Any = "sequential",
                 scheduler: Any = "sync",
                 rank_policy: Any = "static",
                 transport: Any = "fp32",
                 faults: Any = None,
                 validation: Any = "screen",
                 min_clients: int = 1):
        self.cfg, self.fed, self.lora, self.optim = cfg, fed, lora, optim
        self.batch_size, self.local_steps = batch_size, local_steps
        self.svd_method = svd_method
        # client-level differential privacy, applied on the wire by the
        # transport's uplink DP stage (see core/runtime/transport)
        self.dp_clip, self.dp_sigma = dp_clip, dp_sigma
        # deterministic fault injection (None: healthy world) and the
        # validation gate screening every fold (see core/runtime/faults,
        # core/runtime/validation)
        self.faults = faults
        self.gate: ValidationGate = make_validator(
            validation, min_clients=min_clients)
        self.rng = np.random.default_rng(fed.seed)
        key = jax.random.PRNGKey(fed.seed)
        kp, ka = jax.random.split(key)
        self.params = T.init(cfg, kp)
        self.targets = targets or lora.targets
        self.client_ranks = fed.client_ranks()
        self.max_rank = max(self.client_ranks)
        # one shared init at max rank; client k uses its first r_k rows
        self.A_init_full = init_lora(self.params, self.targets, self.max_rank,
                                     float(self.max_rank), ka)
        self.aggregator = aggregator if aggregator is not None else \
            make_aggregator(fed.method, **accepted_config(fed.method, dict(
                tau=fed.tau, svd_method=svd_method,
                zero_padding=fed.zero_padding)))
        # strategies that declare needs_a_init (FFA-style) are handed the
        # frozen shared init explicitly; everything else is left untouched
        if getattr(self.aggregator, "needs_a_init", False) \
                and getattr(self.aggregator, "A_init", None) is None:
            self.aggregator.A_init = self.A_init_full
        self.runner: ClientRunner = make_runner(runner)
        self.scheduler: RoundScheduler = make_scheduler(scheduler)
        self.rank_policy: RankPolicy = make_rank_policy(rank_policy)
        self.transport: Transport = make_transport(
            transport, dp_clip=dp_clip, dp_sigma=dp_sigma, dp_seed=fed.seed,
            fault_plan=faults)
        self.global_state: Optional[AggResult] = None
        self.clients = clients if clients is not None else make_federated_data(
            num_clients=fed.num_clients, seq_len=seq_len,
            vocab=cfg.vocab_size, alpha=fed.dirichlet_alpha, seed=fed.seed)
        ev = eval_data if eval_data is not None else make_eval_data(
            seq_len=seq_len, vocab=cfg.vocab_size)
        self.eval_batch = {k: jnp.asarray(v) for k, v in ev.items()}
        rows, ev_len = self.eval_batch["tokens"].shape
        self._eval = _cached_eval_step(
            cfg, bounded_loss_chunk(rows, ev_len, cfg.vocab_size))
        self.history: List[RoundRecord] = []
        self._pending_resumes = 0    # stamped into the first post-resume record

    # -- helpers -------------------------------------------------------------
    def _train_step(self):
        # rank only affects adapter shapes; jit re-specializes on those, so
        # all ranks share one cached wrapper per (cfg, optim, b_only)
        return _cached_train_step(self.cfg, self.optim, 64,
                                  self.aggregator.trains_b_only)

    def _client_init(self, k: int, rank: Optional[int] = None) -> Dict:
        """Build client k's starting adapters for this round (delegated to
        the aggregation strategy's client-init semantics).  ``rank``
        overrides the client's configured rank when a rank policy adapted
        this round's task."""
        return self.aggregator.client_init(
            self.global_state,
            self.client_ranks[k] if rank is None else rank,
            self.A_init_full)

    def _maybe_crash(self, rnd: int, point: str) -> None:
        if self.faults is not None and self.faults.should_crash(rnd, point):
            raise ServerCrash(rnd, point)

    # -- main loop ------------------------------------------------------------
    def run_round(self, rnd: int) -> RoundRecord:
        """One round; its wall time is the ``round`` span's."""
        with telemetry.span("round", round=rnd) as sp:
            rec = self._round(rnd)
        rec.wall_secs = sp.seconds
        self._pending_resumes = 0
        self.history.append(rec)
        self._maybe_crash(rnd, "post_round")
        return rec

    def _round(self, rnd: int) -> RoundRecord:
        self._maybe_crash(rnd, "begin")
        clock = self.transport.clock
        sim0 = clock.now if clock is not None else 0.0
        self.transport.reset_stats()
        plan = self.scheduler.plan(rnd, self)
        self.rank_policy.assign(rnd, plan, self)
        ranks = [t.rank for t in plan.tasks]
        self.aggregator.begin_round()
        self.gate.begin_round(self.aggregator)
        upload_bytes = 0
        delivered = 0
        dropped = 0
        mid_crash_at = max(1, len(plan.tasks) // 2)

        def deliver(task, adapters, init_adapters=None):
            # uplink through the measured wire (DP clip/noise happens there,
            # against the round's init), then through the validation gate
            # into the server accumulators; the trained adapters go out of
            # scope here (no K-tree round buffer)
            nonlocal upload_bytes, delivered, dropped
            delivered += 1
            fault = (self.faults.client_fault(rnd, task.client_id)
                     if self.faults is not None else None)
            try:
                if fault is not None:
                    if fault.kind == "drop":
                        dropped += 1
                        return
                    if fault.kind == "slow" and clock is not None:
                        clock.advance(fault.delay)
                    adapters = self.faults.poison(adapters, init_adapters,
                                                  rnd, task.client_id)
                adapters, nbytes = self.transport.client_to_server(
                    adapters, self.aggregator, init_adapters=init_adapters,
                    rnd=rnd, client_id=task.client_id)
                upload_bytes += nbytes
                self.gate.submit(task, adapters, task.weight, rank=task.rank,
                                 init_adapters=init_adapters)
                if fault is not None and fault.kind == "duplicate":
                    # at-least-once wire: the same upload arrives twice —
                    # the gate's dedup must fold it exactly once
                    self.gate.submit(task, adapters, task.weight,
                                     rank=task.rank,
                                     init_adapters=init_adapters)
            except DeadClientError:
                pass        # counted in transport stats; treated as a drop
            finally:
                if delivered == mid_crash_at:
                    self._maybe_crash(rnd, "mid_round")

        self.runner.run(self, plan, deliver)
        self._maybe_crash(rnd, "pre_finalize")
        gstats = self.gate.finish()
        tstats = self.transport.reset_stats()
        if not gstats.quorum_met or self.aggregator.num_clients == 0:
            return self._degraded_round(rnd, sim0, gstats, tstats,
                                        upload_bytes, dropped)
        agg = self.aggregator.finalize()
        dims = self.aggregator.dims
        up = self.aggregator.round_upload_params
        # participation-aware downlink count: only clients actually handed
        # the model this round (async: dispatch-time snapshots)
        n_down = plan.downloads if plan.downloads is not None \
            else len(plan.tasks)
        down = self.aggregator.download_params(agg, dims, n_down, ranks)

        # downlink through the measured wire: what the clients resume from
        # next round is the decoded broadcast (identity under fp32)
        bcast, download_bytes = self.transport.server_to_clients(
            agg, self.aggregator, n_down)
        if agg.merge_into_base:
            # FLoRA: every *client* folds the broadcast stack into its base,
            # so the merge consumes the decoded wire tensors, codec included
            if bcast is not None:
                agg.global_adapters = bcast
            with telemetry.span("merge"):
                self.params = merge_lora(self.params, agg.global_adapters)
            eval_params = self.params
        else:
            # broadcast methods: the server evals its exact aggregate;
            # clients resume from the decoded broadcast
            with telemetry.span("merge"):
                eval_params = merge_lora(self.params, agg.global_adapters)
            if bcast is not None:
                agg.global_adapters = bcast
        self.global_state = agg

        loss, acc = self._evaluate(eval_params)
        return RoundRecord(
            round=rnd,
            eval_loss=loss,
            eval_acc=acc,
            upload_params=up,
            download_params=down,
            download_rank=agg.total_download_rank()
            * self.aggregator.download_rank_factor,
            global_rank_total=agg.total_download_rank(),
            upload_bytes=upload_bytes,
            download_bytes=download_bytes,
            retries=tstats.retries,
            dead_clients=tstats.dead_clients + dropped,
            rejected=gstats.rejected,
            quarantined=gstats.quarantined,
            quorum_met=True,
            resumes=self._pending_resumes,
            sim_secs=(clock.now - sim0) if clock is not None else 0.0,
        )

    def _evaluate(self, params) -> Tuple[float, float]:
        """Eval loss and accuracy of ``params`` on the eval rows."""
        with telemetry.span("eval"):
            m = self._eval(params, None, self.eval_batch)
            return float(m["loss"]), float(m["accuracy"])

    def _degraded_round(self, rnd: int, sim0: float, gstats, tstats,
                        upload_bytes: int, dropped: int = 0) -> RoundRecord:
        """Quorum failure: too few accepted updates to trust a fold.  The
        round degrades gracefully — the previous global state is kept (the
        half-filled accumulator is never finalized), clients will resume
        from the old broadcast, and the record carries the fault counters
        so the failure is visible in the history."""
        gs = self.global_state
        if gs is not None and gs.global_adapters is not None \
                and not gs.merge_into_base:
            with telemetry.span("merge"):
                eval_params = merge_lora(self.params, gs.global_adapters)
        else:
            eval_params = self.params
        loss, acc = self._evaluate(eval_params)
        clock = self.transport.clock
        return RoundRecord(
            round=rnd,
            eval_loss=loss,
            eval_acc=acc,
            upload_params=self.aggregator.round_upload_params,
            download_params=0,
            download_rank=0.0,
            global_rank_total=(gs.total_download_rank()
                               if gs is not None else 0),
            upload_bytes=upload_bytes,
            download_bytes=0,
            retries=tstats.retries,
            dead_clients=tstats.dead_clients + dropped,
            rejected=gstats.rejected,
            quarantined=gstats.quarantined,
            quorum_met=False,
            resumes=self._pending_resumes,
            sim_secs=(clock.now - sim0) if clock is not None else 0.0,
        )

    # -- checkpoint / resume ---------------------------------------------------
    def state_dict(self, next_round: int) -> Dict[str, Any]:
        """Everything a fresh process needs to continue from ``next_round``
        bit-identically: base params, global state, the shared rng's exact
        bit-generator state, scheduler in-flight pools, the aggregator's
        streaming accumulators, and the full RoundRecord history."""
        gs = self.global_state
        return {
            "next_round": int(next_round),
            "rng": self.rng.bit_generator.state,
            "params": ckpt_io.to_host(self.params),
            "global_state": None if gs is None else {
                "method": gs.method,
                "global_adapters": ckpt_io.to_host(gs.global_adapters),
                "per_client": ckpt_io.to_host(gs.per_client),
                "ranks": gs.ranks,
                "spectra": ckpt_io.to_host(gs.spectra),
                "merge_into_base": gs.merge_into_base,
            },
            "scheduler": self.scheduler.state_dict(),
            "aggregator": self.aggregator.state_dict(),
            "history": [dataclasses.asdict(r) for r in self.history],
        }

    def load_state_dict(self, state: Dict[str, Any]) -> int:
        """Inverse of :meth:`state_dict`; returns the round to run next."""
        self.rng.bit_generator.state = state["rng"]
        self.params = ckpt_io.to_device(state["params"])
        gs = state["global_state"]
        self.global_state = None if gs is None else AggResult(
            method=gs["method"],
            global_adapters=ckpt_io.to_device(gs["global_adapters"]),
            per_client=ckpt_io.to_device(gs["per_client"]),
            ranks=gs["ranks"],
            spectra=ckpt_io.to_device(gs["spectra"]),
            merge_into_base=gs["merge_into_base"],
        )
        self.scheduler.load_state_dict(state["scheduler"])
        self.aggregator.load_state_dict(state["aggregator"])
        self.history = [RoundRecord(**r) for r in state["history"]]
        return int(state["next_round"])

    def save_checkpoint(self, path: str, next_round: int) -> None:
        """Atomically persist the round-boundary state (temp file +
        ``os.replace`` via :func:`repro.checkpoint.io.save_state`)."""
        ckpt_io.save_state(path, self.state_dict(next_round))

    def restore_checkpoint(self, path: str) -> int:
        """Restore a :meth:`save_checkpoint` blob; returns the next round.
        The first record produced afterwards carries ``resumes=1``."""
        start = self.load_state_dict(ckpt_io.restore_state(path))
        self._pending_resumes = 1
        return start

    def run(self, num_rounds: Optional[int] = None, verbose: bool = False,
            checkpoint: str = "", checkpoint_every: int = 0,
            resume: bool = False) -> List[RoundRecord]:
        """Run rounds ``[start, num_rounds)``.  With ``checkpoint`` set,
        the round-boundary state is saved atomically every
        ``checkpoint_every`` rounds (default 1); with ``resume``, a run
        killed at any point restarts from the last saved boundary and —
        because every in-round decision is a pure function of restored
        state — replays to a bit-identical history."""
        start = 0
        if resume and checkpoint and os.path.exists(checkpoint):
            start = self.restore_checkpoint(checkpoint)
        every = checkpoint_every or (1 if checkpoint else 0)
        for rnd in range(start, num_rounds or self.fed.num_rounds):
            lo = time.perf_counter()
            rec = self.run_round(rnd)
            if verbose:
                compiled = sum(n for n, _ in
                               telemetry.compiles(lo, time.perf_counter())
                               .values())
                print(f"[{self.aggregator.name:9s}] round {rnd:3d} "
                      f"loss={rec.eval_loss:.4f} acc={rec.eval_acc:.3f} "
                      f"down_rank={rec.download_rank:.0f} "
                      f"up={rec.upload_bytes / 2**20:.2f}MB "
                      f"down={rec.download_bytes / 2**20:.2f}MB "
                      f"{rec.wall_secs:.2f}s compiles={compiled}")
            if checkpoint and every and (rnd + 1) % every == 0:
                self.save_checkpoint(checkpoint, rnd + 1)
        return self.history
