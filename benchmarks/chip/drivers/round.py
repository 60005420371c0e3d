"""Driver of the federated-round cells: ``FederatedTrainer.run_round``.

Set-up builds one trainer from the configuration, the traffic file and the
devices the cell uses.  The traffic file gives the clients and ranks, tau,
local steps, batch, packed client data and eval rows, and the deployment:
the client ``runner`` (default ``sequential``) and the aggregation
``method`` (default ``florist``).  A runner or aggregator that takes a mesh
gets one over the cell's devices, as its own default would over every
device JAX sees: the runner the fed mesh (``data`` over the devices), the
aggregator a mesh with one ``model`` axis; any other the trainer builds
itself.  Base weights and the shared LoRA
init are made by the benchmark from the seed (``bench.model`` and the
configuration's family).  The trainer's runner, transport, gate,
aggregator and eval are wrapped in host spans.  Set-up then runs
``warm_rounds`` rounds through the same calls the window makes: they
compile every executable, and the small eager programs of the global
adapters' widths while FLoRIST's kept ranks settle over the first rounds.
Round 0 is recorded for the check: each client's upload before the wire,
the finalized global adapters, the checked clients' batches as the
program's own schedule draws them (``runners._batch_schedule``, the draw
every runner makes), and, where the runner steps through the trainer's
train step, each step's inputs and outputs.

The window runs whole rounds until ``--seconds`` is reached.  Every
deployment setting comes from the traffic file; no performance option of
the program is set.

The check compares round 0 with the plain reference: the reference
follows ``check_clients`` clients through their local steps from the same
init and batches (the adapters' change; where the runner steps through the
train step, also each step's loss and the first gradient as the optimizer
got it), runs the
FLoRIST server on the uploads the clients produced (kept spectrum and
global update), and evaluates its own global update on the eval rows (eval
loss).  ``readings_made`` names the numbers compared; set-up fails where
the cell's limits name others.
"""
from __future__ import annotations

import importlib
import inspect
from typing import Dict, List, Optional
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare as cmp
from bench import model
from bench import traffic as tr

#: every reading; the first two need the per-step record
READINGS = ("step_loss", "grad_norm", "change_norm", "spectrum",
            "rank_energy", "global_update")


def _shared_init(weights, targets, rmax: int, seed: int):
    """The clients' shared LoRA init, made by the benchmark, as a program
    adapter tree (the trainer's own init uses alpha equal to the largest
    rank, so scale 1)."""
    return model.lora_init(weights, targets, rmax,
                           jax.random.fold_in(model.seed_key(seed), 2))


def _runner(name: str, devices):
    """The named client runner, as the trainer would make it; one that
    takes a mesh gets the fed mesh over ``devices``."""
    from repro.core.runtime import runners
    from repro.topology import make_fed_mesh

    cls = runners._REGISTRY.get(name)
    if cls is not None and "mesh" in inspect.signature(cls).parameters:
        return runners.make_runner(name, mesh=make_fed_mesh(len(devices),
                                                            devices=devices))
    return name


def _aggregator(fed, devices):
    """``None``, so that the trainer builds its own aggregator, unless
    ``fed.method``'s takes a mesh: then the trainer's construction, with a
    ``model`` axis over ``devices``."""
    from jax.sharding import Mesh

    # importing the program's distributed module registers its sharded
    # backends, as its own command line does
    import repro.core.distributed  # noqa: F401
    from repro.core.aggregators import (accepted_config, get_aggregator_class,
                                        make_aggregator)
    from repro.core.federated import FederatedTrainer

    cls = get_aggregator_class(fed.method)
    if "mesh" not in inspect.signature(cls).parameters:
        return None
    svd = inspect.signature(FederatedTrainer).parameters["svd_method"].default
    cfg = dict(tau=fed.tau, svd_method=svd, zero_padding=fed.zero_padding,
               mesh=Mesh(np.asarray(devices), ("model",)))
    return make_aggregator(fed.method, **accepted_config(fed.method, cfg))


def _stepwise(runner) -> bool:
    """Whether the runner steps each client through the trainer's train
    step: every runner but the cohort runners, which train a cohort in one
    vmapped call and keep only its final adapters."""
    from repro.core.runtime.runners import CohortRunner

    return not isinstance(runner, CohortRunner)


def _cut(factors: Dict, rank: int) -> Dict:
    return {t: (A[:, :rank], B[:, :, :rank], s) for t, (A, B, s) in factors.items()}


def _leaves(factors: Dict) -> Dict:
    """{(target, "A"|"B"): array} of a {target: (A, B, scale)} dict."""
    out = {}
    for t, (A, B, *_) in factors.items():
        out[(t, "A")], out[(t, "B")] = A, B
    return out


class RoundCell:
    def __init__(self, c: dict, t: dict, seed: int, spans, devices,
                 fault=None):
        from repro.common.config import FedConfig, LoRAConfig, OptimConfig
        from repro.core.federated import FederatedTrainer
        from repro.data.synthetic import ClientDataset
        from repro.models import transformer as T

        self.c, self.t, self.seed, self.spans = c, t, seed, spans
        self.fam = model.family(c)
        self.ref = importlib.import_module("references." + c["reference"])
        self.targets = tuple(t["targets"])
        self.ranks = [r for r, n in t["clients"] for _ in range(n)]
        self.rmax = max(self.ranks)
        self.weights = model.make_weights(c, seed)
        init_tree = _shared_init(self.weights, self.targets, self.rmax, seed)
        self.init = model.lora_factors(self.fam, init_tree)
        clients, ev = tr.client_corpus(t, c["vocab_size"], seed)
        data = [ClientDataset(tok, m, tok.shape[0]) for tok, m in clients]
        n_total = sum(d.num_samples for d in data)
        self.client_weight = [d.num_samples / n_total for d in data]
        o = t["optim"]
        self.optim = {"lr": o["lr"], "betas": tuple(o["betas"]), "eps": o["eps"],
                      "weight_decay": o["weight_decay"],
                      "grad_clip": o["grad_clip"]}
        fed = FedConfig(num_clients=len(self.ranks),
                        clients_per_round=len(self.ranks),
                        method=t.get("method", "florist"),
                        tau=t["tau"], heterogeneous=True,
                        rank_distribution=tuple(tuple(x) for x in t["clients"]),
                        seed=seed % 2**31)
        lora = LoRAConfig(rank=self.rmax, targets=self.targets)
        optim = OptimConfig(**self.optim)
        # the trainer makes its base weights with transformer.init; it is
        # handed the benchmark's instead, so the reference shares them
        with mock.patch.object(T, "init", lambda cfg, key: self.weights):
            self.trainer = FederatedTrainer(
                self.fam.program_config(c), fed, lora, optim, clients=data,
                eval_data={"tokens": ev[0], "loss_mask": ev[1]},
                batch_size=t["batch"], local_steps=t["local_steps"],
                seq_len=t["seq_len"], aggregator=_aggregator(fed, devices),
                runner=_runner(t.get("runner", "sequential"), devices))
        self.trainer.A_init_full = init_tree
        self.stepwise = _stepwise(self.trainer.runner)
        if fault is not None:
            from bench import faults
            faults.plant_round(self.trainer, fault,
                               client=int(np.argmax(self.ranks)))
        self._instrument()
        self.next_round = 0
        self.obs: Optional[dict] = None
        for _ in range(t["warm_rounds"]):
            self._round(record=(self.next_round == 0))

    # -- instrumentation ------------------------------------------------------
    def _instrument(self):
        tr_, sp = self.trainer, self.spans
        self.recording = False
        self._plan = None
        self._steps: List[tuple] = []
        self._by_client: Dict[int, List[tuple]] = {}
        self._uploads: Dict[int, dict] = {}
        get_step = tr_._train_step

        def train_step():
            step = get_step()

            def recorded(params, adapters, opt_state, batch):
                out = step(params, adapters, opt_state, batch)
                if self.recording:
                    self._steps.append((adapters, batch, out))
                return out

            return recorded

        tr_._train_step = train_step

        def before_run(ctx, plan, deliver):
            if self.recording:
                self._plan = plan

        def before_wire(adapters, aggregator, **kw):
            jax.block_until_ready(adapters)
            if self.recording:
                k = kw["client_id"]
                self._by_client[k], self._steps = self._steps, []
                self._uploads[k] = adapters

        sp.wrap(tr_.runner, "run", "clients", before=before_run)
        sp.wrap(tr_.transport, "client_to_server", "wire", before=before_wire)
        sp.wrap(tr_.gate, "submit", "gate")
        sp.wrap(tr_.transport, "server_to_clients", "downlink")
        sp.wrap(tr_.aggregator, "finalize", "finalize",
                ready=lambda r: jax.block_until_ready(r.global_adapters))
        sp.wrap(tr_, "_eval", "eval", ready=jax.block_until_ready)

    def _round(self, record: bool = False):
        self.recording = record
        rec = self.trainer.run_round(self.next_round)
        self.next_round += 1
        if record:
            self.recording = False
            self._record()
        return rec

    def _record(self):
        """Keep round 0 for the check; the steps recorded for each checked
        client have to be as many as its batches where the runner is
        ``stepwise``, else none."""
        from repro.core.runtime.runners import _batch_schedule

        fam, gs = self.fam, self.trainer.global_state
        batches = {task.client_id: [
            (np.asarray(b["tokens"]), np.asarray(b["loss_mask"]))
            for b in _batch_schedule(self.trainer, self._plan.round, task)]
            for task in self._plan.tasks}
        stepped = {k: len(self._by_client.get(k, ()))
                   for k in self.checked_clients()}
        due = {k: len(batches[k]) if self.stepwise else 0 for k in stepped}
        if stepped != due:
            raise RuntimeError(f"train steps recorded per checked client "
                               f"{stepped}; this runner makes {due}")
        glob = model.lora_factors(fam, gs.global_adapters)
        self.obs = {
            "steps": self._by_client, "uploads": self._uploads,
            "batches": batches,
            "spectra": {fam.lora_key(p): [np.asarray(s) for s in v]
                        for p, v in gs.spectra.items()},
            "ranks": {fam.lora_key(p): list(v) for p, v in gs.ranks.items()},
            "global": {t: (np.asarray(A, np.float32),
                           np.asarray(B, np.float32) * np.asarray(s)[:, None, None])
                       for t, (A, B, s) in glob.items()}}

    @property
    def readings_made(self) -> tuple:
        """The numbers this cell's check compares, by its runner: all of
        ``READINGS`` where it is ``stepwise``, else all but the per-step
        ``step_loss`` and ``grad_norm``."""
        return READINGS if self.stepwise else READINGS[2:]

    # -- the measured window --------------------------------------------------
    def window(self, seconds: float, clock) -> dict:
        recs, ends = [], []
        t0 = clock()
        while True:
            recs.append(self._round())
            ends.append(clock())
            if ends[-1] - t0 >= seconds:
                break
        wall = ends[-1] - t0
        self.rounds = len(recs)
        failed = sum(r.dead_clients + r.rejected + r.quarantined for r in recs)
        failed += sum(len(self.ranks) for r in recs if not r.quorum_met)
        return {"metrics": {"round_s": wall / len(recs)},
                "attempted": len(recs) * len(self.ranks), "failed": int(failed),
                "info": {"rounds": len(recs), "window_wall_s": wall,
                         "round_walls_s": [float(x) for x in np.diff([t0] + ends)]}}

    def layer_context(self) -> dict:
        dims = self.fam.Dims.from_config(self.c)
        t = self.t
        steps = [r for r in self.ranks for _ in range(t["local_steps"])] * self.rounds
        return {"kind": "round", "dims": dims, "rounds": self.rounds,
                "train_steps": [self.fam.train_step_work(
                    dims, t["batch"], t["seq_len"], r, self.targets)
                                for r in steps]}

    def memory_report(self) -> str:
        """What the compiler says of the largest client step (where the
        runner steps through the trainer's train step) and the eval, beside
        the device's own peak counter."""
        from repro.core.federated import _cached_eval_step, _cached_train_step
        from repro.optim.adamw import adamw_init
        from repro.train.loss import bounded_loss_chunk

        tr_ = self.trainer
        out = []
        if self.stepwise:
            step = _cached_train_step(tr_.cfg, tr_.optim, 64,
                                      tr_.aggregator.trains_b_only)
            ad = model.lora_tree(self.fam, _cut(self.init, self.rmax))
            b = {"tokens": jnp.zeros((self.t["batch"], self.t["seq_len"]), jnp.int32),
                 "loss_mask": jnp.zeros((self.t["batch"], self.t["seq_len"]),
                                        jnp.float32)}
            m = step.lower(tr_.params, ad, adamw_init(ad), b).compile().memory_analysis()
            out.append(f"train_step rank {self.rmax}: temp {m.temp_size_in_bytes} "
                       f"arguments {m.argument_size_in_bytes}")
        rows, sl = tr_.eval_batch["tokens"].shape
        ev = _cached_eval_step(tr_.cfg, bounded_loss_chunk(rows, sl, self.c["vocab_size"]))
        e = ev.lower(tr_.params, None, tr_.eval_batch).compile().memory_analysis()
        out.append(f"eval_step: temp {e.temp_size_in_bytes} "
                   f"arguments {e.argument_size_in_bytes}")
        return "; ".join(out)

    def release(self):
        """Free the program's state before the reference runs."""
        self.trainer = None

    # -- the check ------------------------------------------------------------
    def checked_clients(self) -> List[int]:
        rng = np.random.default_rng([self.seed, 13])
        n = min(self.t["check_clients"], len(self.ranks))
        top = int(np.argmax(self.ranks))
        rest = [k for k in rng.permutation(len(self.ranks)) if k != top]
        return sorted([top] + [int(k) for k in rest[:n - 1]])

    def program_observed(self) -> dict:
        """Round 0 as the program ran it, in the reference's terms."""
        o, fam = self.obs, self.fam
        b1 = self.optim["betas"][0]
        uploads = {k: model.lora_factors(fam, v) for k, v in o["uploads"].items()}
        steps = {}
        for k in self.checked_clients():
            steps[k] = {"final": _leaves(uploads[k])}
            if self.stepwise:
                recs = o["steps"][k]
                mu = model.lora_factors(fam, recs[0][2][1]["mu"])
                steps[k]["losses"] = [float(r[2][2]["loss"]) for r in recs]
                steps[k]["grad1"] = {key: np.asarray(v) / (1 - b1)
                                     for key, v in _leaves(mu).items()}
        return {"steps": steps, "uploads": uploads, "spectra": o["spectra"],
                "ranks": o["ranks"], "global": o["global"]}

    def batches(self, k: int):
        """Client k's round-0 batches as the program drew them."""
        return self.obs["batches"][k]

    def _local(self, k: int, precision: str):
        return self.ref.adamw_steps(self.c, self.weights,
                                    _cut(self.init, self.ranks[k]),
                                    self.batches(k), self.optim, precision)

    def _ref_global(self, uploads: Dict):
        """FLoRIST on the uploads: per target (dW, svd, spectra, ranks), and
        the global update as LoRA factors at the reference's own ranks."""
        ks = sorted(uploads)
        res = self.ref.florist([uploads[k] for k in ks],
                               [self.client_weight[k] for k in ks],
                               self.t["tau"])
        factors = {}
        for t, (_, (u, s, vt), _, ranks) in res.items():
            p = max(ranks)
            keep = jnp.arange(p)[None, :] < jnp.asarray(ranks)[:, None]
            factors[t] = (vt[:, :p, :] * keep[:, :, None],
                          u[:, :, :p] * (s[:, None, :p] * keep[:, None, :]),
                          jnp.ones(s.shape[0], jnp.float32))
        return res, factors

    def control_observed(self) -> dict:
        """The control: the reference in the program's place, computed in
        the precision below the configuration's (fp8 for the bf16 model,
        bf16 inputs for the fp32 server), on the batches the program drew."""
        steps, uploads = {}, {}
        for k in range(len(self.ranks)):
            final, losses, grads = self._local(k, "fp8")
            uploads[k] = final
            steps[k] = {"losses": losses, "grad1": _leaves(grads[0]),
                        "final": _leaves(final)}
        low = {k: {t: (jnp.asarray(A, jnp.bfloat16).astype(jnp.float32),
                       jnp.asarray(B, jnp.bfloat16).astype(jnp.float32), s)
                   for t, (A, B, s) in v.items()} for k, v in uploads.items()}
        res, factors = self._ref_global(low)
        return {"steps": steps, "uploads": uploads,
                "spectra": {t: list(v[2]) for t, v in res.items()},
                "ranks": {t: v[3] for t, v in res.items()},
                "global": {t: (np.asarray(A), np.asarray(B))
                           for t, (A, B, _) in factors.items()}}

    def check(self) -> Dict[str, float]:
        return self.readings(self.program_observed())

    def readings(self, observed: dict) -> Dict[str, float]:
        """Each number compared, from ``observed`` (the program's round 0 or
        the control's) against the float32 reference."""
        out = {k: 0.0 for k in self.readings_made}
        for k in self.checked_clients():
            ob = observed["steps"][k]
            final, losses, grads = self._local(k, "float32")
            g_ref = [_leaves(g) for g in grads]
            if "step_loss" in out:
                out["step_loss"] = cmp.worst(out["step_loss"], max(
                    cmp.rel(a, b) for a, b in zip(ob["losses"], losses)))
                out["grad_norm"] = cmp.worst(out["grad_norm"], max(
                    cmp.norm_gaps(ob["grad1"], g_ref[0]).values()))
            init = _leaves(_cut(self.init, self.ranks[k]))
            # leaves the reference's gradient leaves at rounding (under a
            # thousandth of the median leaf's at every step) move by
            # round-off alone: they are left out of the change
            meds = [np.median([cmp.norm(v) for v in g.values()]) for g in g_ref]
            still = {key for key in init
                     if max(cmp.norm(g[key]) / max(m, 1e-30)
                            for g, m in zip(g_ref, meds)) < 1e-3}
            fin = _leaves(final)
            d_p = {key: np.asarray(ob["final"][key]) - np.asarray(init[key])
                   for key in init}
            d_r = {key: np.asarray(fin[key]) - np.asarray(init[key])
                   for key in init}
            out["change_norm"] = cmp.worst(out["change_norm"], max(
                cmp.norm_gaps(d_p, d_r, exclude=still).values()))
        res, factors = self._ref_global(observed["uploads"])
        tau = self.t["tau"]
        for t, (dw, svd, spectra, ranks) in res.items():
            for l, s_ref in enumerate(spectra):
                s_p = np.asarray(observed["spectra"][t][l])
                n = min(len(s_p), len(s_ref))
                out["spectrum"] = cmp.worst(out["spectrum"], float(
                    np.max(np.abs(s_p[:n] - s_ref[:n])) / s_ref[0]))
                # the program's kept rank against the threshold in the
                # reference's energies: how far below tau it stopped, or how
                # far past tau it went on
                p = int(observed["ranks"][t][l])
                e = np.cumsum(np.asarray(s_ref, np.float64) ** 2)
                e = e / e[-1]
                miss = max(tau - e[p - 1], (e[p - 2] - tau) if p > 1 else 0.0, 0.0)
                out["rank_energy"] = cmp.worst(out["rank_energy"], float(miss))
                # the program's global update as a rank-p approximation of
                # the clients' update: its error beyond the best rank-p one
                # (Eckart-Young), as a share of the update
                full = np.asarray(dw[l], np.float64)
                A, Bs = observed["global"][t]
                got = np.asarray(Bs[l], np.float64) @ np.asarray(A[l], np.float64)
                best = np.asarray(self.ref.truncated(svd, l, p), np.float64)
                nf = max(np.linalg.norm(full), 1e-30)
                excess = (np.linalg.norm(full - got) - np.linalg.norm(full - best)) / nf
                out["global_update"] = cmp.worst(out["global_update"], float(excess))
        return out


def setup(c: dict, t: dict, seed: int, spans, seconds: float, devices,
          fault=None) -> RoundCell:
    return RoundCell(c, t, seed, spans, devices, fault)
