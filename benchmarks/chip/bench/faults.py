"""Faults planted in the timed path, for the tests and the calibration
that show ``correct`` comes out false when the path is broken.  The
benchmark's own runs never plant one."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROUND_FAULTS = ("state_unchanged", "half_batch", "altered_answer")
#: faults of a path over several chips only
MESH_FAULTS = ("exchange_dropped",)


def _half_rows(m, axis: int):
    """The loss mask with the rows from half the batch axis on set to 0."""
    keep = jnp.arange(m.shape[axis]) < m.shape[axis] // 2
    return m * keep.reshape((-1,) + (1,) * (m.ndim - axis - 1))


def plant_round(trainer, fault: str, client: int) -> None:
    """Break the trainer's timed path in place.

    * ``state_unchanged``: every train step returns its input adapters and
      optimizer state (a cohort runner's call: its input adapters).
    * ``half_batch``: every train step and the eval see only the first half
      of their rows (the rest masked out): the mean is over those.
    * ``altered_answer``: client ``client``'s trained update is doubled
      where it is produced, before it leaves for the server.
    * ``exchange_dropped``: an aggregator whose finalize is sharded over a
      ``model`` axis of several devices keeps what its first device
      computed; the layers the other chips hold never reach the server
      (their factors and spectra read as zeros).

    The train step is broken both where the sequential runner gets it (the
    trainer's ``_train_step``) and, for a runner that trains a cohort in one
    call (one with ``_train_fn``), in that call.
    """
    get_step = trainer._train_step

    def train_step():
        step = get_step()

        def broken(params, adapters, opt_state, batch):
            if fault == "half_batch":
                batch = dict(batch, loss_mask=_half_rows(batch["loss_mask"], 0))
            out = step(params, adapters, opt_state, batch)
            if fault == "state_unchanged":
                return adapters, opt_state, out[2]
            return out

        return broken

    def cohort_fn(train):
        # batches are (clients, steps, rows, tokens)
        def broken(params, stacked, batch):
            if fault == "half_batch":
                batch = dict(batch, loss_mask=_half_rows(batch["loss_mask"], 2))
            out = train(params, stacked, batch)
            return stacked if fault == "state_unchanged" else out

        return broken

    if fault not in ROUND_FAULTS + MESH_FAULTS:
        raise ValueError(f"unknown round fault {fault!r}")
    if fault in ("state_unchanged", "half_batch"):
        trainer._train_step = train_step
        runner = trainer.runner
        if hasattr(runner, "_train_fn"):
            train_fn = runner._train_fn
            runner._train_fn = lambda ctx: cohort_fn(train_fn(ctx))
    if fault == "half_batch":
        ev = trainer._eval

        def half_eval(params, adapters, batch):
            return ev(params, adapters,
                      dict(batch, loss_mask=_half_rows(batch["loss_mask"], 0)))

        trainer._eval = half_eval
    if fault == "altered_answer":
        send = trainer.transport.client_to_server

        def altered(adapters, aggregator, **kw):
            if kw.get("client_id") == client:
                adapters = jax.tree_util.tree_map_with_path(
                    lambda p, x: x * 2 if getattr(p[-1], "key", None) == "B"
                    else x, adapters)
            return send(adapters, aggregator, **kw)

        trainer.transport.client_to_server = altered
    if fault == "exchange_dropped":
        agg = trainer.aggregator
        mesh = getattr(agg, "mesh", None)
        n = dict(mesh.shape).get("model", 1) if mesh is not None else 1
        if n < 2:
            raise ValueError("exchange_dropped needs an aggregator sharded "
                             "over two devices or more")
        materialize = agg._materialize

        def first_device_only(device):
            out = {}
            for path, (Bg, Ag, sp, p) in device.items():
                # layers are padded to a multiple of n and cut in n blocks
                keep = jnp.arange(Bg.shape[0]) < -(-Bg.shape[0] // n)
                out[path] = (Bg * keep[:, None, None], Ag * keep[:, None, None],
                             sp * keep[:, None], p)
            return materialize(out)

        agg._materialize = first_device_only
