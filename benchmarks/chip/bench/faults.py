"""Faults planted in the timed path, for the tests and the calibration
that show ``correct`` comes out false when the path is broken.  The
benchmark's own runs never plant one."""
from __future__ import annotations

import jax
import jax.numpy as jnp

ROUND_FAULTS = ("state_unchanged", "half_batch", "altered_answer")


def plant_round(trainer, fault: str, client: int) -> None:
    """Break the trainer's timed path in place.

    * ``state_unchanged``: every train step returns its input adapters and
      optimizer state.
    * ``half_batch``: every train step and the eval see only the first half
      of their rows (the rest masked out): the mean is over those.
    * ``altered_answer``: client ``client``'s trained update is doubled
      where it is produced, before it leaves for the server.
    """
    get_step = trainer._train_step

    def train_step():
        step = get_step()

        def broken(params, adapters, opt_state, batch):
            if fault == "half_batch":
                m = batch["loss_mask"]
                keep = (jnp.arange(m.shape[0]) < m.shape[0] // 2)[:, None]
                batch = dict(batch, loss_mask=m * keep)
            out = step(params, adapters, opt_state, batch)
            if fault == "state_unchanged":
                return adapters, opt_state, out[2]
            return out

        return broken

    if fault not in ROUND_FAULTS:
        raise ValueError(f"unknown round fault {fault!r}")
    if fault in ("state_unchanged", "half_batch"):
        trainer._train_step = train_step
    if fault == "half_batch":
        ev = trainer._eval

        def half_eval(params, adapters, batch):
            m = batch["loss_mask"]
            keep = (jnp.arange(m.shape[0]) < m.shape[0] // 2)[:, None]
            return ev(params, adapters, dict(batch, loss_mask=m * keep))

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

