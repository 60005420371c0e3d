"""A configuration file to its model family, the base weights and LoRA
init made by the benchmark from the seed, and the walk over the program's
adapter trees.

The family is ``bench/families/<model_type>.py`` (see
``bench/families/__init__.py``); it knows the block layout, and this module
knows none.  Weights are made on the device in one jitted call, in the type
they are served in; the plain reference reads the same arrays, so the
reference never takes weights the program made.
"""
from __future__ import annotations

import functools
import importlib
import json
import os
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def family(c: dict):
    """The family module named by the configuration's ``model_type``."""
    name = str(c.get("model_type", ""))
    path = os.path.join(HERE, "bench", "families", name + ".py")
    if not name.isidentifier() or not os.path.isfile(path):
        raise ValueError(f"model_type {name!r} of {c.get('name')!r} has no "
                         f"family module: {path} does not exist")
    return importlib.import_module("bench.families." + name)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size (the driver's exceed 32 bits)."""
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def config_items(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _weights_fn(c_items):
    c = dict(c_items)
    return jax.jit(functools.partial(family(c).weights, c))


def make_weights(c: dict, seed: int) -> Dict:
    """Base weights from the seed, on the device, in one jitted call."""
    return _weights_fn(config_items(c))(jax.random.fold_in(seed_key(seed), 1))


# -- adapter trees ---------------------------------------------------------------


def adapter_leaves(tree: Dict) -> Dict[Tuple, Tuple]:
    """{path: (A, B, scale)} of every adapter in a program adapter tree (a
    node with an ``A``), in the tree's order; the path is the keys from the
    root, segment indices included."""
    out = {}

    def walk(node, path):
        if "A" in node:
            out[path] = (node["A"], node["B"], node["scale"])
            return
        for k, v in node.items():
            walk(v, path + (k,))

    walk(tree, ())
    return out


def adapter_tree(leaves: Dict[Tuple, Tuple]) -> Dict:
    """Inverse of :func:`adapter_leaves`."""
    tree: Dict = {}
    for path, (A, B, s) in leaves.items():
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = {"A": A, "B": B, "scale": s}
    return tree


def lora_init(weights: Dict, targets: Sequence[str], rank: int,
              key: jax.Array) -> Dict:
    """A LoRA init over every target projection of every segment of
    ``weights["blocks"]`` (stacked leaves ``(L, d_in, d_out)``), as a program
    adapter tree: A Gaussian (std 0.02), B zero, scale 1.  One key is split
    over the leaves in order of segment, then of ``targets``."""
    found = []
    for s, seg in enumerate(weights["blocks"]):
        paths = jax.tree_util.tree_flatten_with_path(seg)[0]
        for t in targets:
            for p, w in paths:
                keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                             for k in p)
                if keys[-1] == t and w.ndim == 3:
                    found.append((("blocks", s) + keys, w.shape))
    ks = jax.random.split(key, len(found))
    return adapter_tree({
        path: (jax.random.normal(k, (L, rank, din), jnp.float32) * 0.02,
               jnp.zeros((L, dout, rank), jnp.float32),
               jnp.ones((L,), jnp.float32))
        for (path, (L, din, dout)), k in zip(found, ks)})


def lora_factors(fam, tree: Dict) -> Dict:
    """{key: (A, B, scale)} of a program adapter tree, keyed by the family's
    ``lora_key``."""
    out = {}
    for path, f in adapter_leaves(tree).items():
        key = fam.lora_key(path)
        if key in out:
            raise ValueError(f"adapters at two paths have the key {key!r}")
        out[key] = f
    return out


def lora_tree(fam, factors: Dict) -> Dict:
    """Inverse of :func:`lora_factors`."""
    return adapter_tree({fam.lora_path(k): f for k, f in factors.items()})
