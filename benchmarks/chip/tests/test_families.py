"""Model families: the dispatch on ``model_type``, the adapter-tree mapping
over every segment of the blocks, the shared LoRA init, and the tiny round
reproducing, bit for bit, what the harness made before the Qwen2 layout
moved into ``bench/families/qwen2.py``."""
import hashlib
import types

import jax
import numpy as np
import pytest

import run
from bench import model, spans as sp, traffic as tr
from bench.families import qwen2
from test_rehearsal import data


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, a in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(a)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_unknown_model_type_names_the_missing_file():
    c = dict(data("tiny"), model_type="no_such_family")
    with pytest.raises(ValueError, match=r"bench/families/no_such_family\.py"):
        model.family(c)
    with pytest.raises(ValueError, match="no_such_family"):
        model.make_weights(c, 0)


def test_qwen2_configs_use_the_qwen2_family():
    assert model.family(model.load_config("qwen2-0.5b")) is qwen2
    assert model.family(data("tiny")) is qwen2


@pytest.fixture(scope="module")
def two_segments():
    """The deepseek-v3 smoke model's parameters (a dense segment, then an
    expert one: two stacked segments of blocks) and its LoRA targets."""
    from repro.configs import get_smoke_config, lora_targets
    from repro.models import transformer as T

    cfg = get_smoke_config("deepseek-v3-671b")
    params = jax.eval_shape(lambda: T.init(cfg, jax.random.PRNGKey(0)))
    assert len(params["blocks"]) == 2
    return params, lora_targets(cfg)


#: a family that keys the reference's factors by the whole path
BY_PATH = types.SimpleNamespace(lora_key=tuple, lora_path=tuple)


def assert_same_tree(a, b):
    pa, ta = jax.tree_util.tree_flatten_with_path(a)
    pb, tb = jax.tree_util.tree_flatten_with_path(b)
    assert ta == tb
    for (path, x), (_, y) in zip(pa, pb):
        assert x is y, jax.tree_util.keystr(path)


def test_lora_tree_and_factors_round_trip_on_two_segments(two_segments):
    from repro.peft.lora import init_lora

    params, targets = two_segments
    tree = init_lora(params, targets, 4, 4.0, jax.random.PRNGKey(1))
    factors = model.lora_factors(BY_PATH, tree)
    assert {p[1] for p in factors} == {0, 1}
    assert len(factors) == 2 * len(targets)
    assert_same_tree(model.lora_tree(BY_PATH, factors), tree)
    # the Qwen2 mapping's bare names would fold the segments into one
    with pytest.raises(ValueError, match="Qwen2"):
        model.lora_factors(qwen2, tree)


def test_shared_init_covers_every_segment(two_segments):
    from repro.peft.lora import init_lora

    params, targets = two_segments
    key = jax.random.PRNGKey(3)
    got = model.lora_init(params, targets, 8, key)
    want = jax.eval_shape(lambda: init_lora(params, targets, 8, 8.0, key))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert x.shape == y.shape and x.dtype == np.float32, path
    for A, B, s in model.adapter_leaves(got).values():
        assert float(np.abs(np.asarray(B)).max()) == 0.0
        assert np.all(np.asarray(s) == 1.0)
        assert 0.015 < float(np.std(np.asarray(A))) < 0.025
    assert_same_tree(model.lora_tree(BY_PATH, model.lora_factors(BY_PATH, got)),
                     got)


def test_qwen2_mapping_round_trips():
    c, t = data("tiny"), data("tiny_round")
    tree = model.lora_init(jax.eval_shape(lambda: model.make_weights(c, 0)),
                           t["targets"], 4, jax.random.PRNGKey(0))
    factors = model.lora_factors(qwen2, tree)
    assert list(factors) == t["targets"]
    assert_same_tree(model.lora_tree(qwen2, factors), tree)


def test_round_reproduces_the_pinned_readings():
    """Weights, init, traffic, batches and every check reading of the tiny
    round equal those recorded before the refactor."""
    pin = data("pinned_round")
    c, t = data("tiny"), data("tiny_round")
    seed = pin["seed"]
    driver = run.load_module("drivers", "round")
    cell = driver.setup(c, t, seed, sp.Spans(), 1.0, jax.devices()[:1])
    ks = cell.checked_clients()
    assert ks == pin["checked_clients"]
    assert digest(cell.weights) == pin["weights"]
    assert digest(cell.trainer.A_init_full) == pin["init"]
    assert digest(tr.client_corpus(t, c["vocab_size"], seed)) == pin["traffic"]
    assert digest({str(k): cell.batches(k) for k in ks}) == pin["batches"]
    cell.release()
    assert cell.check() == pin["readings"]
