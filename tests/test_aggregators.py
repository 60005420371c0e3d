"""The pluggable Aggregator API: registry, streaming lifecycle equivalence
with the legacy one-shot ``aggregate()`` shim, client-init semantics, and
the per-class cost model."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import costs as C
from repro.core.aggregation import aggregate
from repro.core.aggregators import (AggResult, Aggregator, METHODS,
                                    adapter_leaf_paths, available_aggregators,
                                    get_path, leaf_dims, make_aggregator,
                                    register_aggregator)

HOMOG = [8, 8, 8]
HETER = [4, 8, 16]


def _client_tree(rng, L, m, n, r, scale=1.0):
    return {"blocks": {0: {"attn": {"wq": {
        "A": jnp.asarray(rng.normal(size=(L, r, n)), jnp.float32),
        "B": jnp.asarray(rng.normal(size=(L, m, r)), jnp.float32),
        "scale": jnp.full((L,), scale, jnp.float32),
    }}}}}


def _make_clients(rng, ranks):
    trees = [_client_tree(rng, L=2, m=40, n=32, r=r) for r in ranks]
    weights = [0.5, 0.3, 0.2]
    return trees, weights


def _shim_kwargs(method, trees, ranks):
    kw = {"zero_padding": True}
    if method == "ffa":
        kw["A_init"] = trees[0]
    if method == "florist":
        kw["tau"] = 0.9
    return kw


def _cfg_kwargs(method, trees):
    if method == "ffa":
        return {"A_init": trees[0], "zero_padding": True}
    if method == "fedit":
        return {"zero_padding": True}
    if method == "florist":
        return {"tau": 0.9}
    return {}


def _assert_trees_equal(t1, t2):
    assert (t1 is None) == (t2 is None)
    if t1 is None:
        return
    paths1, paths2 = adapter_leaf_paths(t1), adapter_leaf_paths(t2)
    assert paths1 == paths2
    for p in paths1:
        l1, l2 = get_path(t1, p), get_path(t2, p)
        for k in ("A", "B", "scale"):
            np.testing.assert_array_equal(np.asarray(l1[k]),
                                          np.asarray(l2[k]), err_msg=str((p, k)))


def _assert_results_equal(r1: AggResult, r2: AggResult):
    assert r1.method == r2.method
    assert r1.ranks == r2.ranks
    assert r1.merge_into_base == r2.merge_into_base
    assert set(r1.spectra) == set(r2.spectra)
    for p in r1.spectra:
        for s1, s2 in zip(r1.spectra[p], r2.spectra[p]):
            np.testing.assert_array_equal(s1, s2)
    _assert_trees_equal(r1.global_adapters, r2.global_adapters)
    assert (r1.per_client is None) == (r2.per_client is None)
    if r1.per_client is not None:
        assert len(r1.per_client) == len(r2.per_client)
        for c1, c2 in zip(r1.per_client, r2.per_client):
            _assert_trees_equal(c1, c2)


class TestStreamingEquivalence:
    """Incremental add_client/finalize must match the one-shot shim
    bit-for-bit, homogeneous and heterogeneous."""

    @pytest.mark.parametrize("method", METHODS)
    @pytest.mark.parametrize("ranks", [HOMOG, HETER],
                             ids=["homogeneous", "heterogeneous"])
    def test_matches_one_shot_shim(self, rng, method, ranks):
        trees, w = _make_clients(rng, ranks)
        legacy = aggregate(method, trees, w, client_ranks=ranks,
                           **_shim_kwargs(method, trees, ranks))
        strat = make_aggregator(method, **_cfg_kwargs(method, trees))
        strat.begin_round()
        for t, wk, rk in zip(trees, w, ranks):
            strat.add_client(t, wk, rank=rk)
        streamed = strat.finalize()
        _assert_results_equal(legacy, streamed)

    @pytest.mark.parametrize("method", METHODS)
    def test_aggregator_is_reusable_across_rounds(self, rng, method):
        """begin_round must fully reset per-round state."""
        trees, w = _make_clients(rng, HETER)
        strat = make_aggregator(method, **_cfg_kwargs(method, trees))
        first = strat.aggregate(trees, w, client_ranks=HETER)
        second = strat.aggregate(trees, w, client_ranks=HETER)
        _assert_results_equal(first, second)

    def test_upload_accounting_accumulates_per_client(self, rng):
        trees, w = _make_clients(rng, HETER)
        for method in ("florist", "ffa"):
            strat = make_aggregator(method, **_cfg_kwargs(method, trees))
            strat.aggregate(trees, w, client_ranks=HETER)
            assert strat.round_upload_params == C.upload_params(method, trees)

    def test_finalize_without_clients_raises(self):
        strat = make_aggregator("florist")
        strat.begin_round()
        with pytest.raises(ValueError):
            strat.finalize()

    def test_dims_captured_from_first_client(self, rng):
        trees, w = _make_clients(rng, HOMOG)
        strat = make_aggregator("fedit")
        strat.begin_round()
        strat.add_client(trees[0], w[0])
        assert strat.dims == leaf_dims(trees[0])


class TestRegistry:
    def test_paper_methods_registered(self):
        assert set(METHODS) <= set(available_aggregators())

    def test_unknown_method_raises(self):
        with pytest.raises(ValueError):
            make_aggregator("nope")

    def test_custom_aggregator_plugs_in(self, rng):
        """A third-party method is a single registered class — no edits to
        trainer / costs / dispatcher."""

        @register_aggregator("unit-test-sum")
        class SumAggregator(Aggregator):
            def _accumulate(self, update, weight, rank):
                for path in adapter_leaf_paths(update):
                    leaf = get_path(update, path)
                    acc = self._state.setdefault(
                        path, {"A": jnp.zeros_like(leaf["A"]),
                               "B": jnp.zeros_like(leaf["B"])})
                    acc["A"] = acc["A"] + weight * leaf["A"]
                    acc["B"] = acc["B"] + weight * leaf["B"]

            def _finalize(self):
                from repro.core.aggregators import set_path
                out = {}
                ranks = {}
                for path, acc in self._state.items():
                    set_path(out, path, {"A": acc["A"], "B": acc["B"],
                                         "scale": self._ref_scales[path]})
                    ranks[path] = [acc["A"].shape[-2]] * acc["A"].shape[0]
                return AggResult(self.name, out, None, ranks, {})

            def server_flops(self, dims, client_ranks, agg_ranks=None):
                return 0

        trees, w = _make_clients(rng, HOMOG)
        agg = make_aggregator("unit-test-sum").aggregate(trees, w)
        assert agg.method == "unit-test-sum"
        assert agg.total_download_rank() > 0


class TestClientInitSemantics:
    def _a_init(self, rng, L=2, m=40, n=32, r=16):
        t = _client_tree(rng, L, m, n, r)
        leaf = get_path(t, adapter_leaf_paths(t)[0])
        leaf["B"] = jnp.zeros_like(leaf["B"])
        return t

    def test_round_one_starts_at_base(self, rng):
        a_init = self._a_init(rng)
        init = make_aggregator("florist").client_init(None, 8, a_init)
        leaf = get_path(init, adapter_leaf_paths(init)[0])
        assert leaf["A"].shape[-2] == 8
        np.testing.assert_array_equal(np.asarray(leaf["B"]), 0.0)

    def test_flora_reinits_every_round(self, rng):
        trees, w = _make_clients(rng, HOMOG)
        strat = make_aggregator("flora")
        agg = strat.aggregate(trees, w)
        init = strat.client_init(agg, 8, self._a_init(rng))
        leaf = get_path(init, adapter_leaf_paths(init)[0])
        np.testing.assert_array_equal(np.asarray(leaf["B"]), 0.0)

    def test_ffa_keeps_frozen_a(self, rng):
        a_init = self._a_init(rng)
        trees, w = _make_clients(rng, HOMOG)
        strat = make_aggregator("ffa", A_init=a_init)
        agg = strat.aggregate(trees, w)
        init = strat.client_init(agg, 8, a_init)
        got = get_path(init, adapter_leaf_paths(init)[0])["A"]
        want = get_path(a_init, adapter_leaf_paths(a_init)[0])["A"][..., :8, :]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_default_resumes_from_truncated_global(self, rng):
        trees, w = _make_clients(rng, HOMOG)
        strat = make_aggregator("florist", tau=1.0)
        agg = strat.aggregate(trees, w)
        init = strat.client_init(agg, 4, self._a_init(rng))
        leaf = get_path(init, adapter_leaf_paths(init)[0])
        assert leaf["A"].shape[-2] == 4
        g = get_path(agg.global_adapters,
                     adapter_leaf_paths(agg.global_adapters)[0])
        np.testing.assert_array_equal(np.asarray(leaf["A"]),
                                      np.asarray(g["A"][..., :4, :]))


class TestCostModelParity:
    """The registry-dispatched costs.* wrappers must match the per-class
    methods (the formulas moved, the numbers must not)."""

    def test_download_and_flops_dispatch(self, rng):
        trees, w = _make_clients(rng, HETER)
        dims = leaf_dims(trees[0])
        for method in METHODS:
            strat = make_aggregator(method, **_cfg_kwargs(method, trees))
            agg = strat.aggregate(trees, w, client_ranks=HETER)
            assert C.download_params(method, agg, dims, 3, HETER) == \
                strat.download_params(agg, dims, 3, HETER)
            assert C.server_flops(method, dims, HETER, agg.ranks) == \
                strat.server_flops(dims, HETER, agg.ranks)

    def test_ffa_half_rank_factor(self, rng):
        trees, w = _make_clients(rng, HOMOG)
        agg = make_aggregator("ffa", A_init=trees[0]).aggregate(trees, w)
        assert C.total_download_rank(agg) == agg.total_download_rank() / 2.0


class TestBatchedPipeline:
    """The batched finalize (one compiled vmapped call per bucket of
    equal-shaped leaves, one device→host transfer) must match the legacy
    per-(leaf, layer) loop."""

    def _hetero_trees(self, rng, spread=2.0):
        """Heterogeneous ranks with a per-layer energy spread so layers of
        the same leaf select different p_l."""
        trees = []
        for r in HETER:
            t = _client_tree(rng, L=3, m=40, n=32, r=r, scale=1.0)
            leaf = get_path(t, adapter_leaf_paths(t)[0])
            sc = jnp.asarray(spread ** np.arange(3), jnp.float32)
            leaf["B"] = leaf["B"] * sc[:, None, None]
            trees.append(t)
        return trees, [0.5, 0.3, 0.2]

    @pytest.mark.parametrize("svd_method", ["svd", "gram"])
    @pytest.mark.parametrize("tau,max_rank", [(0.9, 0), (0.9, 5), ("auto", 0)])
    def test_matches_loop(self, rng, svd_method, tau, max_rank):
        trees, w = self._hetero_trees(rng)
        loop = make_aggregator("florist", tau=tau, svd_method=svd_method,
                               max_rank=max_rank, pipeline="loop"
                               ).aggregate(trees, w, client_ranks=HETER)
        bat = make_aggregator("florist", tau=tau, svd_method=svd_method,
                              max_rank=max_rank
                              ).aggregate(trees, w, client_ranks=HETER)
        assert bat.ranks == loop.ranks
        for p in loop.spectra:
            for s1, s2 in zip(loop.spectra[p], bat.spectra[p]):
                np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)
        for p in adapter_leaf_paths(loop.global_adapters):
            l, b = (get_path(loop.global_adapters, p),
                    get_path(bat.global_adapters, p))
            assert l["B"].shape == b["B"].shape
            for layer in range(3):
                np.testing.assert_allclose(
                    np.asarray(l["B"][layer] @ l["A"][layer]),
                    np.asarray(b["B"][layer] @ b["A"][layer]),
                    rtol=1e-4, atol=1e-4)

    def test_layers_pick_different_ranks(self, rng):
        trees, w = self._hetero_trees(rng, spread=4.0)
        bat = make_aggregator("florist", tau=0.9).aggregate(
            trees, w, client_ranks=HETER)
        ps = next(iter(bat.ranks.values()))
        assert len(set(ps)) > 1        # the vmapped threshold is per-layer

    def test_equal_shaped_leaves_bucketed_one_call(self, rng, monkeypatch):
        """All equal-shaped leaves must go through a single compiled call."""
        import repro.core.aggregators.florist as F
        trees = []
        for r in HETER:
            t = _client_tree(rng, L=2, m=40, n=32, r=r)
            blk = t["blocks"][0]["attn"]
            blk["wk"] = {k: jnp.array(v) for k, v in blk["wq"].items()}
            trees.append(t)
        calls = []
        real = F.florist_core_batched

        def spy(*a, **kw):
            calls.append(a[0].shape)
            return real(*a, **kw)

        monkeypatch.setattr(F, "florist_core_batched", spy)
        res = make_aggregator("florist", tau=0.9).aggregate(
            trees, [0.5, 0.3, 0.2], client_ranks=HETER)
        assert len(calls) == 1                 # 2 leaves × 2 layers batched
        assert calls[0][0] == 4
        assert len(res.ranks) == 2

    def test_invalid_pipeline_rejected(self):
        with pytest.raises(ValueError):
            make_aggregator("florist", pipeline="nope")


def test_sharded_florist_max_rank_matches_host(rng):
    """Satellite regression: florist_sharded must produce the same ΔW as
    host florist under a rank cap (the padded core used to ignore it)."""
    from repro.core.distributed import ShardedFloristAggregator  # registers

    trees, w = _make_clients(rng, HETER)
    for tau, cap in ((0.95, 4), ("auto", 3)):
        host = make_aggregator("florist", tau=tau,
                               max_rank=cap).aggregate(trees, w)
        shard = make_aggregator("florist_sharded", tau=tau, svd_method="svd",
                                max_rank=cap).aggregate(trees, w)
        assert shard.ranks == host.ranks
        assert all(r <= cap for ps in shard.ranks.values() for r in ps)
        path = adapter_leaf_paths(trees[0])[0]
        h = get_path(host.global_adapters, path)
        s = get_path(shard.global_adapters, path)
        for l in range(2):
            np.testing.assert_allclose(
                np.asarray(h["B"][l] @ h["A"][l]),
                np.asarray(s["B"][l] @ s["A"][l]), rtol=1e-3, atol=1e-3)


def test_sharded_florist_backend_matches_host_deltaw(rng):
    """The registered multi-pod backend (florist_sharded) reconstructs the
    same ΔW as the host-side strategy at τ=1 on a single-device mesh."""
    from repro.core.distributed import ShardedFloristAggregator  # registers

    trees, w = _make_clients(rng, HETER)
    host = make_aggregator("florist", tau=1.0).aggregate(trees, w)
    sharded = make_aggregator("florist_sharded", tau=1.0,
                              svd_method="svd").aggregate(trees, w)
    path = adapter_leaf_paths(trees[0])[0]
    for l in range(2):
        h = get_path(host.global_adapters, path)
        s = get_path(sharded.global_adapters, path)
        np.testing.assert_allclose(
            np.asarray(h["B"][l] @ h["A"][l]),
            np.asarray(s["B"][l] @ s["A"][l]), rtol=1e-3, atol=1e-3)


class TestArrivalOrder:
    """FLoRIST stacks client blocks in descending width, whatever order the
    clients arrive in: the same ΔW, and one concatenation signature per rank
    multiset, so a new arrival order compiles nothing in the finalize."""

    RANKS = [4, 4, 8, 16, 64]
    WEIGHTS = [0.3, 0.25, 0.2, 0.15, 0.1]

    def _clients(self, seed=14):
        rng = np.random.default_rng(seed)
        trees = []
        for r in self.RANKS:
            t = _client_tree(rng, L=2, m=128, n=112, r=r)
            t["blocks"][0]["attn"]["wk"] = _client_tree(
                rng, L=2, m=32, n=112, r=r)["blocks"][0]["attn"]["wq"]
            trees.append(t)
        return trees

    def _run(self, agg, trees, order):
        agg.begin_round()
        for i in order:
            agg.add_client(trees[i], self.WEIGHTS[i], rank=self.RANKS[i])
        return agg.finalize()

    @pytest.mark.parametrize("method,kw", [
        ("florist", {"stream": "stacked"}),
        ("florist", {"stream": "delta"}),
        ("florist_sharded", {"svd_method": "svd"}),
    ], ids=["stacked", "delta", "sharded"])
    def test_result_invariant_to_arrival_order(self, method, kw):
        from repro.core.distributed import ShardedFloristAggregator  # noqa: F401 (registers)

        trees = self._clients()
        a = self._run(make_aggregator(method, tau=0.9, **kw), trees,
                      [0, 1, 2, 3, 4])
        b = self._run(make_aggregator(method, tau=0.9, **kw), trees,
                      [2, 4, 0, 3, 1])
        assert a.ranks == b.ranks
        for p in a.spectra:
            for s1, s2 in zip(a.spectra[p], b.spectra[p]):
                np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)
        for p in adapter_leaf_paths(a.global_adapters):
            la, lb = get_path(a.global_adapters, p), get_path(b.global_adapters, p)
            for layer in range(2):
                np.testing.assert_allclose(
                    np.asarray(la["B"][layer] @ la["A"][layer]),
                    np.asarray(lb["B"][layer] @ lb["A"][layer]),
                    rtol=1e-4, atol=1e-4)

    def test_new_arrival_order_compiles_nothing_in_finalize(self):
        import time

        from repro.common import telemetry

        trees = self._clients()
        agg = make_aggregator("florist", tau=0.9)
        lo = time.perf_counter()
        self._run(agg, trees, [3, 0, 4, 2, 1])
        mid = time.perf_counter()
        self._run(agg, trees, [1, 4, 2, 0, 3])
        # eager ops compile on the CPU too: every one in the second
        # finalize's core must come from the cache
        assert telemetry.compiles(mid).get("finalize.core", (0, 0.0))[0] == 0
        cores = telemetry.records("finalize.core", lo)
        assert len(cores) == 2
        w1, w2 = (s.attrs["stack_widths"] for s in cores)
        assert w1 == w2 == tuple(sorted(self.RANKS, reverse=True))
