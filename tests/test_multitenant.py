"""Multi-tenant adapter serving: registry paging, per-row adapters through
the jitted hot loop, bgmv kernel parity, and the zero-retrace / hot-swap /
bit-identity invariants of ``repro.serve.adapters``."""
import jax
import jax.extend.core as jcore
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config, lora_targets
from repro.models import transformer as T
from repro.peft.lora import PagedLoRA, init_lora, lora_proj, paged_lora_delta
from repro.serve.adapters import AdapterRegistry, attach, is_device_state
from repro.serve.engine import SamplingParams, ServeEngine, _build_engine_step

ARCH = "qwen2-0.5b"
REG_KW = dict(page_rank=4, num_pages=64, max_adapters=16, max_rank=8)


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config(ARCH)
    key = jax.random.PRNGKey(0)
    params = T.init(cfg, key)
    template = init_lora(params, lora_targets(cfg), 4, 8.0, key)
    return cfg, params, template


def _rand_adapter(cfg, params, rank, seed, alpha=8.0):
    """init_lora shape with non-zero B so the adapter changes outputs."""
    k = jax.random.PRNGKey(seed)
    ad = init_lora(params, lora_targets(cfg), rank, alpha, k)

    def fix(path, leaf):
        if getattr(path[-1], "key", None) == "B":
            kk = jax.random.fold_in(k, abs(hash(str(path))) % 2**30)
            return jax.random.normal(kk, leaf.shape) * 0.05
        return leaf

    return jax.tree_util.tree_map_with_path(fix, ad)


def _registry(template):
    return AdapterRegistry(template, **REG_KW)


def _engine(cfg, params, reg, **kw):
    kw.setdefault("batch_slots", 4)
    kw.setdefault("capacity", 64)
    return ServeEngine(cfg, params, registry=reg, seed=0, **kw)


def _count_dots(jaxpr):
    """dot_general count, recursive through scan/cond/pjit sub-jaxprs."""
    n = 0
    for eq in jaxpr.eqns:
        if eq.primitive.name == "dot_general":
            n += 1
        for v in eq.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for s in vs:
                if isinstance(s, jcore.ClosedJaxpr):
                    n += _count_dots(s.jaxpr)
                elif isinstance(s, jcore.Jaxpr):
                    n += _count_dots(s)
    return n


class TestRegistry:
    def test_register_assigns_pages_and_ids(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        i2 = reg.register("b", _rand_adapter(cfg, params, 7, 2))
        assert (i1, i2) == (1, 2)           # id 0 reserved for base
        assert reg.metadata(i1)["rank"] == 4 and len(reg.metadata(i1)["pages"]) == 1
        assert reg.metadata(i2)["rank"] == 7 and len(reg.metadata(i2)["pages"]) == 2
        assert reg.num_free_pages == REG_KW["num_pages"] - 3
        assert reg.is_live(0) and reg.is_live(i1) and not reg.is_live(99)

    def test_register_evict_register_is_deterministic(self, setup):
        """Page/id reuse after evict is exact: same id, same pages, same
        device pool bytes."""
        cfg, params, template = setup
        reg = _registry(template)
        reg.register("keep", _rand_adapter(cfg, params, 4, 1))
        ad = _rand_adapter(cfg, params, 7, 2)
        i_a = reg.register("x", ad)
        pages_a = reg.metadata(i_a)["pages"]
        pools_a = jax.device_get(reg.device_state["pools"])
        table_a = np.asarray(reg.device_state["table"])
        reg.evict("x")
        assert not reg.is_live(i_a)
        i_b = reg.register("x", ad)
        assert i_b == i_a
        assert reg.metadata(i_b)["pages"] == pages_a
        np.testing.assert_array_equal(np.asarray(reg.device_state["table"]),
                                      table_a)
        for la, lb in zip(jax.tree_util.tree_leaves(pools_a),
                          jax.tree_util.tree_leaves(
                              jax.device_get(reg.device_state["pools"]))):
            np.testing.assert_array_equal(la, lb)

    def test_capacity_and_validation_errors(self, setup):
        cfg, params, template = setup
        reg = AdapterRegistry(template, page_rank=4, num_pages=2,
                              max_adapters=4, max_rank=8)
        with pytest.raises(ValueError, match="max_rank"):
            reg.register("big", _rand_adapter(cfg, params, 9, 1))
        reg.register("a", _rand_adapter(cfg, params, 8, 1))    # 2 pages
        with pytest.raises(RuntimeError, match="out of adapter pages"):
            reg.register("b", _rand_adapter(cfg, params, 4, 2))
        with pytest.raises(ValueError, match="already registered"):
            reg.register("a", _rand_adapter(cfg, params, 4, 3))
        with pytest.raises(KeyError):
            reg.swap("nope", _rand_adapter(cfg, params, 4, 4))
        with pytest.raises(ValueError, match="structure"):
            bad = {"not": {"the": {"template": {
                "A": jnp.zeros((4, 8)), "B": jnp.zeros((8, 4)),
                "scale": jnp.float32(1.0)}}}}
            _registry(template).register("bad", bad)

    def test_swap_is_atomic_version_bump(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i_old = reg.register("svc", _rand_adapter(cfg, params, 4, 1))
        i_new = reg.swap("svc", _rand_adapter(cfg, params, 6, 2))
        assert i_new != i_old
        assert reg.resolve("svc") == i_new
        # the old version keeps serving in-flight rows until evicted
        assert reg.is_live(i_old) and reg.metadata(i_old)["retired"]
        assert reg.metadata(i_new)["version"] == 2
        reg.evict(i_old)
        assert not reg.is_live(i_old) and reg.is_live(i_new)

    def test_attach_builds_paged_leaves(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        assert is_device_state(reg.device_state)
        tree = attach(reg.device_state, jnp.asarray([i1, 0], jnp.int32))
        leaves = [l for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, PagedLoRA))
            if isinstance(l, PagedLoRA)]
        assert leaves, "attach produced no PagedLoRA leaves"
        # stacked leaves carry the broadcast layer axis on every child
        for l in leaves:
            if l.a_pages.ndim == 4:
                L = l.a_pages.shape[0]
                assert l.table.shape[0] == L and l.ids.shape == (L, 2)


def _first_paged_leaf(tree):
    """First PagedLoRA of an attached tree, layer-0 slice if stacked."""
    for l in jax.tree_util.tree_leaves(
            tree, is_leaf=lambda x: isinstance(x, PagedLoRA)):
        if isinstance(l, PagedLoRA):
            return (jax.tree_util.tree_map(lambda p: p[0], l)
                    if l.a_pages.ndim == 4 else l)
    raise AssertionError("attach produced no PagedLoRA leaves")


class TestPagedMath:
    def test_paged_xla_rows_independent_and_rank_masked(self, setup):
        """Row math is row-local: a row's delta is bitwise invariant to what
        the other rows' adapters are, and a base (id-0) row's delta is an
        exact zero."""
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        i2 = reg.register("b", _rand_adapter(cfg, params, 7, 2))
        rng = np.random.default_rng(0)
        paged = _first_paged_leaf(
            attach(reg.device_state, jnp.asarray([i1, i2, 0], jnp.int32)))
        x = jnp.asarray(rng.normal(size=(3, 1, paged.a_pages.shape[-1])),
                        jnp.float32)
        d = paged_lora_delta(x, paged)
        assert (np.asarray(d[2]) == 0).all()          # base row: exact zero
        # permuting OTHER rows' ids leaves row 0 bitwise unchanged
        paged2 = _first_paged_leaf(
            attach(reg.device_state, jnp.asarray([i1, 0, i2], jnp.int32)))
        d2 = paged_lora_delta(x, paged2)
        np.testing.assert_array_equal(np.asarray(d[0]), np.asarray(d2[0]))

    def test_bgmv_kernel_matches_xla_twin(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        i2 = reg.register("b", _rand_adapter(cfg, params, 7, 2))
        ids = jnp.asarray([i1, i2, 0, i2], jnp.int32)
        rng = np.random.default_rng(1)
        lx = _first_paged_leaf(attach(reg.device_state, ids, impl="xla"))
        lk = _first_paged_leaf(attach(reg.device_state, ids, impl="kernel"))
        assert lx.impl == "xla" and lk.impl == "kernel"
        x = jnp.asarray(rng.normal(size=(4, 2, lx.a_pages.shape[-1])),
                        jnp.float32)
        dx = paged_lora_delta(x, lx)
        dk = paged_lora_delta(x, lk)
        np.testing.assert_allclose(np.asarray(dx), np.asarray(dk),
                                   atol=1e-4, rtol=1e-4)
        assert (np.asarray(dk[2]) == 0).all()         # base row exact zero


class TestEngine:
    def test_multi_matches_solo_engines_heterogeneous_ranks(self, setup):
        """One engine, >=8 live adapters with mixed ranks in one continuous
        batch: every request's tokens are identical to a solo engine serving
        only that adapter (both through the paged path, so the comparison is
        of bit-identical programs)."""
        cfg, params, template = setup
        reg = _registry(template)
        ranks = [4, 7, 3, 8, 5, 2, 6, 4]
        ads = {f"t{j}": _rand_adapter(cfg, params, r, 10 + j)
               for j, r in enumerate(ranks)}
        ids = {n: reg.register(n, a) for n, a in ads.items()}
        assert len(reg.live_ids) >= 8

        gp = SamplingParams(max_tokens=4)
        prompts = {n: [3 + j, 17 + j] for j, n in enumerate(ads)}
        eng = _engine(cfg, params, reg)
        uids = {n: eng.submit(prompts[n], gp, adapter_id=ids[n]) for n in ads}
        ub = eng.submit([29, 31], gp)                  # base row rides along
        multi = eng.run()

        for n in ads:
            solo_reg = _registry(template)
            aid = solo_reg.register(n, ads[n])
            solo = _engine(cfg, params, solo_reg)
            su = solo.submit(prompts[n], gp, adapter_id=aid)
            assert solo.run()[su] == multi[uids[n]], f"row for {n} diverged"
        base = ServeEngine(cfg, params, batch_slots=4, capacity=64, seed=0)
        bu = base.submit([29, 31], gp)
        assert base.run()[bu] == multi[ub]

    def test_zero_retraces_under_churn(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        eng = _engine(cfg, params, reg, batch_slots=2)
        gp = SamplingParams(max_tokens=4)
        eng.submit([5, 6, 7], gp, adapter_id=i1)
        eng.run()
        baseline = dict(eng.trace_counts)
        assert baseline, "trace counter never fired"
        for s in range(5):
            reg.register(f"x{s}", _rand_adapter(cfg, params, 3 + s % 5, 20 + s))
        reg.swap("x0", _rand_adapter(cfg, params, 6, 30))
        reg.evict("x1")
        eng.submit([5, 6, 7], gp, adapter_id=reg.resolve("x2"))
        eng.run()
        assert dict(eng.trace_counts) == baseline, (
            f"adapter churn retraced: {baseline} -> {dict(eng.trace_counts)}")

    def test_hot_swap_mid_flight_leaves_tokens_unchanged(self, setup):
        cfg, params, template = setup

        def serve(do_swap):
            reg = _registry(template)
            i_old = reg.register("svc", _rand_adapter(cfg, params, 4, 42))
            eng = _engine(cfg, params, reg, batch_slots=2)
            uid = eng.submit([9, 10, 11], SamplingParams(max_tokens=10),
                             adapter_id=i_old)
            assert not eng.run_steps(4)               # still in flight
            if do_swap:
                i_new = reg.swap("svc", _rand_adapter(cfg, params, 6, 43))
                eng.submit([1, 2], SamplingParams(max_tokens=3),
                           adapter_id=i_new)          # new version serves too
            return eng.run()[uid]

        assert serve(False) == serve(True)

    def test_submit_validates_adapter_id(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        eng = _engine(cfg, params, reg)
        with pytest.raises(KeyError, match="unknown or evicted"):
            eng.submit([1], adapter_id=7)
        reg.evict(i1)
        with pytest.raises(KeyError, match="unknown or evicted"):
            eng.submit([1], adapter_id=i1)
        no_reg = ServeEngine(cfg, params, batch_slots=2, capacity=64)
        with pytest.raises(ValueError, match="requires an engine"):
            no_reg.submit([1], adapter_id=1)
        with pytest.raises(ValueError, match="not both"):
            ServeEngine(cfg, params, adapters=template, registry=reg,
                        batch_slots=2, capacity=64)

    def test_reset_slot_clears_adapter_entry(self, setup):
        cfg, params, template = setup
        reg = _registry(template)
        i1 = reg.register("a", _rand_adapter(cfg, params, 4, 1))
        eng = _engine(cfg, params, reg, batch_slots=2)
        eng.submit([5, 6], SamplingParams(max_tokens=8), adapter_id=i1)
        eng.run_steps(2)
        assert int(eng._state["adapter_ids"][0]) == i1
        eng.reset_slot(0)
        assert int(eng._state["adapter_ids"][0]) == 0
        assert eng.slots[0] is None
        assert not bool(eng._state["active"][0])
        # cache row wiped alongside (length leaves may carry a layer axis)
        assert (np.asarray(eng.cache[0]["length"])[..., 0] == 0).all()
        with pytest.raises(ValueError, match="not occupied"):
            eng.reset_slot(0)


class TestBaseOnlyPath:
    def test_base_only_step_compiles_no_lora_dots(self, setup):
        """adapters=None must not pay ANY adapter math: the compiled step
        contains no ``lora_delta``-scoped ops, and its jaxpr has strictly
        fewer dots than the single-tenant adapter step."""
        cfg, params, template = setup
        eng = ServeEngine(cfg, params, batch_slots=2, capacity=32)
        step = _build_engine_step(cfg, 1, False)
        hlo_none = jax.jit(step).lower(
            params, None, eng.cache, eng._state).compile().as_text()
        assert "lora_delta" not in hlo_none
        hlo_ad = jax.jit(step).lower(
            params, template, eng.cache, eng._state).compile().as_text()
        assert "lora_delta" in hlo_ad                 # marker is detectable
        dots_none = _count_dots(jax.make_jaxpr(step)(
            params, None, eng.cache, eng._state).jaxpr)
        dots_ad = _count_dots(jax.make_jaxpr(step)(
            params, template, eng.cache, eng._state).jaxpr)
        assert dots_none < dots_ad
