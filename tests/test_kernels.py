"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _arr(rng, shape, dtype, scale=1.0):
    x = rng.normal(size=shape) * scale
    return jnp.asarray(x, dtype)


class TestLoraMatmul:
    @pytest.mark.parametrize("M,din,dout,r", [
        (64, 64, 64, 4), (128, 192, 160, 8), (100, 96, 224, 16), (256, 128, 128, 32),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, rng, M, din, dout, r, dtype):
        x = _arr(rng, (M, din), dtype)
        w = _arr(rng, (din, dout), dtype, 0.1)
        a = _arr(rng, (r, din), dtype, 0.1)
        b = _arr(rng, (dout, r), dtype, 0.1)
        y = ops.lora_matmul(x, w, a, b, 0.5, bm=64, bn=64)
        yr = ref.lora_matmul_ref(x, w, a, b, jnp.asarray(0.5, dtype))
        tol = 2e-5 if dtype == jnp.float32 else 3e-2
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(yr, np.float32),
                                   rtol=tol, atol=tol * 10)

    def test_batched_input(self, rng):
        x = _arr(rng, (2, 50, 64), jnp.float32)
        w = _arr(rng, (64, 96), jnp.float32, 0.1)
        a = _arr(rng, (4, 64), jnp.float32, 0.1)
        b = _arr(rng, (96, 4), jnp.float32, 0.1)
        y = ops.lora_matmul(x, w, a, b, 2.0, bm=32, bn=32)
        yr = ref.lora_matmul_ref(x.reshape(-1, 64), w, a, b, 2.0).reshape(2, 50, 96)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), rtol=1e-5, atol=1e-4)

    def test_grad_matches_ref(self, rng):
        """custom_vjp: kernel forward, reference-math backward — gradients
        w.r.t. every operand (including scale) match the pure-jnp path."""
        x = _arr(rng, (50, 64), jnp.float32)
        w = _arr(rng, (64, 96), jnp.float32, 0.1)
        a = _arr(rng, (8, 64), jnp.float32, 0.1)
        b = _arr(rng, (96, 8), jnp.float32, 0.1)
        sc = jnp.asarray(0.5)
        co = _arr(rng, (50, 96), jnp.float32)     # non-trivial cotangent
        gk = jax.grad(lambda *t: (ops.lora_matmul(*t, bm=32, bn=32) * co).sum(),
                      argnums=(0, 1, 2, 3, 4))(x, w, a, b, sc)
        gr = jax.grad(lambda *t: (ref.lora_matmul_ref(*t) * co).sum(),
                      argnums=(0, 1, 2, 3, 4))(x, w, a, b, sc)
        for i, (p, q) in enumerate(zip(gk, gr)):
            np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                       rtol=1e-5, atol=1e-4, err_msg=f"arg{i}")

    def test_train_step_grad_parity(self, rng):
        """A full LoRA train step with the fused kernel routed through
        ``lora_proj`` produces the same adapter update as the reference
        path — ``use_kernels=True`` training differentiates correctly."""
        from repro.configs import get_smoke_config, lora_targets
        from repro.models import transformer as T
        from repro.peft import lora
        from repro.peft.lora import init_lora
        from repro.common.config import OptimConfig
        from repro.optim.adamw import adamw_init
        from repro.train.step import make_train_step

        cfg = get_smoke_config("qwen2-0.5b")
        params = T.init(cfg, jax.random.PRNGKey(0))
        adapters = init_lora(params, lora_targets(cfg), 4, 8.0,
                             jax.random.PRNGKey(1))
        batch = {"tokens": jnp.asarray(rng.integers(1, cfg.vocab_size,
                                                    (2, 32)))}
        step = make_train_step(cfg, OptimConfig(lr=1e-2), remat=False)
        outs = {}
        for use_kernel in (False, True):
            old = lora.USE_KERNEL
            lora.USE_KERNEL = use_kernel
            try:
                new_a, _, m = step(params, adapters, adamw_init(adapters),
                                   batch)
            finally:
                lora.USE_KERNEL = old
            outs[use_kernel] = (new_a, float(m["loss"]))
        assert outs[True][1] == pytest.approx(outs[False][1], rel=1e-5)
        for p, q in zip(jax.tree.leaves(outs[True][0]),
                        jax.tree.leaves(outs[False][0])):
            np.testing.assert_allclose(np.asarray(p), np.asarray(q),
                                       rtol=1e-4, atol=1e-5)


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,K,hd", [
        (128, 4, 4, 32),     # MHA
        (256, 8, 2, 64),     # GQA 4x
        (128, 8, 1, 32),     # MQA
    ])
    def test_causal(self, rng, S, H, K, hd):
        q = _arr(rng, (2, S, H, hd), jnp.float32)
        k = _arr(rng, (2, S, K, hd), jnp.float32)
        v = _arr(rng, (2, S, K, hd), jnp.float32)
        o = ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
        orf = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("window", [32, 96, 128])
    def test_sliding_window(self, rng, window):
        q = _arr(rng, (1, 256, 4, 32), jnp.float32)
        k = _arr(rng, (1, 256, 4, 32), jnp.float32)
        v = _arr(rng, (1, 256, 4, 32), jnp.float32)
        o = ops.flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
        orf = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   rtol=1e-5, atol=1e-5)

    def test_bf16(self, rng):
        q = _arr(rng, (1, 128, 4, 64), jnp.bfloat16)
        k = _arr(rng, (1, 128, 2, 64), jnp.bfloat16)
        v = _arr(rng, (1, 128, 2, 64), jnp.bfloat16)
        o = ops.flash_attention(q, k, v, bq=64, bk=64)
        orf = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(np.asarray(o, np.float32),
                                   np.asarray(orf, np.float32),
                                   rtol=3e-2, atol=3e-2)

    @pytest.mark.parametrize("S,window", [(100, 0), (300, 0), (300, 50)])
    def test_odd_lengths_run_kernel_not_fallback(self, rng, monkeypatch,
                                                 S, window):
        """S/T not block multiples: the wrapper pads to block multiples and
        masks the padded KV columns in-kernel — the KERNEL runs (the old
        silent reference fallback is gone; a poisoned ref proves it)."""
        q = _arr(rng, (2, S, 4, 32), jnp.float32)
        k = _arr(rng, (2, S, 2, 32), jnp.float32)
        v = _arr(rng, (2, S, 2, 32), jnp.float32)
        orf = ref.flash_attention_ref(q, k, v, causal=True, window=window)

        def boom(*a, **kw):
            raise AssertionError("fell back to the reference path")
        monkeypatch.setattr(ops.ref, "flash_attention_ref", boom)
        o = ops.flash_attention(q, k, v, causal=True, window=window,
                                bq=128, bk=128)
        assert o.shape == q.shape
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_flows_through_kernel(self, rng):
        """custom_vjp (reference-math backward) lets use_kernels training
        differentiate through the attention kernel."""
        q = _arr(rng, (1, 64, 4, 16), jnp.float32)
        k = _arr(rng, (1, 64, 2, 16), jnp.float32)
        v = _arr(rng, (1, 64, 2, 16), jnp.float32)
        gk = jax.grad(lambda q_: ops.flash_attention(q_, k, v, bq=32,
                                                     bk=32).sum())(q)
        gr = jax.grad(lambda q_: ref.flash_attention_ref(q_, k, v).sum())(q)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=1e-5, atol=1e-5)


class TestWkv6:
    @pytest.mark.parametrize("S,H,hd,chunk", [
        (64, 2, 16, 32), (128, 4, 32, 64), (96, 1, 16, 32),
    ])
    def test_matches_scan(self, rng, S, H, hd, chunk):
        r = _arr(rng, (2, S, H, hd), jnp.float32)
        k = _arr(rng, (2, S, H, hd), jnp.float32)
        v = _arr(rng, (2, S, H, hd), jnp.float32)
        w = -jnp.exp(_arr(rng, (2, S, H, hd), jnp.float32))
        u = _arr(rng, (H, hd), jnp.float32)
        y = ops.wkv6(r, k, v, w, u, chunk=chunk)
        yr = ref.wkv6_ref(r, k, v, w, u)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)

    def test_state_persists_across_chunks(self, rng):
        """Chunked and unchunked must agree exactly — the VMEM state scratch
        carries across sequential grid steps."""
        args = [_arr(rng, (1, 64, 2, 16), jnp.float32) for _ in range(3)]
        w = -jnp.exp(_arr(rng, (1, 64, 2, 16), jnp.float32))
        u = _arr(rng, (2, 16), jnp.float32)
        y1 = ops.wkv6(*args, w, u, chunk=64)
        y2 = ops.wkv6(*args, w, u, chunk=16)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                                   rtol=1e-6, atol=1e-6)


class TestAdapterGram:
    @pytest.mark.parametrize("m,r", [(256, 16), (1000, 48), (512, 160)])
    def test_matches_ref(self, rng, m, r):
        x = _arr(rng, (m, r), jnp.float32)
        g = ops.adapter_gram(x, bm=128)
        gr = ref.adapter_gram_ref(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("m", [100, 129, 257])
    def test_tail_panel_masked(self, rng, m):
        """m not a multiple of bm: the kernel masks the tail panel instead
        of requiring a host-side padding copy."""
        x = _arr(rng, (m, 24), jnp.float32)
        g = ops.adapter_gram(x, bm=128)
        gr = ref.adapter_gram_ref(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=1e-4, atol=1e-3)

    def test_bf16_input_fp32_accum(self, rng):
        x = _arr(rng, (512, 32), jnp.bfloat16)
        g = ops.adapter_gram(x, bm=128)
        assert g.dtype == jnp.float32
        gr = ref.adapter_gram_ref(x)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gr),
                                   rtol=2e-2, atol=2e-1)


class TestFlashJax:
    """The XLA-flash lowering path used by every dry-run."""

    def test_matches_ref_gqa(self, rng):
        from repro.models.attention_core import flash_jax
        q = _arr(rng, (2, 256, 8, 32), jnp.float32)
        k = _arr(rng, (2, 256, 2, 32), jnp.float32)
        v = _arr(rng, (2, 256, 2, 32), jnp.float32)
        o = flash_jax(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
        orf = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(orf),
                                   rtol=1e-5, atol=1e-5)

    def test_grad_flows(self, rng):
        from repro.models.attention_core import flash_jax
        q = _arr(rng, (1, 64, 2, 16), jnp.float32)
        k = _arr(rng, (1, 64, 2, 16), jnp.float32)
        v = _arr(rng, (1, 64, 2, 16), jnp.float32)
        g = jax.grad(lambda q_: flash_jax(q_, k, v, q_chunk=32, kv_chunk=32).sum())(q)
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0


class TestBgmv:
    def test_matches_ref(self, rng):
        """Per-row paged gather at heterogeneous ranks (incl. the rank-0
        base id and an adapter spanning several pages)."""
        B, C, din, dout, P, pr = 5, 3, 32, 24, 9, 4
        x = _arr(rng, (B, C, din), jnp.float32)
        a_pages = _arr(rng, (P, pr, din), jnp.float32)
        b_pages = _arr(rng, (P, dout, pr), jnp.float32)
        table = jnp.asarray([[0, 0, 0], [1, 0, 0], [2, 3, 4], [5, 6, 0]],
                            jnp.int32)
        rank = jnp.asarray([0, 3, 11, 8], jnp.int32)
        scale = jnp.asarray([0.0, 2.0, 0.5, 1.5], jnp.float32)
        ids = jnp.asarray([2, 0, 1, 3, 2], jnp.int32)
        y = ops.bgmv(x, a_pages, b_pages, table, rank, scale, ids)
        yr = ref.bgmv_ref(x, a_pages, b_pages, table, rank, scale, ids)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=1e-5, atol=1e-5)
        assert not np.asarray(y[1]).any()          # base id: exact zero
