"""Native TPU compiles of every Pallas kernel, for a described v5e chip.

Interpret mode (what the CPU tests run) never sees the chip's tiling rules,
so a kernel can pass every parity test and still be refused by the TPU
compiler.  These tests lower each kernel with ``interpret=False`` against a
described ``v5e:2x2`` topology — no chip needed, about a second each — at
the widths the registry's models run: qwen2-0.5b for the dense path,
deepseek-v3's latent for MLA decode, rwkv6-1.6b for the WKV recurrence.

They call the ``*_kernel`` functions directly: ``repro.kernels.ops`` picks
interpret mode from ``jax.default_backend()``, which is the CPU here, so
lowering ``ops.*`` would compile the interpreter's XLA ops and no kernel.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.adapter_gram import adapter_gram_kernel
from repro.kernels.bgmv import bgmv_kernel
from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.lora_matmul import lora_matmul_kernel
from repro.kernels.mla_ring_decode import mla_ring_decode_kernel
from repro.kernels.ring_decode import ring_decode_kernel
from repro.kernels.wkv6 import wkv6_kernel

QWEN = get_config("qwen2-0.5b")
MLA = get_config("deepseek-v3-671b")
RWKV = get_config("rwkv6-1.6b")

SLOTS, CHUNK, CAP = 8, 8, 512          # serving: batch slots, chunk, ring
BATCH, SEQ = 4, 512                    # training: client batch, sequence
MAX_RANK, PAGE_RANK, PAGES = 64, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compilation
    cache off (a described-device compile can be written to it but never
    read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)


def _cases():
    """name -> (fn, [(shape, dtype), ...]) at the registry's widths."""
    bf, f32, i32, i8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
    d = QWEN.d_model
    H, K, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.head_dim
    Hm, kvr, rope = MLA.num_heads, MLA.kv_lora_rank, MLA.qk_rope_head_dim
    Hr, hr = RWKV.d_model // RWKV.rwkv_head_dim, RWKV.rwkv_head_dim
    ring = [((SLOTS,), i32)] * 3
    mla_scale = (MLA.qk_nope_head_dim + rope) ** -0.5
    # the finalize's stacked B: d_out × Σ r_k for four clients of ranks 4..64
    stack = 4 + 8 + 16 + 64
    return {
        "lora_matmul": (
            lambda x, w, a, b: lora_matmul_kernel(x, w, a, b, interpret=False),
            [((BATCH * SEQ, d), bf), ((d, H * hd), bf), ((MAX_RANK, d), bf),
             ((H * hd, MAX_RANK), bf)]),
        "flash_attention": (
            lambda q, k, v: flash_attention_kernel(q, k, v, interpret=False),
            [((BATCH, SEQ, H, hd), bf), ((BATCH, SEQ, K, hd), bf),
             ((BATCH, SEQ, K, hd), bf)]),
        "adapter_gram": (
            lambda x: adapter_gram_kernel(x, interpret=False),
            [((d, stack), f32)]),
        "adapter_gram_layers": (       # vmapped over layers, as in finalize
            jax.vmap(lambda x: adapter_gram_kernel(x, interpret=False)),
            [((QWEN.num_layers, d, stack), f32)]),
        "bgmv": (
            lambda x, a, b, t, r, s: bgmv_kernel(x, a, b, t, r, s,
                                                 interpret=False),
            [((SLOTS, CHUNK, d), bf), ((PAGES, PAGE_RANK, d), bf),
             ((PAGES, H * hd, PAGE_RANK), bf),
             ((SLOTS, MAX_RANK // PAGE_RANK), i32), ((SLOTS,), i32),
             ((SLOTS,), f32)]),
        "ring_decode": (
            lambda q, k, v, p, n, c: ring_decode_kernel(
                q, k, v, p, n, c, cap=CAP, interpret=False),
            [((SLOTS, CHUNK, H, hd), bf), ((SLOTS, CAP, K, hd), bf),
             ((SLOTS, CAP, K, hd), bf)] + ring),
        "ring_decode_int8": (
            lambda q, k, v, p, n, c, ks, vs: ring_decode_kernel(
                q, k, v, p, n, c, cap=CAP, k_scale=ks, v_scale=vs,
                interpret=False),
            [((SLOTS, CHUNK, H, hd), bf), ((SLOTS, CAP, K, hd), i8),
             ((SLOTS, CAP, K, hd), i8)] + ring
            + [((SLOTS, CAP, K, 1), f32)] * 2),
        "mla_ring_decode": (
            lambda q, c, r, p, n, t: mla_ring_decode_kernel(
                q, c, r, p, n, t, cap=CAP, scale=mla_scale, interpret=False),
            [((SLOTS, CHUNK, Hm, kvr + rope), bf), ((SLOTS, CAP, kvr), bf),
             ((SLOTS, CAP, rope), bf)] + ring),
        "mla_ring_decode_int8": (
            lambda q, c, r, p, n, t, s1, s2: mla_ring_decode_kernel(
                q, c, r, p, n, t, cap=CAP, scale=mla_scale, c_kv_scale=s1,
                k_rope_scale=s2, interpret=False),
            [((SLOTS, CHUNK, Hm, kvr + rope), bf), ((SLOTS, CAP, kvr), i8),
             ((SLOTS, CAP, rope), i8)] + ring + [((SLOTS, CAP, 1), f32)] * 2),
        "wkv6": (
            lambda r, k, v, w, u: wkv6_kernel(r, k, v, w, u, interpret=False),
            [((2, SEQ, Hr, hr), bf)] * 4 + [((Hr, hr), f32)]),
    }


CASES = sorted(_cases())


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_natively(one_chip, name):
    fn, specs = _cases()[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
