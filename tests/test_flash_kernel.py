"""Pallas flash-attention kernel vs the dense SDPA oracle (interpret mode),
swept over GQA ratios, chunking, masks and softcap."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import attention as A


def _ref(q, k, v, window, causal, softcap):
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                      n_heads=q.shape[2], n_kv_heads=k.shape[2], d_ff=128,
                      vocab_size=64, attn_logit_softcap=softcap,
                      dtype="float32")
    s = q.shape[1]
    mask = A.causal_mask(s, s, window) if causal else \
        jnp.ones((1, 1, 1, s, s), bool)
    return A._sdpa(cfg, q, k, v, mask).reshape(*q.shape)


@pytest.mark.parametrize("h,kh", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("window,causal,softcap", [
    (0, True, 0.0), (32, True, 0.0), (0, False, 0.0), (0, True, 50.0),
])
def test_flash_kernel_sweep(h, kh, window, causal, softcap):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, hd = 2, 128, 16
    q = jax.random.normal(ks[0], (b, s, h, hd))
    k = jax.random.normal(ks[1], (b, s, kh, hd))
    v = jax.random.normal(ks[2], (b, s, kh, hd))
    out = ops.flash_attention_op(q, k, v, causal=causal, window=window,
                                 softcap=softcap, cq=32, ck=32,
                                 impl="interpret")
    r = _ref(q, k, v, window, causal, softcap)
    assert jnp.allclose(out, r, atol=1e-4), (h, kh, window, causal, softcap)


def test_flash_kernel_uneven_chunks():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 96, 2, 32))
    k = jax.random.normal(ks[1], (1, 96, 2, 32))
    v = jax.random.normal(ks[2], (1, 96, 2, 32))
    out = ops.flash_attention_op(q, k, v, cq=32, ck=16, impl="interpret")
    r = _ref(q, k, v, 0, True, 0.0)
    assert jnp.allclose(out, r, atol=1e-4)


def test_flash_kernel_bf16():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 16), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 64, 2, 16), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 64, 2, 16), jnp.bfloat16)
    out = ops.flash_attention_op(q, k, v, cq=32, ck=32, impl="interpret")
    r = _ref(q, k, v, 0, True, 0.0)
    assert jnp.allclose(out.astype(jnp.float32), r.astype(jnp.float32),
                        atol=3e-2)
