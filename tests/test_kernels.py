"""Per-Pallas-kernel validation: shape/dtype sweeps + hypothesis against the
ref.py pure-jnp oracles (interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


class TestDotInteraction:
    @pytest.mark.parametrize("b,f,s", [(64, 27, 64), (128, 8, 16),
                                       (32, 24, 128), (256, 4, 32)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, b, f, s, dtype):
        z = jax.random.normal(jax.random.PRNGKey(0), (b, f, s), dtype)
        out = ops.dot_interaction_op(z, batch_tile=min(64, b),
                                     impl="interpret")
        r = ref.dot_interaction_ref(z)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        assert out.shape == (b, f * (f - 1) // 2)
        assert jnp.allclose(out.astype(jnp.float32),
                            r.astype(jnp.float32), atol=tol, rtol=tol)

    @pytest.mark.parametrize("b", [100, 37, 1])
    def test_partial_batch_tile_is_padded_internally(self, b):
        # b % batch_tile != 0 used to hard-assert; the tail tile is now
        # padded internally (mirroring the embedding-bag kernels) so odd
        # serving batch sizes run through the dense stage
        z = jax.random.normal(jax.random.PRNGKey(3), (b, 4, 8))
        out = ops.dot_interaction_op(z, batch_tile=64, impl="interpret")
        r = ref.dot_interaction_ref(z)
        assert out.shape == r.shape
        assert jnp.allclose(out, r, atol=1e-4)


class TestEmbeddingBag:
    @pytest.mark.parametrize("r,s,b,hot", [(500, 64, 64, 4), (1000, 32, 128, 1),
                                           (64, 128, 32, 8), (2048, 16, 64, 100)])
    def test_sweep(self, r, s, b, hot):
        key = jax.random.PRNGKey(1)
        tbl = jax.random.normal(key, (r, s))
        idx = jax.random.randint(key, (b, hot), 0, r)
        mask = (jax.random.uniform(key, (b, hot)) < 0.7).astype(jnp.float32)
        out = ops.embedding_bag_op(tbl, idx, mask, batch_tile=min(32, b),
                                   impl="interpret")
        assert jnp.allclose(out, ref.embedding_bag_ref(tbl, idx, mask),
                            atol=1e-4)

    @settings(max_examples=20, deadline=None)
    @given(r=st.integers(8, 300), b=st.sampled_from([8, 16, 32]),
           hot=st.integers(1, 9), seed=st.integers(0, 2**31 - 1))
    def test_property(self, r, b, hot, seed):
        key = jax.random.PRNGKey(seed)
        k1, k2, k3 = jax.random.split(key, 3)
        tbl = jax.random.normal(k1, (r, 16))
        idx = jax.random.randint(k2, (b, hot), 0, r)
        mask = (jax.random.uniform(k3, (b, hot)) < 0.5).astype(jnp.float32)
        out = ops.embedding_bag_op(tbl, idx, mask, batch_tile=b,
                                   impl="interpret")
        assert jnp.allclose(out, ref.embedding_bag_ref(tbl, idx, mask),
                            atol=1e-4)

    def test_all_masked_gives_zero(self):
        tbl = jax.random.normal(jax.random.PRNGKey(0), (50, 8))
        idx = jnp.zeros((16, 3), jnp.int32)
        mask = jnp.zeros((16, 3), jnp.float32)
        out = ops.embedding_bag_op(tbl, idx, mask, batch_tile=16,
                                   impl="interpret")
        assert jnp.allclose(out, 0.0)


class TestRwkv6Wkv:
    @pytest.mark.parametrize("b,s,h,chunk", [(2, 64, 2, 16), (1, 128, 4, 32),
                                             (3, 96, 1, 32), (2, 256, 2, 64)])
    def test_sweep(self, b, s, h, chunk):
        K = 64
        ks = jax.random.split(jax.random.PRNGKey(2), 6)
        r = jax.random.normal(ks[0], (b, s, h, K))
        k = jax.random.normal(ks[1], (b, s, h, K))
        v = jax.random.normal(ks[2], (b, s, h, K))
        logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, K)))
        u = jax.random.normal(ks[4], (h, K)) * 0.5
        s0 = jax.random.normal(ks[5], (b, h, K, K)) * 0.1
        out, sout = ops.rwkv6_wkv_op(r, k, v, logw, u, s0, chunk=chunk,
                                     impl="interpret")
        ro, rs = ref.rwkv6_wkv_ref(r, k, v, logw, u, s0)
        assert jnp.allclose(out, ro, atol=5e-4), (b, s, h, chunk)
        assert jnp.allclose(sout, rs, atol=5e-4)

    def test_extreme_decay_no_overflow(self):
        """Very fast decay (log w << 0) must stay exact — the safety the
        in-kernel pre-mask gives (upper-triangle exponents are +inf)."""
        b, s, h, K = 1, 64, 1, 64
        ks = jax.random.split(jax.random.PRNGKey(3), 3)
        r = jax.random.normal(ks[0], (b, s, h, K))
        k = jax.random.normal(ks[1], (b, s, h, K))
        v = jax.random.normal(ks[2], (b, s, h, K))
        logw = jnp.full((b, s, h, K), -50.0)  # state dies each step
        u = jnp.ones((h, K))
        s0 = jnp.zeros((b, h, K, K))
        out, _ = ops.rwkv6_wkv_op(r, k, v, logw, u, s0, chunk=16,
                                  impl="interpret")
        ro, _ = ref.rwkv6_wkv_ref(r, k, v, logw, u, s0)
        assert bool(jnp.all(jnp.isfinite(out)))
        assert jnp.allclose(out, ro, atol=1e-4)


def test_kernels_match_model_usage():
    """kernels/ops must agree with the model-level chunked implementation."""
    from repro.models.rwkv6 import wkv_chunked

    b, s, h, K = 2, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 6)
    r = jax.random.normal(ks[0], (b, s, h, K))
    k = jax.random.normal(ks[1], (b, s, h, K))
    v = jax.random.normal(ks[2], (b, s, h, K))
    logw = -jnp.exp(jax.random.normal(ks[3], (b, s, h, K)))
    u = jax.random.normal(ks[4], (h, K)) * 0.5
    s0 = jnp.zeros((b, h, K, K))
    o1, s1 = ops.rwkv6_wkv_op(r, k, v, logw, u, s0, chunk=32, impl="interpret")
    o2, s2 = wkv_chunked(r, k, v, logw, u, s0, chunk=32)
    assert jnp.allclose(o1, o2, atol=5e-4)
    assert jnp.allclose(s1, s2, atol=5e-4)
