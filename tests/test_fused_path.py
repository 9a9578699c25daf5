"""The fused sparse hot path (DESIGN.md): stacked-table Pallas embedding
bags, wire codecs for the butterfly exchange, and the cache-aware
distributed forward.  Parity oracle everywhere: ``forward_local`` /
pure-jnp references."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DLRMConfig
from repro.core import alltoallv as A2A
from repro.data import synthetic as S
from repro.kernels import ops, ref
from repro.models import dlrm as D
from repro.serving import hot_cache as HC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# wire codecs
# ---------------------------------------------------------------------------


class TestWireCodecs:
    def _x(self, shape=(16, 6, 8), seed=0, scale=3.0):
        return jax.random.normal(jax.random.PRNGKey(seed), shape) * scale

    def test_float32_is_identity(self):
        x = self._x()
        p = A2A.encode_wire(x, "float32")
        assert p["q"] is x
        assert jnp.array_equal(A2A.decode_wire(p), x)

    def test_bfloat16_roundtrip_error_bound(self):
        x = self._x()
        y = A2A.decode_wire(A2A.encode_wire(x, "bfloat16"))
        # bf16 has 8 significand bits -> relative error < 2^-8
        assert float(jnp.max(jnp.abs(y - x))) < float(jnp.max(jnp.abs(x))) / 128

    def test_int8_per_row_scale_error_bound(self):
        # rows with wildly different magnitudes: per-row scales keep the
        # small rows accurate (a per-tensor scale would zero them out)
        big = self._x((4, 2, 8), seed=1, scale=100.0)
        small = self._x((4, 2, 8), seed=2, scale=0.01)
        x = jnp.concatenate([big, small], axis=0)
        y = A2A.decode_wire(A2A.encode_wire(x, "int8"))
        row_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        assert bool(jnp.all(jnp.abs(y - x) <= row_max / 127.0 + 1e-6))

    def test_int8_scale_is_bf16_and_never_saturates(self):
        # the per-row scale ships as bf16 (2 bytes, not 4); the up-nudged
        # down-cast must keep quantization against the stored scale inside
        # [-127, 127] and the roundtrip inside the f32-scale error bound
        x = self._x((64, 4, 16), seed=3, scale=10.0)
        p = A2A.encode_wire(x, "int8")
        assert p["scale"].dtype == jnp.bfloat16
        raw = jnp.round(x.astype(jnp.float32) /
                        p["scale"].astype(jnp.float32))
        assert float(jnp.max(jnp.abs(raw))) <= 127.0
        y = A2A.decode_wire(p)
        row_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        assert bool(jnp.all(jnp.abs(y - x) <= row_max / 127.0 + 1e-6))

    def test_zero_rows_quantize_exactly(self):
        x = jnp.zeros((8, 3, 16))
        for wire in ("float32", "bfloat16", "int8"):
            assert float(jnp.max(jnp.abs(
                A2A.decode_wire(A2A.encode_wire(x, wire))))) == 0.0

    def test_unknown_wire_raises(self):
        with pytest.raises(ValueError):
            A2A.encode_wire(jnp.ones((2, 2)), "float8")

    def test_wire_stats_accounting(self):
        mask = jnp.asarray([[[1, 1], [0, 0], [1, 0]],
                            [[0, 0], [0, 0], [0, 1]]], jnp.float32)
        st = A2A.wire_stats(mask, embed_dim=4, wire_dtype="bfloat16")
        assert st.total_rows == 6
        assert st.live_rows == 3
        assert st.ref_bytes == 6 * 4 * 4
        assert st.dense_bytes == 6 * 4 * 2
        assert st.live_bytes == 3 * 4 * 2
        assert st.reduction_vs_ref == pytest.approx(1 - 24 / 96)
        st8 = A2A.wire_stats(mask, embed_dim=4, wire_dtype="int8")
        assert st8.live_bytes == 3 * (4 * 1 + 2)  # + per-row bf16 scale


# ---------------------------------------------------------------------------
# stacked-table kernel
# ---------------------------------------------------------------------------


class TestStackedEmbeddingBag:
    @pytest.mark.parametrize("t,r,s,b,hot", [(5, 40, 16, 32, 4),
                                             (3, 100, 8, 64, 1),
                                             (8, 30, 32, 16, 7)])
    def test_sweep_vs_ref(self, t, r, s, b, hot):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        tbl = jax.random.normal(ks[0], (t, r, s))
        idx = jax.random.randint(ks[1], (b, t, hot), 0, r)
        mask = (jax.random.uniform(ks[2], (b, t, hot)) < 0.6) \
            .astype(jnp.float32)
        out = ops.embedding_bag_stacked_op(tbl, idx, mask, batch_tile=16,
                                           impl="interpret")
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        assert out.shape == (b, t, s)
        assert jnp.allclose(out, want, atol=1e-4)

    def test_matches_single_table_kernel(self):
        ks = jax.random.split(jax.random.PRNGKey(1), 3)
        tbl = jax.random.normal(ks[0], (4, 50, 8))
        idx = jax.random.randint(ks[1], (16, 4, 3), 0, 50)
        mask = jnp.ones((16, 4, 3), jnp.float32)
        stacked = ops.embedding_bag_stacked_op(tbl, idx, mask, batch_tile=16,
                                               impl="interpret")
        for ti in range(4):
            single = ops.embedding_bag_op(tbl[ti], idx[:, ti], mask[:, ti],
                                          batch_tile=16, impl="interpret")
            assert jnp.allclose(stacked[:, ti], single, atol=1e-5), ti

    def test_apply_emb_backend_dispatch(self):
        cfg = DLRMConfig(name="t", table_sizes=(60, 40, 80), embed_dim=8,
                         max_hot=4)
        tbl = jax.random.normal(jax.random.PRNGKey(2), (3, 80, 8))
        b = S.make_batch(cfg, 24, mode="hetero", seed=3)
        idx, mask = jnp.asarray(b.idx), jnp.asarray(b.mask)
        r = D.apply_emb(tbl, idx, mask, "ref")
        k = D.apply_emb(tbl, idx, mask, "interpret")
        assert jnp.allclose(r, k, atol=1e-4)
        with pytest.raises(ValueError):
            D.apply_emb(tbl, idx, mask, "cuda")

    def test_forward_local_backends_agree(self):
        cfg = DLRMConfig(name="t", table_sizes=(60, 40, 80), embed_dim=8,
                         bottom_mlp=(16, 8), top_mlp=(16, 1), max_hot=4)
        params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=1)
        b = S.make_batch(cfg, 16, mode="hetero", seed=1)
        dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
        out_ref = D.forward_local(params, cfg, dense, idx, mask)
        cfg_k = cfg.replace(sparse_backend="interpret")
        out_k = D.forward_local(params, cfg_k, dense, idx, mask)
        assert jnp.allclose(out_ref, out_k, atol=1e-4)


# ---------------------------------------------------------------------------
# cache split helpers
# ---------------------------------------------------------------------------


class TestCacheSplit:
    def _setup(self, cache_rows, mode="powerlaw"):
        cfg = DLRMConfig(name="t", table_sizes=(500, 300, 400), embed_dim=8,
                         max_hot=4)
        tables = jax.random.normal(jax.random.PRNGKey(0), (3, 500, 8))
        b = S.make_batch(cfg, 48, mode=mode, seed=1)
        idx, mask = jnp.asarray(b.idx), jnp.asarray(b.mask)
        cache = HC.build_from_batch(tables, b.idx, b.mask, cache_rows)
        return tables, cache, idx, mask

    def test_split_helpers_match_lookup(self):
        tables, cache, idx, mask = self._setup(16)
        hits, miss = HC.lookup(cache, idx, mask)
        assert jnp.array_equal(
            miss, HC.miss_mask_of(cache.slot_of, idx, mask))
        assert jnp.allclose(
            hits, HC.pooled_hits_of(cache.hot_rows, cache.slot_of, idx,
                                    mask))

    def test_cache_rows_zero_degenerate(self):
        tables, cache, idx, mask = self._setup(0)
        assert cache.cache_rows == 0
        hits, miss = HC.lookup(cache, idx, mask)
        assert float(jnp.max(jnp.abs(hits))) == 0.0
        assert jnp.array_equal(miss, mask)
        assert HC.hit_rate(cache, idx, mask) == 0.0

    def test_hits_plus_misses_cover_full_bag(self):
        tables, cache, idx, mask = self._setup(16)
        hits, miss = HC.lookup(cache, idx, mask)
        full = D.apply_emb(tables, idx, mask)
        misses = D.apply_emb(tables, idx, miss)
        assert jnp.allclose(hits + misses, full, atol=1e-5)


# ---------------------------------------------------------------------------
# fused distributed parity (8 forced host devices, subprocess)
# ---------------------------------------------------------------------------


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_fused_distributed_matches_local():
    """Fused (cache + quantized wire) logits match forward_local within the
    wire dtype's tolerance across bounds k in {0, 2} and hit rates
    {0, ~0.5, ~1.0} (cache_rows {0, 40, 100}); float32 wire with no cache
    is the bit-identical reference path."""
    run_sub("""
import jax, jax.numpy as jnp
from repro import compat
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.data import synthetic as S
from repro.serving import hot_cache as HC
from repro.sharding import partition

cfg = DLRMConfig(name="t", table_sizes=(100, 50, 80, 60, 90, 40),
                 embed_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
                 max_hot=4)
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4)
b = S.make_batch(cfg, 64, mode="hetero", t_pad=D.padded_tables(cfg, 4),
                 seed=1)
dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
ref = D.forward_local(params, cfg, dense, idx, mask)
TOL = {"float32": 1e-4, "bfloat16": 5e-2, "int8": 1e-1}
caches = {rows: HC.build_from_batch(params["tables"], b.idx, b.mask, rows)
          for rows in (0, 40, 100)}
hr = {rows: HC.hit_rate(c, idx, mask) for rows, c in caches.items()}
assert hr[0] == 0.0 and 0.3 < hr[40] < 0.95 and hr[100] == 1.0, hr
with partition.axis_rules(mesh):
    for bound, mb in [(0, 1), (2, 4)]:
        for wire, tol in TOL.items():
            for rows, cache in caches.items():
                out = jax.jit(lambda p, d, i, m, bound=bound, mb=mb,
                              w=wire, c=cache:
                              D.forward_distributed(p, cfg, d, i, m,
                                                    bound=bound,
                                                    microbatches=mb,
                                                    cache=c, wire_dtype=w)
                              )(params, dense, idx, mask)
                err = float(jnp.max(jnp.abs(out - ref)))
                assert err < tol, (bound, wire, rows, err)
                # full-hit cache: nothing on the wire -> exact parity with
                # the f32 path even under lossy codecs
                if rows == 100:
                    assert err < 1e-4, (bound, wire, rows, err)
print("OK")
""")


def test_fused_wire_payload_shrinks():
    """Acceptance: under power-law skew + ragged bags, the cache+bf16
    exchange moves >= 40% fewer payload bytes than the f32 reference."""
    cfg = DLRMConfig(name="t", table_sizes=(500, 300, 400, 200), embed_dim=16,
                     max_hot=4)
    b = S.make_batch(cfg, 128, mode="powerlaw_hetero", seed=0)
    tables = jax.random.normal(jax.random.PRNGKey(0), (4, 500, 16))
    cache = HC.build_from_batch(tables, b.idx, b.mask, 32)
    idx, mask = jnp.asarray(b.idx), jnp.asarray(b.mask)
    _, miss_mask = HC.lookup(cache, idx, mask)
    st = A2A.wire_stats(miss_mask, cfg.embed_dim, "bfloat16")
    assert st.reduction_vs_ref >= 0.40, st
    # bf16 alone halves the dense exchange even with no cache
    st_dense = A2A.wire_stats(mask, cfg.embed_dim, "bfloat16")
    assert st_dense.reduction_vs_ref == pytest.approx(0.5)
