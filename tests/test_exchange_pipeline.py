"""The pipelined exchange (DESIGN.md §7): fused single-buffer wire
(``WireLayout`` / ``fuse_wire`` / ``defuse_wire``), the chunked ppermute
butterfly (``ring_exchange``), and their composition through
``forward_distributed(exchange_pipeline=...)`` — ring output asserted
BIT-identical to the monolithic exchange for every bound × codec ×
exchange-mode combination, and the fused ragged exchange asserted to
lower to exactly one collective per step from the jaxpr."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alltoallv as A2A
from repro.models import dlrm as D

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# wire layout + fuse/defuse (no mesh)
# ---------------------------------------------------------------------------


class TestWireLayout:
    def test_fields_are_name_sorted_and_packed(self):
        lay = A2A.wire_layout(3, {"q": ((4, 8), jnp.int8),
                                  "counts": ((1,), jnp.int32),
                                  "ids": ((4,), jnp.int16)})
        assert lay.names == ("counts", "ids", "q")
        assert [f.offset for f in lay.fields] == [0, 4, 12]
        assert lay.slot_bytes == 44 and lay.wire_bytes == 3 * 44
        with pytest.raises(KeyError):
            lay.field("scale")

    def test_slot_pads_to_wire_alignment(self):
        lay = A2A.wire_layout(2, {"q": ((3,), jnp.int8)})
        assert lay.slot_bytes == 4  # 3 payload bytes + 1 pad
        buf = A2A.fuse_wire({"q": jnp.ones((2, 3), jnp.int8)}, lay)
        assert buf.shape == (2, 4) and buf.dtype == jnp.uint8

    @pytest.mark.parametrize("wire", ["float32", "bfloat16", "int8"])
    @pytest.mark.parametrize("ragged", [True, False])
    def test_fuse_defuse_roundtrip_bit_exact(self, wire, ragged):
        p, cap, bs, t_loc, s = 4, 6, 5, 3, 8
        lay = A2A.exchange_wire_layout(ragged=ragged, n_dest=p, cap=cap,
                                       bs=bs, t_loc=t_loc, embed_dim=s,
                                       wire_dtype=wire)
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        pooled = jax.random.normal(
            ks[0], (p, cap, s) if ragged else (p, bs, t_loc, s))
        payload = A2A.encode_wire(pooled, wire)
        if ragged:
            payload["ids"] = jax.random.randint(
                ks[1], (p, cap), 0, bs * t_loc).astype(jnp.int16)
            payload["counts"] = jax.random.randint(
                ks[2], (p, 1), 0, cap + 1)
        back = A2A.defuse_wire(A2A.fuse_wire(payload, lay), lay)
        assert sorted(back) == sorted(payload)
        for k in payload:
            assert np.array_equal(
                np.asarray(back[k]),
                np.asarray(payload[k].reshape(back[k].shape))), k

    def test_single_chunk_defuse_drops_leading_axis(self):
        lay = A2A.exchange_wire_layout(ragged=True, n_dest=3, cap=4, bs=2,
                                       t_loc=2, embed_dim=8,
                                       wire_dtype="int8")
        payload = {
            "q": jnp.arange(3 * 4 * 8, dtype=jnp.int8).reshape(3, 4, 8),
            "scale": jnp.full((3, 4, 1), 0.5, jnp.bfloat16),
            "ids": jnp.arange(12, dtype=jnp.int16).reshape(3, 4),
            "counts": jnp.asarray([[1], [2], [3]], jnp.int32)}
        buf = A2A.fuse_wire(payload, lay)
        c = A2A.defuse_wire(buf[1], lay)
        assert c["q"].shape == (4, 8)
        assert int(c["counts"][0]) == 2
        assert np.array_equal(np.asarray(c["ids"]),
                              np.asarray(payload["ids"][1]))

    def test_fuse_validates_fields_dtype_and_shape(self):
        lay = A2A.wire_layout(2, {"q": ((3,), jnp.float32)})
        with pytest.raises(ValueError):     # missing / extra fields
            A2A.fuse_wire({"q": jnp.ones((2, 3)), "x": jnp.ones((2,))}, lay)
        with pytest.raises(ValueError):     # wrong dtype
            A2A.fuse_wire({"q": jnp.ones((2, 3), jnp.bfloat16)}, lay)
        with pytest.raises(ValueError):     # wrong per-dest bytes
            A2A.fuse_wire({"q": jnp.ones((2, 4), jnp.float32)}, lay)
        with pytest.raises(ValueError):     # wrong n_dest
            A2A.fuse_wire({"q": jnp.ones((3, 3), jnp.float32)}, lay)
        with pytest.raises(ValueError):     # defusing a foreign buffer
            A2A.defuse_wire(jnp.zeros((2, 99), jnp.uint8), lay)

    def test_slot_id_dtype_narrows_and_widens(self):
        assert A2A.slot_id_dtype(24) == jnp.int16
        assert A2A.slot_id_dtype(2 ** 15) == jnp.int16
        assert A2A.slot_id_dtype(2 ** 15 + 1) == jnp.int32

    def test_dispatch_stats_reports_fused_slot_bytes(self):
        # slot_bytes makes payload_bytes the single-buffer bytes the
        # fused exchange moves (ids/counts/padding included), while
        # useful bytes stay the live codec rows
        lay = A2A.exchange_wire_layout(ragged=True, n_dest=2, cap=4, bs=2,
                                       t_loc=2, embed_dim=8,
                                       wire_dtype="int8")
        row = lay.field("q").nbytes // 4
        st = A2A.dispatch_stats(jnp.asarray([3, 1]), 4, row,
                                slot_bytes=lay.slot_bytes)
        assert st.payload_bytes == lay.wire_bytes > 2 * 4 * row
        assert st.useful_bytes == 4 * row
        assert st.padding_fraction == \
            pytest.approx(1 - 4 * row / lay.wire_bytes)
        # without slot_bytes the old rows-only accounting is unchanged
        st0 = A2A.dispatch_stats(jnp.asarray([3, 1]), 4, row)
        assert st0.payload_bytes == 2 * 4 * row
        assert st0.padding_fraction == pytest.approx(0.5)

    def test_dense_vs_ragged_byte_crossover(self):
        # at cap = dense_rows the ragged wire costs MORE than the fused
        # dense butterfly (ids + counts ride along) — the honest number
        # the auto policy's profitability bar protects
        p, bs, t_loc, s = 4, 8, 3, 16
        dense = A2A.dense_wire_bytes(p, bs, t_loc, s, "int8")
        ragged_full = A2A.ragged_wire_bytes(p, bs * t_loc, s, "int8",
                                            n_slots=bs * t_loc)
        ragged_small = A2A.ragged_wire_bytes(p, 4, s, "int8",
                                             n_slots=bs * t_loc)
        assert ragged_full > dense > ragged_small


class TestResolvePipeline:
    def test_policy(self):
        assert D.resolve_pipeline("mono", 8) == "mono"
        assert D.resolve_pipeline("ring", 2) == "ring"
        assert D.resolve_pipeline("auto", 4) == "ring"
        assert D.resolve_pipeline("auto", 8) == "ring"
        assert D.resolve_pipeline("auto", 2) == "mono"
        assert D.resolve_pipeline("auto", 1) == "mono"
        with pytest.raises(ValueError):
            D.resolve_pipeline("butterfly", 4)


# ---------------------------------------------------------------------------
# distributed: ring-vs-mono bit parity + collective count (subprocess)
# ---------------------------------------------------------------------------


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_ring_exchange_unit_matches_manual_stitch():
    """``ring_exchange`` consumption over a shard_map axis reproduces the
    manual per-source stitch of the same destination-major buffers, and
    its chunks arrive from the sources the round schedule promises."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import compat
from repro.core import alltoallv as A2A

p, nb = 4, 6
mesh = compat.make_mesh((p,), ("model",))
# buf[m, d] = 10*m + d stamped per byte (fits uint8): member m's chunk
# for destination d
buf = (10 * jnp.arange(p, dtype=jnp.int32)[:, None, None]
       + jnp.arange(p, dtype=jnp.int32)[None, :, None]
       + jnp.zeros((1, 1, nb), jnp.int32)).astype(jnp.uint8)

def shard_fn(b):
    b = b[0]                                   # (p, nb) this member's sends
    def consume(out, src, chunk):
        # place chunk at row src: order-independent disjoint writes
        return jax.lax.dynamic_update_slice_in_dim(
            out, chunk.astype(jnp.int32)[None], src, axis=0)
    out = A2A.ring_exchange(b, "model", p, consume,
                            jnp.zeros((p, nb), jnp.int32))
    return out[None]

got = jax.shard_map(shard_fn, mesh=mesh,
                       in_specs=(P("model", None, None),),
                       out_specs=P("model", None, None),
                       check_vma=False)(buf)
# member m must hold row src = 10*src + m for every source
want = (10 * jnp.arange(p)[None, :, None]
        + jnp.arange(p)[:, None, None]
        + jnp.zeros((1, 1, nb), jnp.int32))
assert np.array_equal(np.asarray(got), np.asarray(want))
print("OK")
""")


def test_ring_matches_mono_bitwise_full_grid():
    """THE acceptance grid: ring-pipelined exchange output is
    bit-identical to the monolithic fused exchange for every codec ×
    bound × exchange-mode combination (cache on the ragged rows and on
    one dense row, no-cache on the rest), and both match forward_local
    within the codec tolerance."""
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
cache = HC.build_from_batch(params["tables"], b.idx, b.mask, 40)
TOL = {"float32": 1e-4, "bfloat16": 5e-2, "int8": 1e-1}
with partition.axis_rules(mesh):
    for bound, mb in [(0, 1), (2, 4)]:
        for wire, tol in TOL.items():
            for ex, c in [("dense", None), ("dense", cache),
                          ("ragged", cache)]:
                outs = {}
                for pipe in ("mono", "ring"):
                    f = jax.jit(lambda p, d, i, m, bound=bound, mb=mb,
                                w=wire, c=c, ex=ex, pipe=pipe:
                                D.forward_distributed(
                                    p, cfg, d, i, m, bound=bound,
                                    microbatches=mb, cache=c,
                                    wire_dtype=w, exchange=ex,
                                    exchange_pipeline=pipe))
                    outs[pipe] = f(params, dense, idx, mask)
                    err = float(jnp.max(jnp.abs(outs[pipe] - ref)))
                    assert err < tol, (bound, wire, ex, pipe, err)
                assert jnp.array_equal(outs["mono"], outs["ring"]), (
                    bound, wire, ex, "ring diverged from mono bitwise")
print("OK")
""")


def test_fused_exchange_is_one_collective_in_jaxpr():
    """The fused wire's contract, asserted from the jaxpr: a mono step —
    even int8 ragged, whose payload used to ride FOUR per-leaf
    collectives (codebook, scales, ids, counts) — lowers to exactly one
    all_to_all and zero ppermutes per exchange; a ring step to exactly
    P−1 ppermutes and zero all_to_alls."""
    run_sub("""
import collections
import jax, jax.numpy as jnp
from repro import compat
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.data import synthetic as S
from repro.serving import hot_cache as HC
from repro.sharding import partition

def count_collectives(closed):
    c = collections.Counter()
    def walk(jx):
        for eqn in jx.eqns:
            c[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)
    walk(closed.jaxpr)
    return c

cfg = DLRMConfig(name="t", table_sizes=(100, 50, 80, 60, 90, 40),
                 embed_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
                 max_hot=4)
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4)
b = S.make_batch(cfg, 64, mode="hetero", t_pad=D.padded_tables(cfg, 4),
                 seed=1)
dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
cache = HC.build_from_batch(params["tables"], b.idx, b.mask, 40)
with partition.axis_rules(mesh):
    for ex, wire in [("ragged", "int8"), ("ragged", "float32"),
                     ("dense", "int8"), ("dense", "float32")]:
        for pipe, want in [("mono", (1, 0)), ("ring", (0, 3))]:
            jx = jax.make_jaxpr(
                lambda p, d, i, m, w=wire, ex=ex, pipe=pipe:
                D.forward_distributed(p, cfg, d, i, m, cache=cache,
                                      wire_dtype=w, exchange=ex,
                                      exchange_pipeline=pipe)
                )(params, dense, idx, mask)
            c = count_collectives(jx)
            got = (c["all_to_all"], c["ppermute"])
            assert got == want, (ex, wire, pipe, dict(c))
print("OK")
""")
