"""alltoallv machinery + the BLS×MoE composition (the paper's collective
decoupling applied to expert-parallel dispatch)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.alltoallv import dispatch_stats, pack_ragged

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPackRagged:
    def test_roundtrip_and_counts(self):
        rows = jnp.arange(24.0).reshape(12, 2)
        dest = jnp.asarray([0, 0, 1, 2, 2, 2, 3, 3, 3, 3, 0, 1])
        buf, counts, drops = pack_ragged(rows, dest, n_dest=4, cap=8)
        assert counts.tolist() == [3, 2, 3, 4]
        assert int(drops) == 0
        # every valid row lands in its destination bucket
        for d in range(4):
            want = np.asarray(rows)[np.asarray(dest) == d]
            got = np.asarray(buf[d][: int(counts[d])])
            assert np.allclose(np.sort(got, 0), np.sort(want, 0)), d

    def test_capacity_drop(self):
        rows = jnp.ones((10, 2))
        dest = jnp.zeros((10,), jnp.int32)
        buf, counts, drops = pack_ragged(rows, dest, n_dest=2, cap=4)
        assert int(counts[0]) == 4  # 6 dropped (static-shape price)
        assert int(counts[1]) == 0
        assert int(drops) == 6     # ... and the pack says so

    def test_excluded_rows_are_not_drops(self):
        # dest -1 marks dead rows (the ragged exchange's all-hit bags):
        # excluded by design, never reported as drops
        rows = jnp.ones((6, 2))
        dest = jnp.asarray([-1, 0, -1, 1, -1, 1], jnp.int32)
        _, counts, drops = pack_ragged(rows, dest, n_dest=2, cap=4)
        assert counts.tolist() == [1, 2]
        assert int(drops) == 0

    def test_dispatch_stats(self):
        counts = jnp.asarray([3, 2, 3, 4])
        st = dispatch_stats(counts, cap=8, row_bytes=16)
        assert st.useful_bytes == 12 * 16
        assert st.payload_bytes == 32 * 16
        assert st.padding_fraction == pytest.approx(1 - 12 / 32)


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_alltoallv_raw_roundtrip_multidevice():
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.alltoallv import alltoallv_raw, pack_ragged
from repro import compat
mesh = compat.make_mesh((8,), ("model",))

def shard_fn(rows, dest):
    buf, counts, _ = pack_ragged(rows, dest, n_dest=8, cap=16)
    recv, rcounts = alltoallv_raw(buf, counts, "model")
    # checksum of valid rows survives the exchange globally
    mask = jnp.arange(16)[None, :] < rcounts[:, None]
    local = jnp.sum(recv * mask[..., None])
    return jax.lax.psum(local, "model")[None]

rows = jnp.arange(8 * 32 * 4.0).reshape(8 * 32, 4)
dest = jnp.asarray(np.random.default_rng(0).integers(0, 8, 8 * 32))
total = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
    in_specs=(P("model"), P("model")), out_specs=P("model"),
    check_vma=False))(rows, dest)
assert jnp.allclose(total[0], rows.sum()), (float(total[0]), float(rows.sum()))
print("OK")
""")


def test_moe_a2a_dispatch_under_bls_pipeline():
    """The paper's bounded-lag decoupling applied to the MoE dispatch
    all_to_all: stream microbatches, buffer the dispatched tokens k deep,
    outputs must equal the dense reference for every bound."""
    run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.configs.base import ModelConfig, MoEConfig
from repro.core.bls import bls_pipeline, reference_loop
from repro.models import moe as M
from repro import compat

cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64,
                  moe=MoEConfig(n_experts=8, experts_per_token=2, d_expert=16,
                                capacity_factor=8.0),
                  dtype="float32")
mesh = compat.make_mesh((8,), ("model",))
params = M.init_moe(jax.random.PRNGKey(0), cfg, n_shards=8)
moe, e_pad, e_loc = cfg.moe, 8, 1
d = cfg.d_model

def make(bound):
    def shard_fn(router_w, gate, up, down, xs):
        # xs: (N, t_loc, d) stream of microbatches on this shard
        n_shards = 8
        t_loc = xs.shape[1]
        c_send = M.capacity(t_loc, moe.experts_per_token, n_shards,
                            moe.capacity_factor)
        c_exp = M.capacity(t_loc * n_shards, moe.experts_per_token, e_pad,
                           moe.capacity_factor)

        def stage_a(xl):
            w, idx, _ = M.route(router_w, xl, moe, e_pad)
            dest = idx // e_loc
            fe, ft, pos, valid, order = M.dispatch_indices(
                dest, n_shards, c_send)
            fw = w.reshape(-1)[order]
            fx = idx.reshape(-1)[order]
            de = jnp.where(valid, fe, n_shards)
            dp = jnp.where(valid, pos, 0)
            send = jnp.zeros((n_shards, c_send, d), xl.dtype)
            send = send.at[de, dp].set(xl[ft], mode="drop")
            eid = jnp.full((n_shards, c_send), e_loc, jnp.int32)
            eid = eid.at[de, dp].set((fx % e_loc).astype(jnp.int32),
                                     mode="drop")
            side = (de, dp, fw, valid, ft)
            return (send, eid.astype(xl.dtype)), side

        def coll(p):
            send, eid = p
            return (jax.lax.all_to_all(send, "model", 0, 0, tiled=True),
                    jax.lax.all_to_all(eid, "model", 0, 0, tiled=True))

        def stage_b(recv_p, side):
            recv, eid_f = recv_p
            de, dp, fw, valid, ft = side
            rx = recv.reshape(-1, d)
            reid = eid_f.reshape(-1, 1).astype(jnp.int32)
            fe2, ft2, pos2, valid2, _ = M.dispatch_indices(reid, e_loc, c_exp)
            buf = jnp.zeros((e_loc, c_exp, d), rx.dtype)
            buf = buf.at[jnp.where(valid2, fe2, e_loc),
                         jnp.where(valid2, pos2, 0)].set(rx[ft2], mode="drop")
            ob = M._expert_mlp({"gate": gate, "up": up, "down": down}, buf,
                               cfg.act)
            ry = ob.at[jnp.clip(fe2, 0, e_loc - 1),
                       jnp.clip(pos2, 0, c_exp - 1)].get(mode="clip")
            ry = ry * valid2[:, None].astype(ry.dtype)
            back = jnp.zeros((n_shards * c_send, d), ry.dtype).at[ft2].add(ry)
            reply = jax.lax.all_to_all(back.reshape(n_shards, c_send, d),
                                       "model", 0, 0, tiled=True)
            y = reply.reshape(-1, d)[de * c_send + dp]
            y = y * (fw * valid)[:, None].astype(y.dtype)
            return jnp.zeros((t_loc, d), y.dtype).at[ft].add(y)

        if bound is None:
            return reference_loop(stage_a, coll, stage_b, xs)
        out, _ = bls_pipeline(stage_a, coll, stage_b, xs, bound)
        return out

    return jax.jit(jax.shard_map(shard_fn, mesh=mesh,
        in_specs=(P(), P("model", None, None), P("model", None, None),
                  P("model", None, None), P(None, "model", None)),
        out_specs=P(None, "model", None), check_vma=False))

xs = jax.random.normal(jax.random.PRNGKey(1), (5, 64, 32))
f = make(None)
ref = f(params["router"], params["gate"], params["up"], params["down"], xs)
# dense oracle on the flattened stream
dense_out, _ = M.moe_ref_dense(params, cfg, xs.reshape(1, -1, 32))
assert jnp.allclose(ref.reshape(-1, 32), dense_out[0], atol=1e-4)
for k in (0, 1, 2):
    out = make(k)(params["router"], params["gate"], params["up"],
                  params["down"], xs)
    assert jnp.allclose(out, ref, atol=1e-5), k
print("OK")
""")
