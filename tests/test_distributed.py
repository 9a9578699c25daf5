"""Multi-device equivalence tests.  jax locks the device count at first init,
so these run in subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_dlrm_distributed_matches_local_all_bounds():
    run_sub("""
import jax, jax.numpy as jnp
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.data import synthetic as S
from repro.sharding import partition

cfg = DLRMConfig(name="t", table_sizes=(100, 50, 80, 60, 90, 40),
                 embed_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
                 max_hot=4)
from repro import compat
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4)
b = S.make_batch(cfg, 64, mode="hetero", t_pad=D.padded_tables(cfg, 4), seed=1)
dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
ref = D.forward_local(params, cfg, dense, idx, mask)
with partition.axis_rules(mesh):
    for bound, mb in [(0, 1), (0, 4), (1, 4), (2, 4), (3, 8)]:
        out = jax.jit(lambda p, d, i, m, bound=bound, mb=mb:
                      D.forward_distributed(p, cfg, d, i, m, bound=bound,
                                            microbatches=mb))(params, dense, idx, mask)
        assert jnp.allclose(out, ref, atol=1e-4), (bound, mb)
print("OK")
""")


def test_bls_pipeline_with_real_all_to_all():
    run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core.bls import bls_pipeline, reference_loop
from repro import compat
mesh = compat.make_mesh((2, 4), ("data", "model"))
def run(bound):
    def shard_fn(x):
        a = lambda xj: (xj * 1.0, xj.sum(axis=(1, 2)))
        c = lambda p: jax.lax.all_to_all(p, "model", 0, 1, tiled=True)
        b = lambda recv, side: recv.sum(axis=(1, 2)) + 0.1 * side[:recv.shape[0]]
        if bound is None:
            return reference_loop(a, c, b, x)
        out, _ = bls_pipeline(a, c, b, x, bound)
        return out
    return jax.jit(jax.shard_map(shard_fn, mesh=mesh,
        in_specs=P(None, "data", "model", None),
        out_specs=P(None, ("data", "model")), check_vma=False))
x = jax.random.normal(jax.random.PRNGKey(0), (5, 8, 4, 6))
ref = run(None)(x)
for k in [0, 1, 2, 3]:
    assert jnp.allclose(run(k)(x), ref, atol=1e-5), k
print("OK")
""")


def test_moe_a2a_matches_gather_and_ref():
    run_sub("""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.configs.base import ModelConfig, MoEConfig
from repro.models import moe as M
from repro.sharding import partition

cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                  n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64,
                  moe=MoEConfig(n_experts=8, experts_per_token=2, d_expert=16,
                                capacity_factor=8.0),
                  dtype="float32")
from repro import compat
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = M.init_moe(jax.random.PRNGKey(0), cfg, n_shards=4)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 32))
ref, _ = M.moe_ref_dense(params, cfg, x)
with partition.axis_rules(mesh):
    g, _ = jax.jit(lambda p, x: M.moe_gather(p, cfg, x))(params, x)
    a, _ = jax.jit(lambda p, x: M.moe_a2a(p, cfg, x))(params, x)
print("gather diff", float(jnp.max(jnp.abs(g - ref))))
print("a2a diff", float(jnp.max(jnp.abs(a - ref))))
assert jnp.allclose(g, ref, atol=1e-4)
assert jnp.allclose(a, ref, atol=1e-4)
print("OK")
""")


def test_checkpoint_cross_mesh_restore():
    run_sub("""
import jax, jax.numpy as jnp, tempfile
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.runtime import checkpoint as C

tree = {"w": jnp.arange(64.0).reshape(8, 8), "b": jnp.ones((8,))}
with tempfile.TemporaryDirectory() as d:
    C.save(d, 3, tree)
    # restore onto a 2x4 mesh with model sharding (elastic re-mesh)
    from repro import compat
    mesh = compat.make_mesh((2, 4), ("data", "model"))
    shardings = {"w": NamedSharding(mesh, P("data", "model")),
                 "b": NamedSharding(mesh, P("model"))}
    restored, step = C.restore(d, tree, shardings=shardings)
    assert step == 3
    assert jnp.allclose(restored["w"], tree["w"])
    assert restored["w"].sharding.spec == P("data", "model")
print("OK")
""")
