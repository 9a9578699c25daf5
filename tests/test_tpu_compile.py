"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jaxlib, so the embedding-bag kernels and
the one-chip serving step are lowered through Mosaic and compiled for a
``v5e:2x2`` topology at Kaggle widths (26 tables of 1,101,312 rows, s=64,
512 bags of up to 100 indices).  This catches what interpret mode cannot:
refused primitives, tiling violations, scoped-VMEM overruns, and a
relayout copy of the table stack that would not fit the chip's HBM.

The topology is described inside a module fixture (never at import), and
the persistent compilation cache is off around these compiles: their
entries cannot be read back without a chip.
"""
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import base as cb
from repro.kernels import embedding_bag as eb
from repro.models import dlrm as D
from repro.sharding import partition

T, R, S_DIM, B, HOT = 26, 1_101_312, 64, 512, 100
STACK_BYTES = T * R * S_DIM * 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"   # the compiler logs nowhere
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_native_kernel_no_stack_copy(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    # the kernels read the stack in place through its (s, R) view; a
    # relayout copy would need a second ~7 GB buffer
    assert compiled.memory_analysis().temp_size_in_bytes < STACK_BYTES // 8


@pytest.mark.parametrize("pool", ["scalar", "vector"])
def test_streamed_stacked_compiles(one_chip, pool):
    c = _compile(functools.partial(eb.embedding_bag_stacked,
                                   pool_mode=pool),
                 _shape(one_chip, (T, R, S_DIM)),
                 _shape(one_chip, (B, T, HOT), jnp.int32),
                 _shape(one_chip, (B, T, HOT)))
    _assert_native_kernel_no_stack_copy(c)


def test_rows_form_compiles(one_chip):
    n = 4 * B                         # a packed ragged-exchange row set
    c = _compile(eb.embedding_bag_rows,
                 _shape(one_chip, (T, R, S_DIM)),
                 _shape(one_chip, (n,), jnp.int32),
                 _shape(one_chip, (n, HOT), jnp.int32),
                 _shape(one_chip, (n, HOT)))
    _assert_native_kernel_no_stack_copy(c)


@pytest.mark.parametrize("pool", ["scalar", "vector"])
def test_resident_stacked_compiles(one_chip, pool):
    r = 8192
    assert not eb.resolve_row_block(r, S_DIM, 4, 0)[0]   # resident regime
    c = _compile(functools.partial(eb.embedding_bag_stacked,
                                   pool_mode=pool),
                 _shape(one_chip, (T, r, S_DIM)),
                 _shape(one_chip, (B, T, HOT), jnp.int32),
                 _shape(one_chip, (B, T, HOT)))
    assert "tpu_custom_call" in c.as_text()


def test_one_chip_serving_step_compiles(topo):
    """The one-chip BLS step the engine jits (bound 2, 4 microbatches) at
    the registered dlrm-kaggle widths, with sparse_backend='pallas': the
    pooling is the native Mosaic kernel, never an interpreted one."""
    cfg = cb.get_arch("dlrm-kaggle").config.replace(sparse_backend="pallas")
    mesh = Mesh(np.asarray(topo.devices[:1]).reshape(1, 1),
                ("data", "model"))
    shapes = jax.eval_shape(
        lambda k: D.init_dlrm(k, cfg, n_shards=1), jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(lambda a: _shape(rep, a.shape, a.dtype), shapes)
    t_pad, r, s = params["tables"].shape
    assert (t_pad, r, s) == (T, R, S_DIM)
    params["tables"] = _shape(NamedSharding(mesh, P("model", None, None)),
                              (t_pad, r, s))

    def step(params, dense, idx, mask):
        return D.forward_distributed(params, cfg, dense, idx, mask,
                                     bound=2, microbatches=4)

    with partition.axis_rules(mesh):
        c = _compile(step, params,
                     _shape(rep, (B, cfg.n_dense_features)),
                     _shape(rep, (B, t_pad, cfg.max_hot), jnp.int32),
                     _shape(rep, (B, t_pad, cfg.max_hot)))
    _assert_native_kernel_no_stack_copy(c)


def test_stream_plan_compiles_without_while(one_chip):
    """One microbatch's stream plan at Kaggle widths under the auto
    policy (lane tiles, 52 tiles of 6,400 indices): block runs and
    offsets come from sorts and a cumsum, so the TPU program holds no
    ``while`` loop and no gather."""
    plan_of = functools.partial(eb.stacked_stream_plan, T, R, S_DIM, 4)
    c = _compile(plan_of, _shape(one_chip, (B // 4, T, HOT), jnp.int32))
    txt = c.as_text()
    assert " sort(" in txt and not re.search(r"\bwhile\(", txt)
    assert not re.search(r"\b(gather|scatter)\(", txt)
