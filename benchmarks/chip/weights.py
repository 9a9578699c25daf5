"""Random weights made on the device from the seed, in one jitted call:
the parts every DLRM-family architecture (``archs/*.py``) shares.

The benchmark makes the weights itself, so the reference never takes an
array the program made.  They come in the layout the program serves from:

  tables  (t_pad, r_pad, s) in the configuration's dtype; t_pad rounds the
          table count up to a multiple of the chips, r_pad the largest table
          up to whole 128-row lane tiles; sharded table-wise over ``model``
  MLPs    [{"kernel": (d_in, d_out), "bias": (d_out,)}, ...], replicated

Tables are uniform in [-a, a] with a = 2 sqrt(3) / s (standard deviation
2/s); kernels truncated normal with He's scale (2 / d_in) ** 0.5; biases
normal with standard deviation 0.1.  At these scales the CTRs spread over
most of (0, 1) instead of bunching at 0.5, so an error anywhere on the
path moves them, and a dropped bias shows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

LANES = 128


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def stack_shape(cfg: dict) -> tuple:
    chips = cfg["chips"]
    t = len(cfg["table_sizes"])
    t_pad = -(-t // chips) * chips
    r_pad = -(-max(cfg["table_sizes"]) // LANES) * LANES
    return t_pad, r_pad, cfg["embed_dim"]


def tables(key, cfg: dict):
    """The table stack of ``cfg``, uniform in [-a, a]."""
    a = 2.0 * float(np.sqrt(3.0)) / cfg["embed_dim"]
    return jax.random.uniform(key, stack_shape(cfg), jnp.dtype(cfg["dtype"]),
                              -a, a)


def mlp(key, dims, dtype) -> list:
    """Layers ``dims[0] -> dims[1] -> ...``: a split of ``key`` a layer,
    each split again into its kernel's key and its bias's key."""
    out = []
    for i, k in enumerate(jax.random.split(key, len(dims) - 1)):
        kk, kb = jax.random.split(k)
        w = jax.random.truncated_normal(kk, -2.0, 2.0, (dims[i], dims[i + 1]))
        out.append({"kernel": (w * (2.0 / dims[i]) ** 0.5).astype(dtype),
                    "bias": (0.1 * jax.random.normal(
                        kb, (dims[i + 1],))).astype(dtype)})
    return out


def placed(build, mesh):
    """``build`` (key -> params with a ``tables`` stack) jitted so that the
    stack is split table-wise over ``model`` and the rest replicated."""
    rep = NamedSharding(mesh, PartitionSpec())
    shardings = jax.tree.map(lambda _: rep,
                             jax.eval_shape(build, seed_key(0)))
    shardings["tables"] = NamedSharding(
        mesh, PartitionSpec("model", None, None))
    return jax.jit(build, out_shardings=shardings)
