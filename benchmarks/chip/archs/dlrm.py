"""DLRM with the dot interaction (Naumov et al., arXiv:1906.00091, the
reference ``dlrm_s_pytorch.py``): the architecture of ``"arch": "dlrm"``.

  z0     = bottom MLP(dense)                      ReLU between layers
  e_t    = sum_h mask[t, h] * tables[t, idx[t, h]]  sum-pooled bag per table
  z      = [z0, e_0, ..., e_{T-1}]                (T + 1, s)
  inter  = z_i . z_j for i > j, row-major         (lower triangle)
  ctr    = sigmoid(top MLP([z0, inter]))          ReLU between layers

An architecture file gives the benchmark everything that depends on the
model's equations: the program's configuration (``program_config``), the
table layout (``stack_shape``), the weights (``make_params``), the plain
reference (``ctr``) and its block size (``reference_block``), and the work a
request defines (``request_flops``, ``pooling_bytes``).  What every
DLRM-family model shares comes from ``weights``, ``reference`` and
``counts``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import counts
import reference
import weights

# the shared table layout and pooling bytes are this architecture's
stack_shape = weights.stack_shape
pooling_bytes = counts.pooling_bytes


def program_config(cfg: dict):
    """The program's ``DLRMConfig`` for a configuration file."""
    from repro.configs.base import DLRMConfig
    return DLRMConfig(
        name=cfg["name"], n_dense_features=cfg["n_dense_features"],
        table_sizes=tuple(cfg["table_sizes"]), embed_dim=cfg["embed_dim"],
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        max_hot=cfg["max_hot"], arch_interaction_op=cfg["interaction"],
        dtype=cfg["dtype"], sparse_backend=cfg["sparse_backend"],
        exchange_pipeline=cfg["exchange_pipeline"])


def mlp_dims(cfg: dict) -> tuple:
    f = len(cfg["table_sizes"]) + 1
    bot = (cfg["n_dense_features"], *cfg["bottom_mlp"])
    top = (f * (f - 1) // 2 + cfg["embed_dim"], *cfg["top_mlp"])
    return bot, top


def make_params(seed: int, cfg: dict, mesh):
    """``{"tables", "bot", "top"}`` of ``cfg`` for ``seed``, placed on
    ``mesh``: one key split three ways, in that order."""
    dt = jnp.dtype(cfg["dtype"])
    bot_dims, top_dims = mlp_dims(cfg)

    def build(key):
        kt, kb, ktop = jax.random.split(key, 3)
        return {"tables": weights.tables(kt, cfg),
                "bot": weights.mlp(kb, bot_dims, dt),
                "top": weights.mlp(ktop, top_dims, dt)}

    return weights.placed(build, mesh)(weights.seed_key(seed))


@functools.partial(jax.jit, static_argnames=("precision", "n_tables"))
def _dense_part(rest, dense, pooled, *, precision: str, n_tables: int):
    """CTRs from dense features (B, n_dense) and pooled bags (B, T_pad, s)."""
    z0 = reference._mlp(rest["bot"], dense, precision)
    z = jnp.concatenate([z0[:, None, :], pooled[:, :n_tables]], axis=1)
    zz = reference._dot(z, z, precision, "bfs,bgs->bfg")
    ii, jj = np.tril_indices(z.shape[1], k=-1)
    top_in = jnp.concatenate([z0, zz[:, ii, jj]], axis=-1)
    return jax.nn.sigmoid(reference._mlp(rest["top"], top_in, precision)[:, 0])


def ctr(params, dense: np.ndarray, idx: np.ndarray, mask: np.ndarray, *,
        cfg: dict, precision: str = "highest", block: int = 512):
    """Reference CTRs of the requests (dense, idx, mask), float32."""
    return reference.ctr(_dense_part, params, dense, idx, mask,
                         n_tables=len(cfg["table_sizes"]),
                         precision=precision, block=block)


def reference_block(cfg: dict) -> int:
    """Rows per reference block: about 51,200 gathered rows a block, at
    most 8,192 requests."""
    return max(512, min(8192, (51200 // cfg["max_hot"]) // 512 * 512))


def dense_flops(cfg: dict) -> int:
    """FLOPs of one request outside the pooling: both MLPs and the dot
    interaction, F * (F - 1) / 2 dots of width s over F = T + 1 features."""
    bot, top = mlp_dims(cfg)
    f = len(cfg["table_sizes"]) + 1
    return (counts._mlp_flops(bot) + f * (f - 1) * cfg["embed_dim"]
            + counts._mlp_flops(top))


def request_flops(cfg: dict, valid: np.ndarray) -> np.ndarray:
    """Model FLOPs of requests with ``valid`` indices each."""
    return dense_flops(cfg) + counts.pooling_flops(cfg, valid)

