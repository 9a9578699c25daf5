"""Plain reference of a DLRM-family model, and its lower-precision control:
the parts every architecture (``archs/*.py``) shares.

Written in ``jax.numpy`` with no kernel, cache, exchange or pipeline, and
importing nothing of the program.  Bags are sum-pooled table by table on
the device that holds the table, in float32:

  e_t    = sum_h mask[t, h] * tables[t, idx[t, h]]

and an architecture's dense part (its MLPs and interaction) turns the dense
features and the pooled bags into CTRs on the first device.  ``precision``
names how the matrix products are computed:
  "highest"  float32 products (the configuration's stated precision);
  "high"     the control: each float32 operand split into a bfloat16 high
             part and a bfloat16 low part, and the three products hi*hi,
             hi*lo, lo*hi summed in float32 -- what a TPU's three-pass
             ``high`` precision computes, written out so that it computes
             the same on any backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high")


def _dot(a, b, precision: str, spec: str):
    if precision == "highest":
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")

    def split(x):
        # reduce_precision rounds to bfloat16 where a compiler allowed
        # excess precision would drop a float32 -> bfloat16 -> float32
        # round trip, and with it the low part
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return hi.astype(jnp.bfloat16), (x - hi).astype(jnp.bfloat16)

    (ah, al), (bh, bl) = split(a), split(b)

    def d(x, y):
        return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)

    return d(ah, bh) + (d(ah, bl) + d(al, bh))


def _mlp(layers, x, precision):
    for i, lp in enumerate(layers):
        x = _dot(x, lp["kernel"], precision, "bi,io->bo") + lp["bias"]
        if i < len(layers) - 1:
            x = jax.nn.relu(x)
    return x


@jax.jit
def _pool_table(stack, t, idx, mask):
    """Sum-pooled bags of table ``t`` of a local stack: idx/mask (B, hot).
    The bag is summed one position at a time, in float32, so no compiler
    pass can fold the weighted sum into a lower-precision matmul."""
    table = jax.lax.dynamic_index_in_dim(stack, t, 0, keepdims=False)
    rows = jnp.take(table, idx, axis=0).astype(jnp.float32)  # (B, hot, s)

    def add(h, acc):
        return acc + rows[:, h] * mask[:, h, None]

    return jax.lax.fori_loop(0, idx.shape[1], add,
                             jnp.zeros((idx.shape[0], rows.shape[-1]),
                                       jnp.float32))


def _shards(tables):
    """(first table index, local stack) for each distinct table shard."""
    seen, out = set(), []
    for sh in tables.addressable_shards:
        start = sh.index[0].start or 0
        if start not in seen:
            seen.add(start)
            out.append((start, sh.data))
    return sorted(out, key=lambda x: x[0])


def pooled_bags(tables, idx: np.ndarray, mask: np.ndarray,
                n_tables: int) -> np.ndarray:
    """(B, T_pad, s) float32 pooled bags, each table pooled on its device."""
    b, t_pad, _ = idx.shape
    out = np.zeros((b, t_pad, tables.shape[-1]), np.float32)
    for start, stack in _shards(tables):
        dev = next(iter(stack.devices()))
        for lt in range(stack.shape[0]):
            t = start + lt
            if t >= n_tables:
                continue
            ix = jax.device_put(idx[:, t], dev)
            mk = jax.device_put(mask[:, t], dev)
            out[:, t] = np.asarray(_pool_table(stack, lt, ix, mk))
    return out


def ctr(dense_part, params, dense: np.ndarray, idx: np.ndarray,
        mask: np.ndarray, *, n_tables: int, precision: str = "highest",
        block: int = 512) -> np.ndarray:
    """Reference CTRs of the requests (dense, idx, mask), in blocks of
    ``block`` rows (the last block padded), as a float32 array.
    ``dense_part(rest, dense, pooled, *, precision, n_tables)`` is the
    architecture's jitted dense part; ``rest`` is ``params`` without the
    tables, on the first device."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    dev = jax.devices()[0]
    rest = jax.device_put({k: v for k, v in params.items()
                           if k != "tables"}, dev)
    n = dense.shape[0]
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        sel = np.arange(lo, lo + block).clip(max=hi - 1)
        pooled = pooled_bags(params["tables"], idx[sel], mask[sel], n_tables)
        got = dense_part(rest, jax.device_put(dense[sel], dev),
                         jax.device_put(pooled, dev), precision=precision,
                         n_tables=n_tables)
        out[lo:hi] = np.asarray(got)[:hi - lo]
    return out
