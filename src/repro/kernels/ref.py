"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dot_interaction_ref(z):
    """z:(B,F,S) -> (B, F(F-1)/2) lower-triangle of Z @ Z^T (reference DLRM
    interact_features)."""
    b, f, s = z.shape
    zz = jnp.einsum("bfs,bgs->bfg", z.astype(jnp.float32),
                    z.astype(jnp.float32))
    ii, jj = jnp.tril_indices(f, k=-1)
    return zz[:, ii, jj].astype(z.dtype)


def embedding_bag_ref(table, idx, mask):
    """table:(R,S) idx:(B,hot) mask:(B,hot) -> (B,S) masked-sum bags."""
    rows = table[jnp.clip(idx, 0, table.shape[0] - 1)]      # (B,hot,S)
    return jnp.sum(rows * mask[..., None].astype(rows.dtype), axis=1)


def embedding_bag_stacked_ref(tables, idx, mask):
    """tables:(T,R,S) idx/mask:(B,T,hot) -> (B,T,S) per-table masked sums.
    Materializes each table's (B,hot,S) gather the Pallas kernel avoids.
    One table at a time: on the TPU the stack lives rows-minor, and a
    gather over the whole stack would first copy all of it into a padded
    rows-major layout (more than a chip holds at Criteo-Kaggle size)."""
    per_table = jax.lax.map(
        lambda a: embedding_bag_ref(*a),
        (tables, idx.astype(jnp.int32).swapaxes(0, 1), mask.swapaxes(0, 1)))
    return per_table.swapaxes(0, 1)


def embedding_bag_rows_ref(tables, tid, idx, mask):
    """tables:(T,R,S) tid:(N,) idx/mask:(N,hot) -> (N,S) masked sums, each
    row pooled against its own table — the packed-ragged form (the pool
    half of the ragged miss-residual exchange).  OOB ids clip exactly like
    the stacked reference so every backend agrees."""
    rows = tables[tid[:, None], jnp.clip(idx, 0, tables.shape[1] - 1)]
    return jnp.sum(rows * mask[..., None].astype(rows.dtype), axis=1)


def rwkv6_wkv_ref(r, k, v, logw, u, state):
    """Exact WKV recurrence.  r,k,logw:(B,S,H,K) v:(B,S,H,V) u:(H,K)
    state:(B,H,K,V) -> (out (B,S,H,V), final state)."""

    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhk,bhkv->bhv", rt, s + u[None, :, :, None] * kv)
        s = jnp.exp(wt)[..., None] * s + kv
        return s, out

    xs = tuple(a.swapaxes(0, 1) for a in (r, k, v, logw))
    state, out = jax.lax.scan(step, state, xs)
    return out.swapaxes(0, 1), state
