"""Pallas TPU kernel: chunk-parallel RWKV-6 WKV with data-dependent decay.

The chunked algorithm (models/rwkv6.py::wkv_chunked) needs the per-chunk
pairwise decay tensor exp(L_{t-1} - L_s) of shape (C, C, K).  A pure-XLA
implementation materialises it in HBM every chunk (B·H·C²·K·4 bytes — the
dominant memory term of rwkv6 training).  This kernel is the TPU adaptation:
the tensor is built and consumed inside VMEM per (batch, head, chunk) grid
step and never touches HBM; the running (K, V) state is carried in a VMEM
scratch across the sequential chunk dimension — the same carry pattern flash
attention uses for its running softmax.

All exponentials are of non-positive cumulative-log-decay differences, so the
kernel is exact (no clamping) — verified against the recurrent oracle in
tests/test_kernels.py across shape/dtype sweeps.

Grid: (B, H, NC) with NC innermost/sequential ("arbitrary" semantics).
VMEM per step: 4·C·K (r,k,v,w) + C²·K (decay) + K·V (state) floats;
C=64, K=V=64 -> ~1.2 MB, comfortably under the ~16 MB v5e VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, out_ref, sout_ref,
            state, *, n_chunks: int):
    nc = pl.program_id(2)

    @pl.when(nc == 0)
    def _init():
        state[...] = s0_ref[0, 0].astype(jnp.float32)

    rc = r_ref[0, :, 0, :].astype(jnp.float32)   # (C, K)
    kc = k_ref[0, :, 0, :].astype(jnp.float32)
    vc = v_ref[0, :, 0, :].astype(jnp.float32)   # (C, V)
    wc = w_ref[0, :, 0, :].astype(jnp.float32)   # (C, K) log decay <= 0
    uu = u_ref[0].astype(jnp.float32)            # (K,)
    c = rc.shape[0]

    linc = jnp.cumsum(wc, axis=0)                # inclusive cum log decay
    lexc = linc - wc                             # exclusive
    st = state[...]

    # cross-chunk: decay-from-chunk-start times carried state  (MXU)
    cross = (rc * jnp.exp(lexc)) @ st            # (C, V)

    # intra-chunk: pairwise decay tensor lives only in VMEM      (VPU + MXU)
    # mask BEFORE exponentiating: upper-triangle exponents are positive and
    # would overflow to inf (inf * 0 = nan after the contraction)
    diff = lexc[:, None, :] - linc[None, :, :]             # (C, C, K)
    tril = jnp.tril(jnp.ones((c, c), jnp.float32), -1)
    wdiff = jnp.exp(jnp.where(tril[:, :, None] > 0, diff, -jnp.inf))
    scores = jnp.einsum("tk,tsk,sk->ts", rc, wdiff, kc,
                        preferred_element_type=jnp.float32)
    intra = scores @ vc                          # (C, V)

    # current-token bonus
    bonus = jnp.sum(rc * uu[None, :] * kc, axis=-1, keepdims=True) * vc

    out_ref[0, :, 0, :] = (cross + intra + bonus).astype(out_ref.dtype)

    # state update: decay whole chunk + inject decayed keys      (MXU)
    ltot = linc[-1:, :]                          # (1, K)
    kdec = kc * jnp.exp(ltot - linc)             # (C, K)
    state[...] = jnp.exp(ltot[0])[:, None] * st + kdec.T @ vc

    @pl.when(nc == n_chunks - 1)
    def _final():
        sout_ref[0, 0] = state[...].astype(sout_ref.dtype)


def wkv_chunked_pallas(r, k, v, logw, u, state0, *, chunk: int = 64,
                       interpret: bool = False):
    """r,k,logw:(B,S,H,K) v:(B,S,H,V) u:(H,K) state0:(B,H,K,V)
    -> (out (B,S,H,V), state (B,H,K,V)).  S % chunk == 0."""
    b, s, h, kk = r.shape
    vv = v.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    seq_spec = pl.BlockSpec((1, chunk, 1, kk),
                            lambda bi, hi, ci: (bi, ci, hi, 0))
    val_spec = pl.BlockSpec((1, chunk, 1, vv),
                            lambda bi, hi, ci: (bi, ci, hi, 0))
    st_spec = pl.BlockSpec((1, 1, kk, vv), lambda bi, hi, ci: (bi, hi, 0, 0))
    out, sout = pl.pallas_call(
        functools.partial(_kernel, n_chunks=nc),
        grid=(b, h, nc),
        in_specs=[seq_spec, seq_spec, val_spec, seq_spec,
                  pl.BlockSpec((1, kk), lambda bi, hi, ci: (hi, 0)),
                  st_spec],
        out_specs=[val_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((b, s, h, vv), r.dtype),
                   jax.ShapeDtypeStruct((b, h, kk, vv), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((kk, vv), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(r, k, v, logw, u, state0)
    return out, sout
