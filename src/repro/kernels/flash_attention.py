"""Pallas TPU kernel: flash attention (causal / sliding-window / softcap,
GQA-aware) — the MXU form of models/attention.py::_flash.

Grid (B, H, nq, nk) with the kv-chunk dimension innermost/sequential: the
running (m, l, acc) online-softmax state lives in VMEM scratch across kv
chunks, exactly the carry pattern the XLA-level flash expresses through
scan — here the (cq, ck) score tile never leaves VMEM and the causal upper
triangle of chunk pairs is skipped with @pl.when (the XLA scan pays it).

VMEM per step: q/k/v tiles (cq+2ck)·hd + score tile cq·ck + acc cq·hd
floats; cq=ck=256, hd=128 -> ~0.6 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_scr, l_scr, acc_scr, *,
            scale: float, softcap: float, window: int, causal: bool,
            cq: int, ck: int, n_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal skip: kv chunk entirely in the future of this q chunk
    q_last = qi * cq + cq - 1
    k_first = ki * ck
    live = jnp.logical_or(jnp.logical_not(causal), k_first <= q_last)
    if window:
        # and not entirely outside the window
        k_last = ki * ck + ck - 1
        q_first = qi * cq
        live = jnp.logical_and(live, q_first - k_last < window + cq)

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)      # (cq, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)      # (ck, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        qpos = qi * cq + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 0)
        kpos = ki * ck + jax.lax.broadcasted_iota(jnp.int32, (cq, ck), 1)
        ok = jnp.ones((cq, ck), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window:
            ok = jnp.logical_and(ok, qpos - kpos < window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]                             # (cq, 1)
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(ok, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + p @ v
        m_scr[...] = m_new

    @pl.when(ki == n_k - 1)
    def _final():
        out_ref[0, :, 0, :] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(out_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, cq: int = 256,
                           ck: int = 256, interpret: bool = False):
    """q:(B,S,H,hd) k,v:(B,T,Kh,hd) GQA -> (B,S,H,hd).  S%cq==0, T%ck==0."""
    b, s, h, hd = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    cq, ck = min(cq, s), min(ck, t)
    assert s % cq == 0 and t % ck == 0
    nq, nk = s // cq, t // ck
    grid = (b, h, nq, nk)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=hd ** -0.5, softcap=softcap,
                          window=window, causal=causal, cq=cq, ck=ck,
                          n_k=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, cq, 1, hd), lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
            pl.BlockSpec((1, ck, 1, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
            pl.BlockSpec((1, ck, 1, hd),
                         lambda bi, hi, qi, ki, g=g: (bi, ki, hi // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, cq, 1, hd),
                               lambda bi, hi, qi, ki: (bi, qi, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s, h, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((cq, 1), jnp.float32),
            pltpu.VMEM((cq, 1), jnp.float32),
            pltpu.VMEM((cq, hd), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "arbitrary")),
    )(q, k, v)
    return out
