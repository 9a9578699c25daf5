"""Jit'd dispatch wrappers over the Pallas kernels.

``impl`` is explicit and static everywhere: ``'pallas'`` is the compiled
Mosaic kernel (TPU only — anywhere else it fails at lowering, it never
degrades), ``'interpret'`` runs the same kernel body through the Pallas
interpreter (how the CPU tests validate it against the ref.py oracles), and
``'ref'`` is the oracle itself (used for A/B in benchmarks).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref as _ref
from repro.kernels.dot_interaction import dot_interaction as _dot_pallas
from repro.kernels.embedding_bag import embedding_bag as _bag_pallas
from repro.kernels.embedding_bag import embedding_bag_rows as _rows_pallas
from repro.kernels.embedding_bag import embedding_bag_stacked as _bags_pallas
from repro.kernels.flash_attention import flash_attention_pallas as _fa_pallas
from repro.kernels.rwkv6_wkv import wkv_chunked_pallas as _wkv_pallas


def _interpret(impl: str) -> bool:
    """The kernel-backend half of ``impl``: compiled or interpreted."""
    if impl not in ("pallas", "interpret"):
        raise ValueError(f"impl must be 'ref', 'pallas' or 'interpret', "
                         f"got {impl!r}")
    return impl == "interpret"


@functools.partial(jax.jit, static_argnames=("impl", "batch_tile"))
def dot_interaction_op(z, *, impl: str = "pallas", batch_tile: int = 128):
    if impl == "ref":
        return _ref.dot_interaction_ref(z)
    return _dot_pallas(z, batch_tile=batch_tile, interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("impl", "batch_tile",
                                             "row_block", "pool_mode",
                                             "plan_method"))
def embedding_bag_op(table, idx, mask, *, impl: str = "pallas",
                     batch_tile: int = 64, row_block: int = 0,
                     pool_mode: str = "auto", plan=None,
                     plan_method: str = "auto"):
    if impl == "ref":
        return _ref.embedding_bag_ref(table, idx, mask)
    return _bag_pallas(table, idx, mask, batch_tile=batch_tile,
                       row_block=row_block, pool_mode=pool_mode,
                       plan=plan, plan_method=plan_method,
                       interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("impl", "batch_tile",
                                             "row_block", "pool_mode",
                                             "plan_method"))
def embedding_bag_stacked_op(tables, idx, mask, *, impl: str = "pallas",
                             batch_tile: int = 64, row_block: int = 0,
                             pool_mode: str = "auto", plan=None,
                             plan_method: str = "auto"):
    """(T,R,s) stacked embedding bags -> (B,T,s); the model hot path.
    ``row_block`` 0 = auto (VMEM-resident when the table block fits, the
    lane-tile DMA stream otherwise); ``pool_mode`` scalar walk vs
    unrolled vector walk; ``plan`` a precomputed StreamPlan (streamed
    regime, built off the critical path); the kernel pads partial batch
    tiles internally, so any B works."""
    if impl == "ref":
        return _ref.embedding_bag_stacked_ref(tables, idx, mask)
    return _bags_pallas(tables, idx, mask, batch_tile=batch_tile,
                        row_block=row_block, pool_mode=pool_mode,
                        plan=plan, plan_method=plan_method,
                        interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("impl", "row_tile",
                                             "row_block", "pool_mode",
                                             "plan_method"))
def embedding_bag_rows_op(tables, tid, idx, mask, *, impl: str = "pallas",
                          row_tile: int = 64, row_block: int = 0,
                          pool_mode: str = "auto",
                          plan_method: str = "auto"):
    """(N, hot) packed ragged rows pooled against their own tables ->
    (N, s); the pool half of the ragged miss-residual exchange."""
    if impl == "ref":
        return _ref.embedding_bag_rows_ref(tables, tid, idx, mask)
    return _rows_pallas(tables, tid, idx, mask, row_tile=row_tile,
                        row_block=row_block, pool_mode=pool_mode,
                        plan_method=plan_method, interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def rwkv6_wkv_op(r, k, v, logw, u, state0, *, impl: str = "pallas",
                 chunk: int = 64):
    if impl == "ref":
        return _ref.rwkv6_wkv_ref(r, k, v, logw, u, state0)
    return _wkv_pallas(r, k, v, logw, u, state0, chunk=chunk,
                       interpret=_interpret(impl))


@functools.partial(jax.jit, static_argnames=("impl", "causal", "window",
                                             "softcap", "cq", "ck"))
def flash_attention_op(q, k, v, *, impl: str = "pallas", causal: bool = True,
                       window: int = 0, softcap: float = 0.0, cq: int = 256,
                       ck: int = 256):
    return _fa_pallas(q, k, v, causal=causal, window=window,
                      softcap=softcap, cq=cq, ck=ck,
                      interpret=_interpret(impl))
