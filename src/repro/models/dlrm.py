"""DLRM (Naumov et al., arXiv:1906.00091) — the paper's reference model.

Architecture: dense features -> bottom MLP; categorical features -> embedding
bags over (table-parallel) embedding tables; pairwise dot interaction; top MLP
-> CTR logit.

Distribution follows the reference implementation the paper extends: tables
are TABLE-parallel across the ``model`` axis (each member owns T/P whole
tables, padded), each member runs its bags for the WHOLE per-data-row batch,
and the butterfly alltoall (batch split / table concat) hands every member the
full feature set for its 1/P batch slice.  The BLS pipeline wraps exactly this
exchange (``serve_stream``), with bound k as in the paper.

Tables are stacked (T_pad, R_max, s) so the whole sparse arsenal is one
shardable array; real Criteo tables are ragged in R — padding waste is
reported by ``table_stats``.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import DLRMConfig
from repro.core import alltoallv as a2a_mod
from repro.core import bls as bls_mod
from repro.core import integrity as integ_mod
from repro.models import layers as L
from repro.serving import hot_cache as hc_mod
from repro.sharding import partition

# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def padded_tables(cfg: DLRMConfig, n_shards: int) -> int:
    t = cfg.n_tables
    return ((t + n_shards - 1) // n_shards) * n_shards


def padded_rows(cfg: DLRMConfig) -> int:
    """Stack height: the largest table rounded up to whole 128-row lane
    tiles.  On the TPU a row is a lane column of its table, so the padding
    costs no memory, and the streamed kernel can DMA lane-aligned blocks
    (kernels/embedding_bag.py)."""
    lanes = 128
    return -(-max(cfg.table_sizes) // lanes) * lanes


def init_dlrm(key, cfg: DLRMConfig, n_shards: int = 16, mesh=None):
    """Random DLRM params, built on the device under jit (no host copy of
    the stack, no second copy while scaling).  With ``mesh`` the tables
    come out sharded over its ``model`` axis (each device builds only its
    own shard) and the MLPs replicated."""
    t_pad = padded_tables(cfg, n_shards)
    r_max = padded_rows(cfg)
    dt = jnp.dtype(cfg.dtype)

    def mlp_params(key, dims):
        ks = jax.random.split(key, len(dims) - 1)
        return [L.init_dense(ks[i], dims[i], dims[i + 1], cfg.dtype,
                             bias=True) for i in range(len(dims) - 1)]

    def build(key):
        kt, kb, ktop = jax.random.split(key, 3)
        # N.B. a (T_pad, R_max, s) stack; rows beyond a table's true size
        # are never indexed (synthetic data clips indices per true size).
        tables = L.truncated_normal(kt, (t_pad, r_max, cfg.embed_dim),
                                    1.0 / cfg.embed_dim, dt)
        bot_dims = (cfg.n_dense_features, *cfg.bottom_mlp)
        n_feat = cfg.n_tables + 1
        n_inter = n_feat * (n_feat - 1) // 2 \
            if cfg.arch_interaction_op == "dot" else n_feat * cfg.embed_dim
        top_in = n_inter + cfg.embed_dim
        top_dims = (top_in, *cfg.top_mlp)
        return {
            "tables": tables,
            "bot": mlp_params(kb, bot_dims),
            "top": mlp_params(ktop, top_dims),
        }

    if mesh is None:
        return jax.jit(build)(key)
    from jax.sharding import NamedSharding
    shapes = jax.eval_shape(build, key)
    rep = NamedSharding(mesh, P())
    out = jax.tree.map(lambda _: rep, shapes)
    out["tables"] = NamedSharding(mesh, P("model", None, None))
    return jax.jit(build, out_shardings=out)(key)


def dlrm_specs(cfg: DLRMConfig):
    return {
        "tables": ("table_shard", None, None),
        "bot": [L.dense_specs(None, None, bias=True)
                for _ in range(len(cfg.bottom_mlp))],
        "top": [L.dense_specs(None, None, bias=True)
                for _ in range(len(cfg.top_mlp))],
    }


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def apply_mlp(params, x, final_act: Optional[str] = None):
    """Reference DLRM MLP: ReLU between layers; optional sigmoid at the end
    is left to the loss (logits returned)."""
    for i, lp in enumerate(params):
        x = L.dense(lp, x)
        if i < len(params) - 1:
            x = jax.nn.relu(x)
    return x


def resolve_sparse_backend(backend: str) -> str:
    """'auto' -> the native Pallas kernel on TPU, the jnp reference
    elsewhere (interpret mode is for validation, not speed)."""
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend not in ("ref", "pallas", "interpret"):
        raise ValueError(f"unknown sparse_backend {backend!r}")
    return backend


def apply_emb(tables, idx, mask, backend: str = "ref",
              row_block: int = 0, pool_mode: str = "auto", plan=None):
    """Embedding bags.  tables:(T,R,s) idx:(B,T,hot) mask:(B,T,hot)
    -> (B,T,s).  The paper's dominant stage (its Fig. 5 flame graph).

    backend 'ref' is the pure-jnp contraction (materializes each
    table's (B,hot,s) gather); 'pallas' dispatches to the compiled
    stacked-table kernel in kernels/embedding_bag.py, which streams rows
    through VMEM and never builds that intermediate, and 'interpret' runs
    that kernel body through the Pallas interpreter.  ``row_block``
    (cfg.row_block) picks the kernel regime: 0 auto — VMEM-resident table
    blocks when they fit, lane-tile DMA row streaming otherwise;
    ``pool_mode`` (cfg.pool_mode) the scalar walk vs the unrolled
    vector walk (DESIGN.md §1).  ``plan`` consumes a precomputed StreamPlan
    (kernels.embedding_bag.stacked_stream_plan / build_forward_plans) so
    the index-bucketing sort sits off the critical path; the jnp reference
    has no plan to consume, so passing one with backend 'ref' raises."""
    backend = resolve_sparse_backend(backend)
    if backend != "ref":
        from repro.kernels.ops import embedding_bag_stacked_op
        with jax.named_scope("pool"):
            return embedding_bag_stacked_op(tables, idx.astype(jnp.int32),
                                            mask, impl=backend,
                                            row_block=row_block,
                                            pool_mode=pool_mode, plan=plan)
    if plan is not None:
        raise ValueError("apply_emb: a precomputed stream plan only "
                         "applies to the kernel backends, not 'ref'")
    # shared with the kernel oracle so every backend clips OOB ids the
    # same way
    from repro.kernels.ref import embedding_bag_stacked_ref
    with jax.named_scope("pool"):
        return embedding_bag_stacked_ref(tables, idx, mask)


@dataclasses.dataclass
class ExchangeDiag:
    """Per-step exchange diagnostics (the cap autotuner's observation).
    ``live_max``/``drops``/``approx_rows`` are traced scalars; the
    exchange decision and its static geometry ride as pytree metadata so
    the whole object can cross a jit boundary.  ``approx_rows`` is the
    degraded-serving quality ledger: the number of live (sample, table)
    bags whose miss residual was served from the fallback because its
    owning member was excluded (``degraded_members``) — quality loss is
    accounted, never silent."""
    live_max: object        # int32 scalar: max per-(microbatch, dest) live rows
    drops: object           # int32 scalar: rows the cap dropped (0 when dense)
    approx_rows: object = 0  # int32 scalar: bags served from the fallback
    exchange: str = "dense"  # resolved decision: dense | ragged | local
    cap: int = 0
    dense_rows: int = 0     # what the dense butterfly moves per destination


jax.tree_util.register_pytree_node(
    ExchangeDiag,
    lambda d: ((d.live_max, d.drops, d.approx_rows),
               (d.exchange, d.cap, d.dense_rows)),
    lambda meta, leaves: ExchangeDiag(*leaves, *meta))


def apply_emb_rows(tables, tid, idx, mask, backend: str = "ref",
                   row_block: int = 0, pool_mode: str = "auto"):
    """Row-wise embedding bags: tables (T,R,s), tid (N,), idx/mask (N,hot)
    -> (N,s) masked sums.  The packed-ragged analogue of ``apply_emb``: it
    pools ONLY the rows that ride the exchange, so the lookup work shrinks
    from O(B·T·hot) to O(P·cap·hot) gathers along with the wire bytes.
    OOB ids clip exactly like kernels/ref.py so the paths agree.

    Dispatches through the SAME :func:`resolve_sparse_backend` as
    ``apply_emb`` — 'auto'/'interpret'/'pallas' mean the same thing on the
    dense and ragged paths; the kernel form shares the streaming core (and
    both pool modes) of ``embedding_bag_stacked`` (DESIGN.md §1), so
    packed rows of a production-size stack DMA only the row blocks they
    touch."""
    backend = resolve_sparse_backend(backend)
    with jax.named_scope("pool"):
        if backend != "ref":
            from repro.kernels.ops import embedding_bag_rows_op
            return embedding_bag_rows_op(tables, tid.astype(jnp.int32),
                                         idx.astype(jnp.int32), mask,
                                         impl=backend, row_block=row_block,
                                         pool_mode=pool_mode)
        from repro.kernels.ref import embedding_bag_rows_ref
        return embedding_bag_rows_ref(tables, tid, idx, mask)


def resolve_pipeline(pipeline: str, n_shards: int) -> str:
    """Static exchange-pipeline selection (DESIGN.md §7): 'mono' is one
    fused all_to_all per exchange; 'ring' decomposes it into P−1 chunked
    ppermute rounds with per-peer decode/compute overlap.  'auto' goes
    ring at P >= 4 — below that there are at most two ring rounds to
    overlap and the monolithic collective's single issue wins."""
    if pipeline not in ("mono", "ring", "auto"):
        raise ValueError(f"unknown exchange_pipeline {pipeline!r}")
    if pipeline == "auto":
        return "ring" if n_shards >= 4 else "mono"
    return pipeline


def resolve_exchange(exchange: str, *, use_cache: bool, cap: int,
                     dense_rows: int) -> tuple[bool, int]:
    """Static (trace-time) exchange selection -> (use_ragged, cap).

    ``dense_rows`` (= bs · t_loc) is what the equal-split butterfly moves
    per destination; ``cap`` 0 means dense-equivalent (lossless, never
    drops).  The ``auto`` policy goes ragged only when a cache is shrinking
    the live set AND the cap actually undercuts the dense buffer
    (cap · P < B · T per shard): with no cache nearly every row is live, a
    zero-drop cap degenerates to the dense buffer, and the butterfly's
    simpler wire format wins."""
    if exchange not in ("dense", "ragged", "auto"):
        raise ValueError(f"unknown exchange {exchange!r}")
    cap = max(1, min(int(cap), dense_rows)) if cap else dense_rows
    if exchange == "dense":
        return False, cap
    if exchange == "ragged":
        return True, cap
    return bool(use_cache) and cap < dense_rows, cap


def ragged_exchange_pack(tables, idx, miss_mask, *, n_dest: int, cap: int,
                         wire: str = "float32", backend: str = "ref",
                         row_block: int = 0, pool_mode: str = "auto"):
    """Stage-a half of the ragged miss-residual exchange for ONE member.

    idx/miss_mask (B_mb, t_loc, hot) cover this member's LOCAL tables for
    every destination's batch slice (B_mb = n_dest · bs).  Live rows (>=1
    surviving index) are packed into cap-padded per-destination buckets
    BEFORE pooling, only the packed rows are bag-pooled, and the pooled
    vectors are codec-encoded.  Returns (payload, drops) with payload
    {"q" (n_dest, cap, s) [, "scale"], "ids" (n_dest, cap),
    "counts" (n_dest, 1) int32 — already the fused wire's per-destination
    field shape, so the payload fuses as-is}; an id encodes
    sample-within-slice · t_loc + local_table, so the receiver rebuilds the
    dense layout knowing only the source rank.  Ids ship in the narrowest
    dtype addressing the bs·t_loc slots (``slot_id_dtype``: int16 when it
    fits) and are widened only after the exchange."""
    b_mb, t_loc, hot = idx.shape
    bs = b_mb // n_dest
    live = (miss_mask > 0).any(axis=-1)                    # (B_mb, t_loc)
    samp = jnp.arange(b_mb, dtype=jnp.int32)[:, None]
    lt = jnp.arange(t_loc, dtype=jnp.int32)[None, :]
    id_dt = a2a_mod.slot_id_dtype(bs * t_loc)
    ids = ((samp % bs) * t_loc + lt).astype(id_dt)         # (B_mb, t_loc)
    rows = {"idx": idx.reshape(b_mb * t_loc, hot).astype(jnp.int32),
            "mask": miss_mask.reshape(b_mb * t_loc, hot),
            "ids": ids.reshape(-1)}
    # flattened (sample, table) order is destination-grouped (destination
    # = sample // bs), so the sort-free segment pack applies
    packed, counts, drops = a2a_mod.pack_ragged_segments(
        rows, live.reshape(-1), n_dest, cap)
    # dead slots carry ids 0 / mask 0 and pool to an exact zero
    tid = packed["ids"] % t_loc
    pooled = apply_emb_rows(tables, tid.reshape(-1),
                            packed["idx"].reshape(n_dest * cap, hot),
                            packed["mask"].reshape(n_dest * cap, hot),
                            backend=backend, row_block=row_block,
                            pool_mode=pool_mode)
    payload = a2a_mod.encode_wire(
        pooled.reshape(n_dest, cap, -1), wire)
    payload.update(ids=packed["ids"], counts=counts.reshape(n_dest, 1))
    return payload, drops


def ragged_exchange_unpack(recv, *, t_loc: int, bs: int,
                           out_dtype=jnp.float32):
    """Stage-b half: decode + scatter the received buckets back into the
    dense (bs, t_pad, s) layout the interaction expects.  Bucket q came
    from source rank q, which owns global tables [q·t_loc, (q+1)·t_loc);
    rows nobody sent (all-hit / empty bags) stay exactly zero, matching
    what they pool to in the dense exchange.  Narrow wire ids widen to
    int32 here, after the exchange."""
    n_dest, cap = recv["ids"].shape
    t_pad = n_dest * t_loc
    rows = a2a_mod.decode_wire(
        {k: v for k, v in recv.items() if k in ("q", "scale")}, out_dtype)
    ids = recv["ids"].astype(jnp.int32)
    src = jnp.arange(n_dest, dtype=jnp.int32)[:, None]
    samp = ids // t_loc
    table = src * t_loc + ids % t_loc
    flat = samp * t_pad + table
    out = a2a_mod.unpack_ragged(rows, flat, recv["counts"].reshape(-1),
                                bs * t_pad)
    return out.reshape(bs, t_pad, rows.shape[-1])


def dot_interaction(z):
    """z:(B,F,s) -> (B, F(F-1)/2) lower-triangle pairwise dots (the
    reference's interact_features; kernels/dot_interaction.py = Pallas)."""
    b, f, s = z.shape
    zz = jnp.einsum("bfs,bgs->bfg", z, z)
    ii, jj = jnp.tril_indices(f, k=-1)
    return zz[:, ii, jj]


def forward_local(params, cfg: DLRMConfig, dense, idx, mask):
    """Single-device reference forward (oracle for the distributed path)."""
    t = cfg.n_tables
    z0 = apply_mlp(params["bot"], dense)                       # (B, s)
    # pool every table the batch carries (T, or T_pad with empty padding
    # bags) and drop the padding after: slicing a padded stack down to T
    # would copy all of it
    n = idx.shape[1]
    emb = apply_emb(params["tables"][:n], idx, mask,
                    backend=cfg.sparse_backend, row_block=cfg.row_block,
                    pool_mode=cfg.pool_mode)[:, :t]
    z = jnp.concatenate([z0[:, None, :], emb], axis=1)         # (B, T+1, s)
    inter = dot_interaction(z)
    top_in = jnp.concatenate([z0, inter.astype(z0.dtype)], axis=-1)
    return apply_mlp(params["top"], top_in)[..., 0]            # (B,) logit


# ---------------------------------------------------------------------------
# distributed forward (reference-DLRM butterfly over the ``model`` axis)
# ---------------------------------------------------------------------------


def _batch_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def forward_distributed(params, cfg: DLRMConfig, dense, idx, mask, *,
                        bound: int = 0, microbatches: int = 1,
                        unroll: Optional[int] = None,
                        restore_order: bool = True,
                        cache=None, wire_dtype: Optional[str] = None,
                        exchange: Optional[str] = None,
                        ragged_cap: Optional[int] = None,
                        exchange_pipeline: Optional[str] = None,
                        row_block: Optional[int] = None,
                        pool_mode: Optional[str] = None,
                        plan=None,
                        deltas=None,
                        migration=None,
                        repair=None,
                        quarantine=None,
                        wire_flip=None,
                        wire_check: bool = False,
                        table_inv=None,
                        degraded_members: tuple = (),
                        degraded_fallback: str = "zero",
                        return_diag: bool = False):
    """dense:(B, n_dense) idx/mask:(B, T_pad, hot); batch B sharded over
    (pod, data) [dense replicated across ``model`` within a data row, as the
    reference's data loader scatters it]; tables over ``model``.  bound>0
    runs the BLS pipeline over ``microbatches`` slices of the batch (the
    iteration stream); bound=0 + microbatches=1 is the reference synchronous
    step.  Returns (B,) CTR logits in input order (restore_order=False keeps
    pipeline order — microbatch-major — and skips a reshuffle collective).

    ``cache`` (serving/hot_cache.HotCache over the full (T_pad, R, s) stack,
    replicated on every member) moves the skewed head of the access
    distribution off the wire: each member pools the cache HITS of its own
    batch slice locally in stage_a, only the miss residual is bag-pooled
    from the sharded tables and rides the butterfly, and the pooled-hit
    correction is added after the exchange — hits + misses sum to the full
    bag, so the composition changes WHAT is exchanged, never the logits
    (up to fp summation order).  ``wire_dtype`` (default cfg.wire_dtype)
    applies core/alltoallv's codec to the exchanged payload; 'float32' is
    bit-identical to the reference exchange.

    ``exchange`` (default cfg.exchange) selects the collective:  'dense'
    is the equal-split butterfly of the full pooled buffer; 'ragged' packs
    only the live (>=1-miss) rows into ``ragged_cap``-padded
    per-destination buckets and ships them through a counts-aware
    alltoallv (DESIGN.md §6) — the exchanged bytes AND the BLS ring slots
    shrink from O(B·T) to O(P·cap); 'auto' resolves per
    :func:`resolve_exchange`.

    Either way the payload rides the FUSED wire (DESIGN.md §7): every
    leaf — codec rows, scales, slot ids, counts — is bitcast into one
    contiguous ``(P, slot_bytes)`` uint8 buffer per destination, so one
    exchange is exactly one collective and a BLS ring slot is one flat
    leaf.  ``exchange_pipeline`` (default cfg.exchange_pipeline) picks how
    that buffer moves: 'mono' is the single fused all_to_all; 'ring'
    decomposes it into P−1 chunked ppermute rounds consumed per peer
    inside stage_b — round r+1's shift is issued while round r's chunk is
    defused, codec-decoded, scattered and pooled-hit-corrected —
    bit-identical output to 'mono' per codec (disjoint table slices per
    source); 'auto' resolves per :func:`resolve_pipeline`.
    ``row_block`` (default cfg.row_block)
    selects the embedding-bag kernel regime on BOTH pooling paths
    (DESIGN.md §1: 0 auto — VMEM-resident table blocks when they fit,
    lane-tile DMA row streaming otherwise); ``pool_mode`` (default
    cfg.pool_mode) the scalar vs chunked-vector pooling loop.

    ``plan`` consumes the per-(member, microbatch) StreamPlans of
    :func:`build_forward_plans`, built OFF the critical path (the serving
    engine dispatches flush n+1's plan while flush n pools) — stage_a then
    pools straight out of the precomputed buckets and the index sort never
    sits between exchange and pool.  Plans describe the DENSE pooling
    path; combining one with a ragged exchange (whose packed row set is
    data-dependent) raises.  ``return_diag=True`` additionally returns
    {live_max, drops, approx_rows, exchange, cap, dense_rows} — the
    signals the serving cap autotuner and degraded-mode ledger consume.

    ``degraded_members`` (model-axis positions) serves AROUND slow or
    suspect members instead of waiting on them: their table shards'
    exchange contribution is masked out and each affected bag's miss
    residual is served from ``degraded_fallback`` — 'zero' (the residual
    vanishes; cache hits, which never ride the wire, still land) or
    'mean' (the owning table's mean row scaled by the residual weight
    sum; needs the cache layout's replicated idx/mask).  The quality
    loss is never silent: ``approx_rows`` counts exactly the live
    (sample, table) bags served from the fallback.

    ``deltas`` (DESIGN.md §10) threads versioned embedding row updates
    through the SAME fused exchange: a dict of ``(P, microbatches, ...)``
    leaves — ``dvec (…, dcap, s)`` new rows, ``dgid (…, dcap)`` flat
    table·R+row ids, ``dcs`` source-stamped checksums, ``dcnt``/``dver``
    per-slice count and version — built by
    ``runtime.freshness.FreshnessManager.next_wire``.  Each member's
    stage_a repacks its slice by OWNER (``pack_ragged_tree`` into the
    ``"xdelta"`` sub-blob of the wire layout; a slice holds ≤ dcap rows,
    so the dcap-cap buckets can never drop), the exchange moves it for
    free (one extra WireField, zero extra collectives), and stage_b
    returns each member's harvested per-source buckets as an extra
    ``staged`` output — the FORWARD never mutates tables; the atomic
    apply between flushes does, which is what keeps a degraded member
    serving its last-good version instead of blocking traffic.

    ``migration`` (DESIGN.md §11) threads live-resharding row shipments
    through the same fused exchange as a SECOND rider field, ``"xmig"``:
    a dict of ``(P, microbatches, ...)`` leaves — ``mgid (…, mcap)``
    flat ORIGINAL table·R+row ids of rows the member currently owns,
    ``mdst (…, mcap)`` the future owner each row ships to, ``mcnt``/
    ``mepoch`` per-slice count and migration epoch — built by
    ``runtime.reshard.ReshardExecutor.next_wire``.  Each member's
    stage_a GATHERS the row vectors from its own table shard on device,
    stamps per-row checksums (the freshness path's ``row_checksum``
    fold, computed on device over the exact bytes that ship), repacks by
    destination and fuses into the ``"xmig"`` sub-blob; stage_b returns
    the harvested per-source buckets as an extra staged output.  Zero
    extra collectives, and the forward never mutates tables — the
    executor banks, verifies and commits on the host between flushes.

    ``repair`` (DESIGN.md §12) threads integrity-repair rows through the
    same fused exchange as a THIRD rider field, ``"xrep"``: a dict of
    ``(P, microbatches, ...)`` leaves — ``rvec (…, rcap, s)`` known-good
    rows from the host-side authoritative mirror, ``rgid (…, rcap)``
    flat ORIGINAL table·R+row ids, ``rcs`` mirror-stamped checksums,
    ``rcnt`` per-slice counts — built by
    ``runtime.scrub.Scrubber.next_wire``.  Each member's stage_a repacks
    its slice by the quarantined row's OWNER and fuses it into the
    ``"xrep"`` sub-blob; stage_b returns the harvested per-source
    buckets as an extra staged output.  Zero extra collectives, and the
    forward never mutates tables — the scrubber verifies and commits on
    the host between flushes.

    ``quarantine`` is a replicated ``(Q,)`` int32 array of PHYSICAL flat
    gids (slot·R + row, −1 padding) currently under quarantine: their
    bag contributions are mask-excluded at the top of the shard — the
    zero-fallback degraded serving of PR 6, at row rather than member
    granularity — on BOTH the cache-hit and the miss-residual path, so
    a corrupt row is never served while its repair is in flight.  Rides
    the jitted step as a dynamic arg: quarantining/repairing rows never
    retraces.

    ``wire_check=True`` adds the ``"wcs"`` segment checksum to the fused
    layout: stage_a stamps every destination slot after fusing, stage_b
    verifies each received segment (mono: per source row; ring: per
    chunk) and ZEROES a corrupt source's entire embedding contribution
    for that microbatch (its riders are independently checksummed and
    count-clamped host-side), returning a per-source corrupt-flag leaf
    the engine escalates through the confirm → degrade → evict ladder.
    ``wire_flip`` is the matching fault hook: a replicated ``(P, P)``
    uint8 array; entry (src, dst) != 0 makes member src XOR one payload
    byte of its slot to dst after stamping — XOR with 0 is the identity,
    so the clean path stays bit-exact with the hook armed.

    ``table_inv`` activates a non-identity table PLACEMENT (DESIGN.md
    §11): a replicated ``(T_pad,)`` int32 array mapping original table
    id -> physical slot (column of idx/mask, stack position of the
    sharded tables).  The caller permutes idx/mask/tables/cache into
    physical order; the forward only (a) routes delta rows to
    ``inv[gid // R] // t_loc`` instead of ``(gid // R) // t_loc`` and
    (b) un-permutes the exchanged table columns right before
    ``dot_interaction`` — a traced gather, so a cutover swaps the array
    without retracing.  ``None`` keeps every code path bit-identical to
    the pre-placement forward.
    """
    mesh = partition.current_mesh()
    if deltas is not None and (mesh is None
                               or "model" not in mesh.axis_names):
        raise ValueError(
            "forward_distributed: deltas ride the model-axis exchange — "
            "install a model mesh via partition.axis_rules")
    if migration is not None and (mesh is None
                                  or "model" not in mesh.axis_names):
        raise ValueError(
            "forward_distributed: migration rows ride the model-axis "
            "exchange — install a model mesh via partition.axis_rules")
    if (repair is not None or wire_check) and (
            mesh is None or "model" not in mesh.axis_names):
        raise ValueError(
            "forward_distributed: repair rows / wire verification ride "
            "the model-axis exchange — install a model mesh via "
            "partition.axis_rules")
    if mesh is None or "model" not in mesh.axis_names:
        if cache is not None or (wire_dtype or cfg.wire_dtype) != "float32":
            import warnings
            warnings.warn(
                "forward_distributed: no model-axis mesh installed — "
                "falling back to forward_local; cache/wire_dtype are "
                "inactive (install one via partition.axis_rules)",
                stacklevel=2)
        logits = forward_local(params, cfg, dense, idx, mask)
        if return_diag:
            return logits, ExchangeDiag(jnp.int32(0), jnp.int32(0),
                                        jnp.int32(0), "local")
        return logits
    n_shards = mesh.shape["model"]
    baxes = _batch_axes(mesh)
    mb = microbatches
    wire = wire_dtype if wire_dtype is not None else cfg.wire_dtype
    backend = cfg.sparse_backend
    rblk = row_block if row_block is not None else cfg.row_block
    pool = pool_mode if pool_mode is not None else cfg.pool_mode
    use_cache = cache is not None and cache.cache_rows > 0
    if use_cache and cache.slot_of.shape[0] != idx.shape[1]:
        raise ValueError(
            f"cache covers {cache.slot_of.shape[0]} tables but idx has "
            f"{idx.shape[1]} (padded) — build the cache over the full "
            f"(T_pad, R, s) stack")
    emb_dtype = params["tables"].dtype
    # static exchange selection: per-destination rows of the dense
    # butterfly vs the requested bucket cap
    n_data = 1
    for a in baxes:
        n_data *= mesh.shape[a]
    t_loc_g = idx.shape[1] // n_shards
    bs_g = dense.shape[0] // (n_data * mb * n_shards)
    dense_rows = bs_g * t_loc_g
    use_ragged, cap = resolve_exchange(
        exchange if exchange is not None else cfg.exchange,
        use_cache=use_cache,
        cap=ragged_cap if ragged_cap is not None else cfg.ragged_cap,
        dense_rows=dense_rows)
    pipe = resolve_pipeline(
        exchange_pipeline if exchange_pipeline is not None
        else cfg.exchange_pipeline, n_shards)
    has_delta = deltas is not None
    dcap = int(deltas["dgid"].shape[-1]) if has_delta else 0
    dlayout = a2a_mod.delta_wire_layout(
        n_shards, dcap, params["tables"].shape[2], emb_dtype) \
        if has_delta else None
    has_mig = migration is not None
    mcap = int(migration["mgid"].shape[-1]) if has_mig else 0
    mlayout = a2a_mod.mig_wire_layout(
        n_shards, mcap, params["tables"].shape[2], emb_dtype) \
        if has_mig else None
    has_rep = repair is not None
    rcap = int(repair["rgid"].shape[-1]) if has_rep else 0
    rlayout = a2a_mod.rep_wire_layout(
        n_shards, rcap, params["tables"].shape[2], emb_dtype) \
        if has_rep else None
    has_quar = quarantine is not None
    has_inv = table_inv is not None
    # the ONE static layout both exchange halves (and the BLS ring slot)
    # agree on: the whole payload as a (P, slot_bytes) uint8 buffer —
    # delta rows and migrating rows included, as the opaque "xdelta" /
    # "xmig" byte fields
    layout = a2a_mod.exchange_wire_layout(
        ragged=use_ragged, n_dest=n_shards, cap=cap, bs=bs_g,
        t_loc=t_loc_g, embed_dim=params["tables"].shape[2],
        wire_dtype=wire, emb_dtype=emb_dtype,
        delta_bytes=dlayout.slot_bytes if has_delta else 0,
        mig_bytes=mlayout.slot_bytes if has_mig else 0,
        rep_bytes=rlayout.slot_bytes if has_rep else 0,
        wire_check=wire_check)
    if wire_check and wire_flip is None:
        # the injection hook is a dynamic arg so arming/disarming a
        # corruption never retraces; default = all-zeros = identity
        wire_flip = jnp.zeros((n_shards, n_shards), jnp.uint8)
    if plan is not None and use_ragged:
        raise ValueError(
            "forward_distributed: precomputed stream plans describe the "
            "dense pooling path; the ragged exchange packs a data-"
            "dependent row set per step and plans its own buckets — "
            "build plans only when the exchange resolves dense")
    has_plan = plan is not None
    deg = tuple(sorted({int(d) for d in degraded_members}))
    fb_rows = None
    if deg:
        if degraded_fallback not in ("zero", "mean"):
            raise ValueError(
                f"unknown degraded_fallback {degraded_fallback!r}")
        if any(d < 0 or d >= n_shards for d in deg):
            raise ValueError(
                f"degraded_members {deg} out of range for {n_shards} "
                "members")
        if len(deg) >= n_shards:
            raise ValueError(
                "forward_distributed: every member degraded — nothing "
                "would serve the exchange; evict instead")
        if degraded_fallback == "mean":
            if not use_cache:
                raise ValueError(
                    "degraded_fallback='mean' needs the cache layout: the "
                    "fallback weight sums come from each member's own "
                    "replicated (idx, mask) slice over ALL tables, which "
                    "only the cache path ships — use 'zero' or serve "
                    "with a cache")
            # per-table profile row (replicated): what a deployment keeps
            # as the cold-start embedding — bag ~= mean row * weight sum
            fb_rows = params["tables"].astype(jnp.float32).mean(axis=1) \
                .astype(emb_dtype)
    deg_mask = [1 if i in deg else 0 for i in range(n_shards)]

    def shard_fn(tables, bot, top, dense_s, idx_s, mask_s, *extra):
        # per-shard shapes: tables (t_loc,R,s); dense (B_row, n_dense)
        # replicated over model; idx/mask (B_row, t_loc, hot) — or
        # (B_row, t_pad, hot) replicated when the cache path needs every
        # member to see its own batch slice across ALL tables.
        m = jax.lax.axis_index("model")
        t_loc = tables.shape[0]
        b_row = dense_s.shape[0]
        bs = b_row // (mb * n_shards)  # rows per (microbatch, member)
        # positional unpacking of the optional extras, in append order:
        # cache (2) | fb_rows (1) | plan (1) | deltas (1) | migration (1)
        # | repair (1) | quarantine (1) | wire_flip (1) | table_inv (1)
        ei = 0
        cache_args = ()
        if use_cache:
            cache_args = extra[:2]
            ei = 2
        fbr = None
        if fb_rows is not None:
            fbr = extra[ei]
            ei += 1
        # member plan: strip the model-slot axis -> leaves (mb, tiles, ...)
        plan_s = None
        if has_plan:
            plan_s = jax.tree.map(lambda a: a[0], extra[ei])
            ei += 1
        # member delta slices: strip the model-slot axis -> (mb, dcap, ...)
        deltas_s = None
        if has_delta:
            deltas_s = jax.tree.map(lambda a: a[0], extra[ei])
            ei += 1
        # member migration slices: strip the model-slot axis
        mig_s = None
        if has_mig:
            mig_s = jax.tree.map(lambda a: a[0], extra[ei])
            ei += 1
        # member repair slices: strip the model-slot axis
        rep_s = None
        if has_rep:
            rep_s = jax.tree.map(lambda a: a[0], extra[ei])
            ei += 1
        # quarantined PHYSICAL gids (replicated, −1 padding)
        qgids_s = None
        if has_quar:
            qgids_s = extra[ei]
            ei += 1
        # wire-corruption injection matrix (replicated)
        wflip_s = None
        if wire_check:
            wflip_s = extra[ei]
            ei += 1
        # original table -> physical slot (replicated; identity when the
        # placement is trivial but migration still needs the array)
        inv_s = None
        if has_inv:
            inv_s = extra[ei]
            ei += 1
        elif has_mig or has_rep:
            inv_s = jnp.arange(n_shards * t_loc, dtype=jnp.int32)

        if has_quar:
            # quarantine mask (DESIGN.md §12): exclude every index that
            # resolves to a quarantined PHYSICAL row from its bag — the
            # zero fallback of PR 6's degraded serving at row granularity,
            # applied BEFORE the cache/residual split so neither the
            # cached copy nor the resident row of a corrupt gid is ever
            # served while its repair is in flight.  idx columns are
            # physical slots: the full stack when the cache path
            # replicates idx/mask, this member's t_loc block otherwise.
            r_rows = tables.shape[1]
            col0 = jnp.int32(0) if use_cache else m * t_loc
            colt = col0 + jnp.arange(idx_s.shape[1], dtype=jnp.int32)
            gid_b = (colt[None, :, None] * r_rows
                     + idx_s.astype(jnp.int32))         # (B_row, t, hot)
            quar = (gid_b[..., None] == qgids_s[None, None, None, :]) \
                .any(-1)
            mask_s = mask_s * (~quar).astype(mask_s.dtype)

        def local_miss(ix, mk):
            """This member's local-table (idx, residual mask) slice."""
            if not use_cache:
                return ix, mk
            _, slot_of = cache_args
            ix_loc = jax.lax.dynamic_slice_in_dim(ix, m * t_loc, t_loc,
                                                  axis=1)
            mk_loc = jax.lax.dynamic_slice_in_dim(mk, m * t_loc, t_loc,
                                                  axis=1)
            slot_loc = jax.lax.dynamic_slice_in_dim(slot_of, m * t_loc,
                                                    t_loc, axis=0)
            return ix_loc, hc_mod.miss_mask_of(slot_loc, ix_loc, mk_loc)

        def pack_delta(dx):
            """One (member, microbatch) delta slice -> the per-destination
            "xdelta" sub-blob: route each valid row to its OWNING member
            (the row's table's PHYSICAL slot // t_loc — gids stay in
            original space on the wire; placement only redirects them),
            repack into dcap-cap buckets (a slice holds <= dcap rows, so
            drops are structurally impossible) and fuse per the
            sub-layout.  Checksums ride verbatim — stamped at the source,
            verified by the receiving HOST."""
            r_rows = tables.shape[1]
            n_valid = dx["dcnt"].reshape(())
            valid = jnp.arange(dcap, dtype=jnp.int32) < n_valid
            gid = dx["dgid"].astype(jnp.int32)
            phys = gid // r_rows if inv_s is None \
                else jnp.take(inv_s, gid // r_rows, mode="clip")
            dest = jnp.where(valid, phys // t_loc, -1)
            bk, cnts, _ = a2a_mod.pack_ragged_tree(
                {"dvec": dx["dvec"].astype(emb_dtype), "dgid": gid,
                 "dcs": dx["dcs"]}, dest, n_shards, dcap)
            ver = jnp.broadcast_to(dx["dver"].reshape(1, 1),
                                   (n_shards, 1)).astype(jnp.int32)
            return a2a_mod.fuse_wire(
                {"dvec": bk["dvec"], "dgid": bk["dgid"], "dcs": bk["dcs"],
                 "dcnt": cnts.reshape(n_shards, 1), "dver": ver}, dlayout)

        # device-side stamp: the shared fold from core/integrity (uint32
        # wraparound, congruent mod 2^32 to the host's uint64-then-mask,
        # so the receiving host verifies with the numpy original)
        mig_checksum = integ_mod.row_checksum_device

        def pack_mig(mx):
            """One (member, microbatch) migration slice -> the
            per-destination "xmig" sub-blob: the CURRENT owner gathers
            each valid row's vector from its own table shard (original
            gid -> physical slot via ``inv`` -> local slot on this
            member), stamps the checksum on device over the exact bytes
            that ship, routes by the row's FUTURE owner (``mdst``) and
            fuses per the sub-layout.  A slice holds <= mcap rows, so
            the mcap-cap buckets can never drop."""
            r_rows = tables.shape[1]
            n_valid = mx["mcnt"].reshape(())
            valid = jnp.arange(mcap, dtype=jnp.int32) < n_valid
            gid = mx["mgid"].astype(jnp.int32)
            phys = jnp.take(inv_s, gid // r_rows, mode="clip")
            # local gather: the executor only fills rows THIS member owns,
            # so phys - m*t_loc lands in [0, t_loc); jnp clamps the
            # excluded rows' indices harmlessly
            vec = tables[jnp.clip(phys - m * t_loc, 0, t_loc - 1),
                         gid % r_rows]
            epoch = jnp.broadcast_to(mx["mepoch"].reshape(1),
                                     (mcap,)).astype(jnp.int32)
            cs = mig_checksum(vec, gid, epoch)
            dest = jnp.where(valid, mx["mdst"].astype(jnp.int32), -1)
            bk, cnts, _ = a2a_mod.pack_ragged_tree(
                {"mvec": vec.astype(emb_dtype), "mgid": gid, "mcs": cs},
                dest, n_shards, mcap)
            ep = jnp.broadcast_to(mx["mepoch"].reshape(1, 1),
                                  (n_shards, 1)).astype(jnp.int32)
            return a2a_mod.fuse_wire(
                {"mvec": bk["mvec"], "mgid": bk["mgid"], "mcs": bk["mcs"],
                 "mcnt": cnts.reshape(n_shards, 1), "mepoch": ep}, mlayout)

        def pack_rep(rx):
            """One (member, microbatch) repair slice -> the
            per-destination "xrep" sub-blob: route each valid mirror row
            to the OWNER of its quarantined physical slot (same
            original-gid → ``inv`` → owner routing as the delta path),
            repack into rcap-cap buckets (a slice holds <= rcap rows, so
            drops are structurally impossible) and fuse per the
            sub-layout.  Checksums ride verbatim — stamped by the host
            mirror, verified by the receiving HOST before apply."""
            r_rows = tables.shape[1]
            n_valid = rx["rcnt"].reshape(())
            valid = jnp.arange(rcap, dtype=jnp.int32) < n_valid
            gid = rx["rgid"].astype(jnp.int32)
            phys = gid // r_rows if inv_s is None \
                else jnp.take(inv_s, gid // r_rows, mode="clip")
            dest = jnp.where(valid, phys // t_loc, -1)
            bk, cnts, _ = a2a_mod.pack_ragged_tree(
                {"rvec": rx["rvec"].astype(emb_dtype), "rgid": gid,
                 "rcs": rx["rcs"]}, dest, n_shards, rcap)
            return a2a_mod.fuse_wire(
                {"rvec": bk["rvec"], "rgid": bk["rgid"], "rcs": bk["rcs"],
                 "rcnt": cnts.reshape(n_shards, 1)}, rlayout)

        @jax.named_scope("stage_a")
        def stage_a(x):
            j, d, ix, mk = x[:4]
            xi = 4
            plan_j = None
            if has_plan:
                plan_j = x[xi]
                xi += 1
            delta_j = None
            if has_delta:
                delta_j = x[xi]
                xi += 1
            mig_j = None
            if has_mig:
                mig_j = x[xi]
                xi += 1
            rep_j = x[xi] if has_rep else None
            ix_loc, miss_mk = local_miss(ix, mk)
            if use_cache:
                hot_rows, slot_of = cache_args
                # member m's own batch slice over ALL tables: pool the
                # cache hits locally from the replicated hot block
                ix_m = jax.lax.dynamic_slice_in_dim(ix, m * bs, bs, axis=0)
                mk_m = jax.lax.dynamic_slice_in_dim(mk, m * bs, bs, axis=0)
                hits_m = hc_mod.pooled_hits_of(hot_rows, slot_of, ix_m,
                                               mk_m).astype(emb_dtype)
                if deg and fb_rows is not None:
                    # fold the mean-row fallback into the post-exchange
                    # hit correction: degraded tables' residuals never
                    # arrive, so approximate each as mean_row * (residual
                    # weight sum) — zero exactly where nothing was live
                    w = hc_mod.miss_mask_of(slot_of, ix_m, mk_m).sum(-1)
                    dcol = jnp.repeat(jnp.asarray(deg_mask, w.dtype),
                                      t_loc)
                    hits_m = hits_m + ((w * dcol)[..., None]
                                       * fbr[None]).astype(emb_dtype)
            else:
                hits_m = jnp.zeros((bs, 0, 0), emb_dtype)  # empty side slot
            if use_ragged:
                # pack the live rows first, pool only what ships
                payload, _ = ragged_exchange_pack(
                    tables, ix_loc, miss_mk, n_dest=n_shards, cap=cap,
                    wire=wire, backend=backend, row_block=rblk,
                    pool_mode=pool)
            else:
                pooled = apply_emb(tables, ix_loc, miss_mk, backend,
                                   row_block=rblk, pool_mode=pool,
                                   plan=plan_j)
                # destination-major: all_to_all's split groups are the
                # leading bs-row blocks, a free reshape
                payload = jax.tree.map(
                    lambda a: a.reshape(n_shards, bs, *a.shape[1:]),
                    a2a_mod.encode_wire(pooled, wire))
            if has_delta:
                payload["xdelta"] = pack_delta(delta_j)
            if has_mig:
                payload["xmig"] = pack_mig(mig_j)
            if has_rep:
                payload["xrep"] = pack_rep(rep_j)
            if wire_check:
                payload["wcs"] = jnp.zeros((n_shards, 1), jnp.uint32)
            # one flat uint8 leaf per destination: the whole exchange is
            # one collective, and the BLS ring buffers a single array
            buf = a2a_mod.fuse_wire(payload, layout)
            if wire_check:
                # stamp each destination slot's segment checksum, THEN
                # apply the injected corruption (XOR one payload byte
                # outside the wcs field; XOR 0 is the identity, so the
                # clean path is bit-exact with the hook armed) — the
                # receiver's verify must catch the flip
                buf = integ_mod.wire_stamp(buf, layout)
                fb = next(f.offset for f in layout.fields
                          if f.name != "wcs")
                buf = buf.at[:, fb].set(buf[:, fb] ^ wflip_s[m])
            # member m's dense rows of microbatch j (matches a2a delivery)
            dm = jax.lax.dynamic_slice_in_dim(d, m * bs, bs, axis=0)
            z0 = apply_mlp(bot, dm)                   # (bs, s)
            return buf, (z0, hits_m)

        def collective(buf):
            if pipe == "ring":
                # the exchange is deferred to stage_b's ppermute rounds:
                # the send buffer itself rides the ring slot, so each
                # peer's chunk is decoded the moment it lands instead of
                # after the whole collective
                return buf
            # the fused butterfly: ONE all_to_all moves codec rows,
            # scales, ids and counts together
            return a2a_mod.alltoallv_fused(buf, "model")

        def chunk_slice(chunk, hits, src, wok=None):
            """One source's contribution as its dense (bs, t_loc, s)
            table slice: defuse + codec-decode (+ ragged scatter) + that
            source's pooled-hit correction.  Sources own disjoint table
            ranges, so per-peer consumption composes bit-identically to
            the monolithic defuse.  ``wok`` (wire_check only) is this
            chunk's segment-verify flag: a corrupt chunk's contribution
            is zeroed — jnp.where, not a multiply, because corrupt bytes
            may decode to NaN and NaN·0 is NaN."""
            f = a2a_mod.defuse_wire(chunk, layout)
            if use_ragged and wok is not None:
                # containment: a corrupt chunk's slot ids are garbage —
                # zeroing its count keeps the scatter from landing rows
                # anywhere at all
                f = dict(f)
                f["counts"] = f["counts"] * wok.astype(f["counts"].dtype)
            if use_ragged:
                # the chunk is a one-source exchange: with n_dest=1 the
                # shared unpack's flat slot reduces to exactly the
                # shipped id (samp·t_loc + local_table), so the id
                # contract lives in ONE place
                sl = ragged_exchange_unpack(
                    jax.tree.map(lambda a: a[None], f), t_loc=t_loc,
                    bs=bs, out_dtype=emb_dtype)
            else:
                sl = a2a_mod.decode_wire(f, emb_dtype)   # (bs, t_loc, s)
            if deg:
                # src is TRACED in the ring schedule — mask against a
                # constant member vector, not a Python membership test
                sl = jnp.where(jnp.asarray(deg_mask, jnp.bool_)[src],
                               jnp.zeros_like(sl), sl)
            if wok is not None:
                sl = jnp.where(wok, sl, jnp.zeros_like(sl))
            if use_cache:
                # hits never rode the wire: they land even for a
                # rejected segment (same semantics as degraded serving)
                sl = sl + jax.lax.dynamic_slice_in_dim(
                    hits, src * t_loc, t_loc, axis=1)
            return sl

        def delta_of(chunk):
            """The "xdelta" sub-blob of one source's chunk, defused into
            its harvested leaves (dcap rows destined to THIS member)."""
            return a2a_mod.defuse_wire(
                a2a_mod.defuse_wire(chunk, layout)["xdelta"], dlayout)

        def mig_of(chunk):
            """The "xmig" sub-blob of one source's chunk, defused into
            its harvested leaves (mcap migrating rows whose FUTURE owner
            is this member)."""
            return a2a_mod.defuse_wire(
                a2a_mod.defuse_wire(chunk, layout)["xmig"], mlayout)

        def rep_of(chunk):
            """The "xrep" sub-blob of one source's chunk, defused into
            its harvested leaves (rcap repair rows for quarantined rows
            THIS member owns)."""
            return a2a_mod.defuse_wire(
                a2a_mod.defuse_wire(chunk, layout)["xrep"], rlayout)

        @jax.named_scope("stage_b")
        def stage_b(recv, side):
            z0, hits = side
            staged = staged_m = staged_r = wbad = None
            if has_delta:
                # per-source harvest buckets this member will hand its
                # host: (P_src, dcap, ...) per delta sub-field
                staged = {f.name: jnp.zeros((n_shards,) + f.shape, f.dtype)
                          for f in dlayout.fields}
            if has_mig:
                staged_m = {f.name: jnp.zeros((n_shards,) + f.shape,
                                              f.dtype)
                            for f in mlayout.fields}
            if has_rep:
                staged_r = {f.name: jnp.zeros((n_shards,) + f.shape,
                                              f.dtype)
                            for f in rlayout.fields}
            if wire_check:
                # per-source corrupt-segment flags, harvested by the host
                # like the riders (NO psum: collective counts are a gate)
                wbad = jnp.zeros((n_shards,), jnp.int32)
            if pipe == "ring":
                # chunked ppermute butterfly: round r+1's shift is in
                # flight while round r's chunk is defused, decoded,
                # scattered and hit-corrected into its table slice
                def consume(out, src, chunk):
                    emb, stg, stg_m, stg_r, wb = out
                    wok = None
                    if wire_check:
                        wok = integ_mod.wire_verify(chunk, layout)
                        wb = wb.at[src].set((~wok).astype(jnp.int32))
                    emb = jax.lax.dynamic_update_slice_in_dim(
                        emb, chunk_slice(chunk, hits, src, wok),
                        src * t_loc, axis=1)
                    if has_delta:
                        dd = delta_of(chunk)
                        stg = {k: stg[k].at[src].set(dd[k]) for k in stg}
                    if has_mig:
                        mm = mig_of(chunk)
                        stg_m = {k: stg_m[k].at[src].set(mm[k])
                                 for k in stg_m}
                    if has_rep:
                        rr = rep_of(chunk)
                        stg_r = {k: stg_r[k].at[src].set(rr[k])
                                 for k in stg_r}
                    return emb, stg, stg_m, stg_r, wb

                init = jnp.zeros((bs, n_shards * t_loc,
                                  layout.field("q").shape[-1]), emb_dtype)
                emb_all, staged, staged_m, staged_r, wbad = \
                    a2a_mod.ring_exchange(
                        recv, "model", n_shards, consume,
                        (init, staged, staged_m, staged_r, wbad))
            else:
                f = a2a_mod.defuse_wire(recv, layout)
                wok_v = None
                if wire_check:
                    wok_v = integ_mod.wire_verify(recv, layout)  # (P,)
                    wbad = (~wok_v).astype(jnp.int32)
                    if use_ragged:
                        # containment: corrupt sources' slot ids are
                        # garbage and the mono scatter spans ALL sources'
                        # slots — zero their counts so nothing lands
                        f = dict(f)
                        f["counts"] = (f["counts"]
                                       * wok_v.astype(f["counts"].dtype)
                                       [:, None])
                if has_delta:
                    # (P_src, sub_slot_bytes) -> per-source harvest leaves
                    staged = a2a_mod.defuse_wire(f["xdelta"], dlayout)
                if has_mig:
                    staged_m = a2a_mod.defuse_wire(f["xmig"], mlayout)
                if has_rep:
                    staged_r = a2a_mod.defuse_wire(f["xrep"], rlayout)
                if use_ragged:
                    emb_all = ragged_exchange_unpack(
                        f, t_loc=t_loc, bs=bs, out_dtype=emb_dtype)
                else:
                    # (P, bs, t_loc, s) source-major -> (bs, t_pad, s)
                    q = a2a_mod.decode_wire(f, emb_dtype)
                    emb_all = q.transpose(1, 0, 2, 3).reshape(
                        bs, n_shards * t_loc, q.shape[-1])
                if deg:
                    # drop degraded sources' table columns (x * 1.0 is
                    # bit-exact for the survivors)
                    keep = 1 - jnp.asarray(deg_mask, emb_all.dtype)
                    emb_all = emb_all * jnp.repeat(keep, t_loc)[None, :,
                                                                None]
                if wire_check:
                    # zero corrupt sources' columns (jnp.where: corrupt
                    # bytes may decode to NaN)
                    keep_w = jnp.repeat(wok_v, t_loc)[None, :, None]
                    emb_all = jnp.where(keep_w, emb_all,
                                        jnp.zeros_like(emb_all))
                if use_cache:
                    emb_all = emb_all + hits          # pooled-hit correction
            t = cfg.n_tables
            # placement: exchanged columns are PHYSICAL slots; gather the
            # real tables back into original order for the interaction
            # (identity placement keeps the bit-exact static slice)
            emb_t = jnp.take(emb_all, inv_s[:t], axis=1) if has_inv \
                else emb_all[:, :t]
            z = jnp.concatenate([z0[:, None, :], emb_t], axis=1)
            inter = dot_interaction(z)
            top_in = jnp.concatenate([z0, inter.astype(z0.dtype)], axis=-1)
            logits = apply_mlp(top, top_in)[..., 0]
            stg = ((staged,) * has_delta + (staged_m,) * has_mig
                   + (staged_r,) * has_rep + (wbad,) * wire_check)
            return (logits,) + stg if stg else logits

        def split(a):  # (B_row, ...) -> (mb, B_row/mb, ...)
            return a.reshape(mb, a.shape[0] // mb, *a.shape[1:])

        # live-count / drop diagnostics for the serving cap autotuner:
        # elementwise work independent of the pipeline schedule, reduced to
        # replicated scalars (max per-(microbatch, destination) live rows
        # seen anywhere; rows the cap would drop).  Only traced when the
        # caller asked — the re-probe and the two collectives are pure
        # overhead on the training / parity paths.
        diag = ()
        if return_diag:
            axes_all = ("model",) + baxes
            _, miss_all = local_miss(idx_s, mask_s)
            live = (miss_all > 0).any(-1)
            cnt = live.reshape(mb, n_shards, bs, t_loc) \
                .sum((2, 3)).astype(jnp.int32)
            live_max = jax.lax.pmax(jnp.max(cnt), axes_all)
            drops_l = jnp.sum(jnp.maximum(cnt - cap, 0)) if use_ragged \
                else jnp.int32(0)
            # degraded ledger: every live residual bag on a degraded
            # member's shard was served from the fallback — count them on
            # the owning rank, sum across the pod
            approx_l = (live.sum().astype(jnp.int32)
                        * jnp.asarray(deg_mask, jnp.int32)[m]) if deg \
                else jnp.int32(0)
            diag = (live_max, jax.lax.psum(drops_l, axes_all),
                    jax.lax.psum(approx_l, axes_all))

        js = jnp.arange(mb, dtype=jnp.int32)
        xs = (js, split(dense_s), split(idx_s), split(mask_s))
        if has_plan:
            xs = xs + (plan_s,)        # leaves already microbatch-major
        if has_delta:
            xs = xs + (deltas_s,)      # leaves (mb, dcap, ...)
        if has_mig:
            xs = xs + (mig_s,)         # leaves (mb, mcap, ...)
        if has_rep:
            xs = xs + (rep_s,)         # leaves (mb, rcap, ...)
        n_riders = (int(has_delta) + int(has_mig) + int(has_rep)
                    + int(wire_check))
        if bound == 0 and mb == 1:
            payload, side = stage_a(jax.tree.map(lambda a: a[0], xs))
            res = stage_b(collective(payload), side)
            if n_riders:
                lg, *stg = res
                # + microbatch and model-slot axes for the out_specs
                return (lg[None],) + diag + tuple(
                    jax.tree.map(lambda a: a[None, None], s) for s in stg)
            return (res[None],) + diag
        outs, _ = bls_mod.bls_pipeline(stage_a, collective, stage_b, xs,
                                       bound, unroll=unroll)
        if n_riders:
            lg, *stg = outs            # staged leaves (mb, P_src, ...)
            return (lg,) + diag + tuple(
                jax.tree.map(lambda a: a[None], s) for s in stg)
        return (outs,) + diag  # (mb, bs) [, scalar, scalar]

    sparse_spec = (P(baxes if baxes else None, None, None) if use_cache
                   else P(baxes if baxes else None, "model", None))
    in_specs = [P("model", None, None),
                jax.tree.map(lambda _: P(), params["bot"]),
                jax.tree.map(lambda _: P(), params["top"]),
                P(baxes if baxes else None, None),
                sparse_spec,
                sparse_spec]
    args = [params["tables"], params["bot"], params["top"], dense, idx, mask]
    if use_cache:
        in_specs += [P(), P()]              # hot block replicated everywhere
        args += [cache.hot_rows, cache.slot_of]
    if fb_rows is not None:
        in_specs += [P(None, None)]         # profile rows replicated
        args += [fb_rows]
    if has_plan:
        # plan leaves are model-major on axis 0, (data-row, microbatch)-
        # major on axis 1 — exactly what build_forward_plans emits
        in_specs += [jax.tree.map(
            lambda _: P("model", baxes if baxes else None), plan)]
        args += [plan]
    if has_delta:
        # delta slices are model-major on axis 0: member m's (mb, ...) rows
        in_specs += [jax.tree.map(lambda _: P("model"), deltas)]
        args += [deltas]
    if has_mig:
        # migration slices likewise: member m ships the rows IT owns
        in_specs += [jax.tree.map(lambda _: P("model"), migration)]
        args += [migration]
    if has_rep:
        # repair slices likewise: any member may carry mirror rows
        in_specs += [jax.tree.map(lambda _: P("model"), repair)]
        args += [repair]
    if has_quar:
        in_specs += [P()]              # quarantine gids replicated
        args += [jnp.asarray(quarantine, jnp.int32)]
    if wire_check:
        in_specs += [P()]              # corruption matrix replicated
        args += [jnp.asarray(wire_flip, jnp.uint8)]
    if has_inv:
        in_specs += [P()]              # placement map replicated
        args += [jnp.asarray(table_inv, jnp.int32)]
    out_spec = P(None, baxes + ("model",) if baxes else "model")
    out_specs = (out_spec, P(), P(), P()) if return_diag else (out_spec,)
    if has_delta:
        # each member's harvest: (P_dst, mb, P_src, ...) per sub-field
        out_specs = out_specs + (
            {f.name: P("model") for f in dlayout.fields},)
    if has_mig:
        out_specs = out_specs + (
            {f.name: P("model") for f in mlayout.fields},)
    if has_rep:
        out_specs = out_specs + (
            {f.name: P("model") for f in rlayout.fields},)
    if wire_check:
        # per-destination corrupt-source flags: (P_dst · P_src,) global,
        # reshaped host-side
        out_specs = out_specs + (P("model"),)
    out, *rest_out = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_specs,
        check_vma=False,
    )(*args)
    wbad_out = rest_out.pop() if wire_check else None
    rep_out = rest_out.pop() if has_rep else None
    mig_out = rest_out.pop() if has_mig else None
    staged_out = rest_out.pop() if has_delta else None
    diag_out = rest_out
    # out: (mb, B/mb) where each row of size B/mb is laid out
    # [data-row, member, bs]; input order within a data row is
    # [microbatch, member, bs].
    if not restore_order:
        logits = out.reshape(-1)
    else:
        o = out.reshape(mb, n_data, n_shards, bs_g)
        logits = o.transpose(1, 0, 2, 3).reshape(-1)
    ret = (logits,)
    if return_diag:
        ret = ret + (ExchangeDiag(
            *diag_out, "ragged" if use_ragged else "dense",
            cap, dense_rows),)
    if has_delta:
        ret = ret + (staged_out,)
    if has_mig:
        ret = ret + (mig_out,)
    if has_rep:
        ret = ret + (rep_out,)
    if wire_check:
        ret = ret + (wbad_out,)
    return ret if len(ret) > 1 else logits


def build_forward_plans(params, cfg: DLRMConfig, idx, *,
                        microbatches: int = 1, batch_tile: int = 64,
                        cache=None, exchange: Optional[str] = None,
                        ragged_cap: Optional[int] = None,
                        row_block: Optional[int] = None,
                        plan_method: str = "auto"):
    """Precompute the per-(member, microbatch) embedding-bag StreamPlans
    ``forward_distributed(..., plan=...)`` consumes — the serving half of
    the plan/compute overlap (DESIGN.md §1): ``DLRMEngine`` dispatches this
    (async) for flush n+1 while flush n's step still occupies the device,
    so the index-bucketing sort never sits between exchange and pool.

    Returns a StreamPlan pytree whose leaves are model-major on axis 0 and
    (data-row, microbatch)-major on axis 1 — the exact layout the forward's
    shard_map redistributes — or None when there is no plan to build: no
    model-axis mesh, the 'ref' backend (no kernel), a VMEM-resident
    regime (no streaming), or an exchange that resolves ragged (packed
    row sets are data-dependent).  Plans are built from indices alone, so
    a cache's miss masks never invalidate them."""
    mesh = partition.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return None
    if resolve_sparse_backend(cfg.sparse_backend) == "ref":
        return None
    from repro.kernels import embedding_bag as eb
    n_shards = mesh.shape["model"]
    baxes = _batch_axes(mesh)
    mb = microbatches
    rblk = row_block if row_block is not None else cfg.row_block
    r = params["tables"].shape[1]
    s = params["tables"].shape[2]
    item = jnp.dtype(params["tables"].dtype).itemsize
    try:
        streamed, _ = eb.resolve_row_block(r, s, item, rblk)
    except ValueError:
        return None                 # forward will raise on its own terms
    if not streamed:
        return None
    # mirror the forward's static exchange selection: plans only serve the
    # dense pooling path
    use_cache = cache is not None and cache.cache_rows > 0
    n_data = 1
    for a in baxes:
        n_data *= mesh.shape[a]
    t_loc = idx.shape[1] // n_shards
    bs_g = idx.shape[0] // (n_data * mb * n_shards)
    use_ragged, _ = resolve_exchange(
        exchange if exchange is not None else cfg.exchange,
        use_cache=use_cache,
        cap=ragged_cap if ragged_cap is not None else cfg.ragged_cap,
        dense_rows=bs_g * t_loc)
    if use_ragged:
        return None

    # ONE source of truth for gid layout and effective block height: the
    # same stacked_stream_plan the kernel entry points advertise, applied
    # to each member's per-microbatch index slice
    def per_mb(ix):
        return eb.stacked_stream_plan(t_loc, r, s, item, ix,
                                      batch_tile=batch_tile,
                                      row_block=rblk,
                                      plan_method=plan_method)

    def plan_fn(idx_s):
        if use_cache:               # idx replicated over model: slice ours
            m = jax.lax.axis_index("model")
            idx_s = jax.lax.dynamic_slice_in_dim(idx_s, m * t_loc, t_loc,
                                                 axis=1)
        b_row, _, hot = idx_s.shape
        plans = jax.vmap(per_mb)(
            idx_s.reshape(mb, b_row // mb, t_loc, hot))
        return jax.tree.map(lambda a: a[None], plans)   # + model-slot axis

    sparse_spec = (P(baxes if baxes else None, None, None) if use_cache
                   else P(baxes if baxes else None, "model", None))
    out_spec = P("model", baxes if baxes else None)
    # the spec tree must match the output tree INCLUDING the plan's static
    # rb/total_rows metadata (pytree aux participates in structure
    # equality) — probe it from per_mb itself rather than re-deriving rb
    b_mb = idx.shape[0] // (n_data * mb)
    plan_struct = jax.eval_shape(per_mb, jax.ShapeDtypeStruct(
        (b_mb, t_loc, idx.shape[2]), jnp.int32))
    with jax.named_scope("plan"):
        return jax.shard_map(
            plan_fn, mesh=mesh, in_specs=(sparse_spec,),
            out_specs=jax.tree.map(lambda _: out_spec, plan_struct),
            check_vma=False,
        )(idx.astype(jnp.int32))


# ---------------------------------------------------------------------------
# loss / metrics
# ---------------------------------------------------------------------------


def bce_loss(logits, labels):
    lf = logits.astype(jnp.float32)
    yf = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(lf, 0) - lf * yf + jnp.log1p(jnp.exp(-jnp.abs(lf))))


def table_stats(cfg: DLRMConfig, n_shards: int = 16) -> dict:
    t_pad = padded_tables(cfg, n_shards)
    r_max = padded_rows(cfg)
    real = sum(cfg.table_sizes) * cfg.embed_dim
    padded = t_pad * r_max * cfg.embed_dim
    return {"t_pad": t_pad, "r_max": r_max,
            "padding_fraction": 1.0 - real / padded,
            "bytes": padded * jnp.dtype(cfg.dtype).itemsize}
