"""Pallas TPU kernels: embedding-bag gather + masked pooling (DLRM apply_emb).

The paper's flame graph (Fig. 5) shows apply_emb dominating DLRM inference;
this is its TPU form.

**Table view.**  On the TPU a ``(T, R, s)`` f32 stack with ``s < 128``
lives in HBM with the row axis minor (XLA's layout ``{1,2,0}``): every
table is physically an ``(s, R)`` matrix whose COLUMNS are embedding rows.
The kernels consume exactly that view — ``jnp.swapaxes(tables, 1, 2)``,
a free bitcast on the chip — so no padded (R, 128) copy of the stack is
ever made (for the Kaggle stack that copy alone would not fit a 16 GB
chip).  One embedding row is one lane column; gathering it means loading
the aligned 128-lane tile that holds it and rotating the column into
place (:func:`_stage_col`).  DMAs move whole 128-lane tiles, so a
natively-lowered streamed kernel needs ``R`` and the block height to be
multiples of 128 (``init_dlrm`` pads R; the padding is what the layout
holds anyway).

Two regimes, one knob (``row_block``, DESIGN.md §1):

* **VMEM-resident** — a whole ``(s, R)`` table rides a BlockSpec into VMEM
  and the (sample × hot) index list is pooled straight out of it.  Only
  sound while ``R · s · itemsize`` fits the VMEM budget (rows ≲ 16k at
  s=64 f32).

* **DMA-streamed** — production-size tables cannot be resident, so the
  table stays in HBM (``memory_space=ANY``) and the kernel streams
  ``(s, row_block)`` blocks with ``pltpu.make_async_copy`` through a ring
  of K = :data:`STREAM_SLOTS` VMEM slots, one DMA semaphore each: the
  copies run up to K blocks ahead of the block being pooled.  Blocks
  never straddle tables (block *k* of table *t* covers rows ``[k·rb,
  k·rb + rb)`` clamped into the table).  Indices are pre-bucketed per
  block OUTSIDE the kernel (:func:`_stream_plan`): grouping by block id
  makes each block's indices a contiguous segment of the planned list,
  and empty blocks are compacted away entirely — each grid step DMAs only
  the blocks its indices touch.  The auto block height is one 128-row
  lane tile (:func:`auto_row_block`), not the tallest block the VMEM
  budget holds: indices spread over a tall table touch one or two rows a
  block at any height, so a taller block only copies more bytes nobody
  reads, and the deep ring hides the latency of the many small copies.

Per-tile plan scalars (row ids, staging slots, weights, compacted block
per position, block offsets) ride in SMEM, where the scalar core can use
them as DMA offsets and loop bounds.  Each staged row lands in an f32 ``(hot, s,
128)`` VMEM accumulator — bag ``b``'s ``h``-th index in lane ``b`` of row
``h`` — written through refs, never through a loop-carried value.  The
final reduce over ``hot`` runs in the reference order, so every kernel
form is bit-identical to the jnp oracle in f32 no matter which block
order the rows arrived in.

Each regime walks its indices in one of two **pool modes** (``pool_mode``):

* ``scalar`` — a ``fori_loop`` over exactly one position per step;
* ``vector`` (the default under ``auto``) — ``chunk`` positions per step,
  statically unrolled so their independent loads, rotates and stores
  overlap.  The streamed kernel walks the planned list itself in chunks,
  across block boundaries (a chunk waits for every block it reaches),
  padding the list's tail with copies of its last entry; the resident
  kernel pads with weight-0 positions that address no kept bag.

The **stream plan** itself (:func:`_stream_plan`) has two builders behind
one ``plan_method`` knob: ``sort`` (``O(L log L)``: one sort by row id,
a cumsum of the block-change flags, and a second sort that compacts the
block offsets — no search loop, no batched gather) and
``count`` (a counting sort keyed by block id: one histogram over ``nb``
buckets whose prefix sum IS the segment-offset table — ``O(L · nb)``
vectorized work, no comparison sort); ``auto`` picks ``count`` while
``L · nb`` stays under :data:`PLAN_COUNT_WORK` and falls back to ``sort``
past it.  Plans are plain pytrees (:class:`StreamPlan`), so they can be
built OFF the critical path — :func:`build_stream_plan` /
:func:`stacked_stream_plan` construct one outside the kernel call and every
entry point accepts ``plan=`` to consume it, which is how
``forward_distributed`` / ``DLRMEngine`` overlap plan construction with
stage_a compute (DESIGN.md §1).

Interpret-mode dispatch runs the identical streaming schedule as pure jax
ops (:func:`_stream_rows_jnp`) by default, so CPU validation inside the
jitted multi-device forward runs plain ops, while the Pallas DMA pipeline
itself is validated standalone (``dma=True``) and lowers natively on TPU.

Entry points: :func:`embedding_bag` (single table), :func:`embedding_bag_
stacked` (the (T, R, s) model stack), :func:`embedding_bag_rows` (ragged
packed rows — the pool half of the ragged miss-residual exchange, DESIGN.md
§6).  All three pad partial batch tiles internally (no ``B % bt`` crash) and
accept ``row_block``: ``0`` auto (resident when it fits, streamed
otherwise), ``> 0`` forced streaming at that block height, ``-1`` forced
resident (raises when the block cannot fit — the CPU-side stand-in for the
TPU VMEM OOM).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budgets (bytes).  RESIDENT bounds the one (s, R) table block the
# resident kernel keeps live per grid step (16 MiB scoped VMEM, minus the
# staging accumulator and headroom -> 4 MiB ~ 16k rows at s=64 f32, the
# DESIGN.md §1 number).  STREAM bounds the streamed kernel's ring of DMA
# slots TOGETHER, and STAGE bounds the (tile, hot, s) f32 staging work —
# the wrappers shrink the ring / batch_tile to respect them.
RESIDENT_VMEM_BYTES = 4 << 20
STREAM_VMEM_BYTES = 4 << 20
STAGE_VMEM_BYTES = 2 << 20

# The vreg lane width.  Table rows are lane columns of the (s, R) view,
# DMAs and tile loads move whole 128-lane tiles, and the staging
# accumulator keeps one bag per lane, so a tile holds at most LANES bags.
# One (s, LANES) tile is also the streamed kernel's auto fetch unit.
LANES = 128

# Streamed DMA ring depth: VMEM slots (and DMA semaphores) the streamed
# kernel cycles through; its copies run up to STREAM_SLOTS blocks ahead of
# the block being pooled.  Lane-tile blocks serve one to three indices
# each, so a shallow queue would leave every copy's latency exposed; 16
# slots of one (64, 128) f32 tile are 512 KiB (32 measured no faster on a
# TPU v5e).  The ring shrinks to fit STREAM_VMEM_BYTES for taller explicit
# blocks (never below two slots).
STREAM_SLOTS = 16

# Vector-pool unroll: positions staged per loop step.
POOL_CHUNK = 8



# Counting-sort plan budget: the count method materializes a
# (tiles, L, nb) one-hot running sum to rank indices within their block
# bucket; past this many TOTAL cells the argsort plan (O(tiles · L) peak
# memory) is the better trade, so ``auto`` falls back.
PLAN_COUNT_WORK = 4 << 20


def fits_resident(rows: int, s: int, itemsize: int) -> bool:
    """Can one (rows, s) table block sit whole in the resident budget?"""
    return rows * s * itemsize <= RESIDENT_VMEM_BYTES


def auto_row_block(total_rows: int) -> int:
    """Streamed block height: one 128-row lane tile, clipped to the table.

    The fetch unit is set by the indices, not by the VMEM budget: a
    streamed table is tall and its indices are spread, so a touched block
    serves one or two of them whatever its height, and every byte past
    the touched tile is copied for nothing.  Every touched lane tile lies
    inside a touched taller block, so fetched bytes only fall as the unit
    shrinks; the copy count rises, bounded by L a tile, and the DMA ring
    (:data:`STREAM_SLOTS`) keeps those copies overlapped."""
    return min(total_rows, LANES)


def resolve_row_block(total_rows: int, s: int, itemsize: int,
                      row_block: int) -> tuple[bool, int]:
    """(streamed?, effective row_block) for a table of ``total_rows``.

    row_block 0 = auto (resident iff the block fits RESIDENT_VMEM_BYTES,
    else streamed in :func:`auto_row_block` lane tiles), > 0 = forced
    streaming at min(row_block, total_rows), -1 = forced
    resident (raises when the block cannot fit VMEM)."""
    if row_block == -1:
        if not fits_resident(total_rows, s, itemsize):
            raise ValueError(
                f"resident embedding-bag kernel: table block "
                f"{total_rows}x{s}x{itemsize}B = "
                f"{total_rows * s * itemsize} B exceeds the "
                f"{RESIDENT_VMEM_BYTES} B VMEM budget — use row_block=0 "
                f"(auto) or > 0 to stream row blocks (DESIGN.md §1)")
        return False, total_rows
    if row_block > 0:
        return True, min(row_block, total_rows)
    if row_block != 0:
        raise ValueError(f"row_block must be -1, 0 or positive, "
                         f"got {row_block}")
    if fits_resident(total_rows, s, itemsize):
        return False, total_rows
    return True, auto_row_block(total_rows)


def resolve_pool_mode(pool_mode: str) -> str:
    """'auto' -> the vectorized chunked-gather pool (the fast path);
    'scalar' keeps the one-row-per-iteration walk for A/B."""
    if pool_mode == "auto":
        return "vector"
    if pool_mode not in ("scalar", "vector"):
        raise ValueError(f"pool_mode must be 'scalar', 'vector' or 'auto', "
                         f"got {pool_mode!r}")
    return pool_mode


# ---------------------------------------------------------------------------
# the stream plan: per-block index bucketing, built on or off the hot path
# ---------------------------------------------------------------------------


class StreamPlan(NamedTuple):
    """Pre-bucketed indices for the streamed kernel — a pytree whose array
    leaves ride through jit/shard_map while ``rb``/``total_rows`` travel
    as STATIC metadata (see the pytree registration below), so it can be
    built ahead of time (jitted separately, shipped through shard_map) and
    handed to any entry point via ``plan=`` — and a plan built for a
    different block height or table cannot be consumed silently.

    All array leaves are int32.  sid/pos/inv/cum are (tiles, L); off is
    (tiles, nbmax); nblk is (tiles, 1).  ``pos[p]`` is the original flat
    position of planned entry ``p`` (its staging slot and weight),
    ``inv`` is the inverse permutation (``inv[pos[p]] == p``), ``cum`` the
    compacted block index owning each planned position (non-decreasing:
    block ``j``'s positions are one contiguous run).  Weights are NOT
    part of the plan — the kernel reads them at ``pos`` at consumption
    time, so a plan built from indices alone (before cache miss-masks
    exist) stays valid.  Blocks are per table: ``off`` is the flat start row
    ``t·rows + c`` of a block that lies inside table ``t``."""
    sid: jax.Array     # planned (block-grouped) flat row ids
    pos: jax.Array     # original position of each planned entry
    inv: jax.Array     # planned position of each original entry
    off: jax.Array     # clamped flat start row per compacted block
    nblk: jax.Array    # compacted (touched) block count
    cum: jax.Array     # compacted block index per planned position
    rb: int = 0           # static: block height the plan bucketed for
    total_rows: int = 0   # static: flat row-space height
    rows: int = 0         # static: rows per table (blocks never straddle)


N_PLAN_LEAVES = 6          # array fields above; rb/total_rows/rows are aux

# rb/total_rows/rows are STATIC aux data, not traced leaves: tree
# transforms (vmap over microbatches, shard_map redistribution, scan
# slicing) map the six index arrays and carry the geometry alongside,
# and _check_plan can raise at trace time when a plan meets a call with a
# different row_block/table — shapes alone cannot always tell them apart
# (nbmax clamps to L for any sufficiently tall table).
jax.tree_util.register_pytree_node(
    StreamPlan,
    lambda p: (tuple(p[:N_PLAN_LEAVES]), (p.rb, p.total_rows, p.rows)),
    lambda aux, leaves: StreamPlan(*leaves, *aux))


def _n_blocks(total_rows: int, rows: int, rb: int) -> int:
    """Block count of a stack of ``total_rows // rows`` tables."""
    return (total_rows // rows) * -(-rows // rb)


def _block_of(gid, rb: int, rows: int):
    """Block id of flat row ids: table-major, ``ceil(rows / rb)`` blocks
    per table."""
    return (gid // rows) * -(-rows // rb) + (gid % rows) // rb


def _block_start(bid, rb: int, rows: int):
    """Flat start row of block ``bid``, clamped inside its table so a
    table whose height is not a multiple of ``rb`` streams an overlapping
    final block instead of reading the next table."""
    nbt = -(-rows // rb)
    return (bid // nbt) * rows + jnp.clip((bid % nbt) * rb, 0, rows - rb)


def _resolve_plan_method(plan_method: str, L: int, nb_total: int,
                         tiles: int = 1) -> str:
    if plan_method == "auto":
        return "count" if tiles * L * nb_total <= PLAN_COUNT_WORK \
            else "sort"
    if plan_method not in ("sort", "count"):
        raise ValueError(f"plan_method must be 'sort', 'count' or 'auto', "
                         f"got {plan_method!r}")
    return plan_method


def _inverse_perm(perm):
    """Invert a batch of permutations with ONE flat 1-D scatter (XLA's 2-D
    indexed scatter path is measurably slower on the hosts that build
    plans)."""
    tiles, L = perm.shape
    flat = (perm + jnp.arange(tiles, dtype=jnp.int32)[:, None] * L) \
        .reshape(-1)
    arL = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32),
                           (tiles, L)).reshape(-1)
    return jnp.zeros((tiles * L,), jnp.int32).at[flat].set(arL) \
        .reshape(tiles, L)


def _sort_with(keys, *payload):
    """Sort each row of ``keys`` (tiles, L) and carry ``payload`` arrays of
    the same shape along: one sort, no gather (a batched gather costs the
    TPU several times what the sort does)."""
    return jax.lax.sort((keys, *payload), dimension=1, num_keys=1)


def _plan_sort(gid, rb: int, total_rows: int, nbmax: int,
               rows: int) -> StreamPlan:
    """The comparison-sort plan builder: sort by full row id, carrying the
    original positions along.  Each block's run starts where the sorted
    list's block id changes; a second sort that moves those change
    positions to the front, in order, carrying each one's block start
    along, compacts the block offsets.  Every step is a sort or an
    elementwise pass over L, so nothing grows with the ``nbmax`` blocks a
    tile may touch."""
    tiles, L = gid.shape
    iota = jax.lax.broadcasted_iota(jnp.int32, (tiles, L), 1)
    sid, pos = _sort_with(gid, iota)
    _, inv = _sort_with(pos, iota)
    blk = _block_of(sid, rb, rows)                         # (tiles, L)
    first = jnp.concatenate(
        [jnp.ones((tiles, 1), bool), blk[:, 1:] != blk[:, :-1]], axis=-1)
    cum = jnp.cumsum(first.astype(jnp.int32), axis=-1) - 1  # compact index
    nblk = cum[:, -1:] + 1                                  # (tiles, 1)
    _, off = _sort_with(jnp.where(first, iota, L + iota),
                        _block_start(blk, rb, rows))
    valid = jnp.arange(nbmax, dtype=jnp.int32)[None, :] < nblk
    return StreamPlan(
        sid, pos, inv,
        jnp.where(valid, off[:, :nbmax], 0).astype(jnp.int32),
        nblk.astype(jnp.int32), cum, rb=rb, total_rows=total_rows,
        rows=rows)


# chunk length of the hierarchical running count below: shortening the
# scan axis from L to RANK_CHUNK turns XLA's sequential cumsum into wide
# vector steps (the scan runs over the chunk axis with (L/chunk)·nb-wide
# element ops), which is where the counting plan's build-time win over the
# argsort plan comes from.
RANK_CHUNK = 128


def _bucket_rank(key, nb_total: int):
    """(stable within-bucket rank, bucket histogram) for ``key`` (tiles, L)
    int32 in [0, nb_total).  The running count is hierarchical: per-chunk
    one-hot cumsum (short scan axis, wide ops) + an exclusive chunk-offset
    cumsum over the chunk counts."""
    tiles, L = key.shape
    c = min(RANK_CHUNK, L)
    Lp = -(-L // c) * c
    kp = jnp.pad(key, ((0, 0), (0, Lp - L)), constant_values=nb_total)
    oh = (kp.reshape(tiles, Lp // c, c)[..., None] ==
          jnp.arange(nb_total, dtype=jnp.int32)).astype(jnp.int32)
    within = jnp.cumsum(oh, axis=2)               # (tiles, C, c, nb)
    per = within[:, :, -1, :]                     # (tiles, C, nb)
    coff = jnp.cumsum(per, axis=1) - per          # exclusive chunk offsets
    run = (within + coff[:, :, None, :]).reshape(tiles, Lp, nb_total)
    rank = jnp.take_along_axis(run[:, :L], key[..., None],
                               axis=2)[..., 0] - 1
    hist = coff[:, -1] + per[:, -1]               # (tiles, nb)
    return rank, hist


def _plan_count(gid, rb: int, total_rows: int, nbmax: int,
                rows: int) -> StreamPlan:
    """The counting-sort plan builder: bucket by block id (``nb_total``
    buckets).  One histogram's prefix sum IS the segment-offset table, and
    the stable within-bucket rank comes from the hierarchical one-hot
    running count — no comparison sort anywhere.  Within a block the
    planned order is original (stable) order rather than row-id order;
    nothing downstream depends on within-block order (each staging slot is
    keyed by original position), so the pooled output is bit-identical to
    the sort plan's."""
    tiles, L = gid.shape
    nb_total = _n_blocks(total_rows, rows, rb)
    key = _block_of(gid, rb, rows)                        # (tiles, L)
    rank, hist = _bucket_rank(key, nb_total)
    excl = jnp.cumsum(hist, axis=-1) - hist               # segment offsets
    dest = jnp.take_along_axis(excl, key, axis=-1) + rank  # (tiles, L)
    pos = _inverse_perm(dest)
    sid = jnp.take_along_axis(gid, pos, axis=-1)
    inv = dest.astype(jnp.int32)
    ne = hist > 0
    nblk = ne.sum(axis=-1, keepdims=True).astype(jnp.int32)
    cidx = jnp.cumsum(ne.astype(jnp.int32), axis=-1) - 1
    # compacted-slot scatter, flat 1-D with a global OOB sentinel so empty
    # buckets drop instead of colliding with the next tile's slot 0
    ti = jnp.arange(tiles, dtype=jnp.int32)[:, None]
    cflat = jnp.where(ne, ti * nbmax + cidx, tiles * nbmax).reshape(-1)
    zB = jnp.zeros((tiles * nbmax,), jnp.int32)
    arB = jnp.broadcast_to(jnp.arange(nb_total, dtype=jnp.int32),
                           (tiles, nb_total)).reshape(-1)
    bid = zB.at[cflat].set(arB, mode="drop").reshape(tiles, nbmax)
    jr = jnp.arange(nbmax, dtype=jnp.int32)
    valid = jr[None, :] < nblk
    zero = jnp.zeros((), jnp.int32)
    off = jnp.where(valid, _block_start(bid, rb, rows), zero)
    cum = jnp.take_along_axis(cidx, _block_of(sid, rb, rows), axis=-1)
    return StreamPlan(sid, pos, inv, off.astype(jnp.int32),
                      nblk, cum.astype(jnp.int32),
                      rb=rb, total_rows=total_rows, rows=rows)


def _stream_plan(gid, rb: int, total_rows: int, nbmax: int,
                 plan_method: str = "auto", rows: int = 0) -> StreamPlan:
    """Pre-bucket a tile batch of indices per row block (the XLA half of
    the streamed kernel).

    gid (tiles, L) int32 flat row ids in [0, total_rows).  Grouping by
    block id makes every block's indices one contiguous segment of the
    planned list, and blocks nobody indexes vanish from the compacted block
    list — the kernel DMAs only touched blocks and walks the planned list
    exactly once (total work stays L gathers per tile).  The last block's
    DMA start is clamped to ``total_rows - rb`` so a table whose row count
    is not a multiple of ``rb`` streams an overlapping final block instead
    of reading the next table (blocks never straddle tables: the kernel
    DMAs one table's (s, rb) window per block).

    ``plan_method``: 'sort' (sort by row id, O(L log L), block offsets
    compacted by a second sort), 'count' (counting sort keyed by block
    id, O(L · nb) vectorized), 'auto' (count
    under :data:`PLAN_COUNT_WORK`, sort past it).  ``rows`` is the height
    of one table of the stack (0: one table of ``total_rows``)."""
    tiles, L = gid.shape
    rows = rows or total_rows
    nb_total = _n_blocks(total_rows, rows, rb)
    method = _resolve_plan_method(plan_method, L, nb_total, tiles)
    build = _plan_count if method == "count" else _plan_sort
    # the device scope the trace's plan-build ops are named by
    with jax.named_scope("plan"):
        return build(gid, rb, total_rows, nbmax, rows)


def _stream_geometry(total_rows: int, s: int, n: int, hot: int,
                     row_tile: int, rb: int, rows: int):
    """(nt, tiles, n_pad, L, nbmax) — the one tiling both the Pallas
    kernels and the jnp emulation (and any precomputed plan) share, so a
    plan built outside can never disagree with the executor.  A tile
    holds at most LANES bags (one per accumulator lane)."""
    nt = min(_stage_tile(row_tile, n, hot, s), LANES)
    tiles = -(-n // nt)
    n_pad = tiles * nt
    L = nt * hot
    nbmax = min(_n_blocks(total_rows, rows, rb), L)
    return nt, tiles, n_pad, L, nbmax


def _ring_slots(nbmax: int, rb: int, s: int, itemsize: int) -> int:
    """Slots of the streamed kernel's DMA ring: :data:`STREAM_SLOTS`,
    fewer where taller blocks would overrun STREAM_VMEM_BYTES (never below
    two), and never more than the blocks one tile can touch."""
    fit = STREAM_VMEM_BYTES // (max(rb, LANES) * s * itemsize)
    return min(max(2, min(STREAM_SLOTS, fit)), nbmax)


def build_stream_plan(total_rows: int, s: int, gid, *, row_tile: int,
                      rb: int, plan_method: str = "auto",
                      rows: int = 0) -> StreamPlan:
    """Build a :class:`StreamPlan` for ``gid`` (n, hot) pre-clipped flat
    row ids OUTSIDE the kernel call — the off-critical-path half of the
    plan/compute overlap (DESIGN.md §1).  The tiling geometry is exactly
    what :func:`_stream_rows` derives, so the plan drops in via ``plan=``.
    ``rows`` is one table's height (0: a single table)."""
    n, hot = gid.shape
    rows = rows or total_rows
    nt, tiles, n_pad, L, nbmax = _stream_geometry(
        total_rows, s, n, hot, row_tile, rb, rows)
    if n_pad != n:
        gid = jnp.pad(gid, ((0, n_pad - n), (0, 0)))
    return _stream_plan(gid.reshape(tiles, L).astype(jnp.int32), rb,
                        total_rows, nbmax, plan_method, rows)


def fetch_bytes(plan: StreamPlan, s: int, itemsize: int) -> tuple[int, int]:
    """(touched blocks, bytes the streamed kernel copies) for ``plan``:
    every compacted block is one (s, rb) DMA, so the bytes are
    Σ nblk · rb · s · itemsize.  Reads the plan on the host — a
    measurement helper, never called on the serving path."""
    units = int(np.asarray(plan.nblk, np.int64).sum())
    return units, units * plan.rb * s * itemsize


def _check_plan(plan: StreamPlan, tiles: int, L: int, nbmax: int,
                rb: int, total_rows: int, rows: int):
    # rb/total_rows/rows ride the plan as static metadata: leaf shapes
    # alone cannot always distinguish two block heights (nbmax clamps to L
    # for any sufficiently tall table), and consuming a plan bucketed for a
    # different rb would gather silently-wrong rows
    meta = ("rb", "total_rows", "rows")
    want = {"sid": (tiles, L), "pos": (tiles, L), "inv": (tiles, L),
            "off": (tiles, nbmax), "nblk": (tiles, 1), "cum": (tiles, L),
            "rb": rb, "total_rows": total_rows, "rows": rows}
    got = {k: tuple(getattr(plan, k).shape) for k in want if k not in meta}
    got.update({k: getattr(plan, k) for k in meta})
    if got != want:
        raise ValueError(
            f"precomputed StreamPlan does not match this call's geometry: "
            f"want {want}, got {got} — build it with build_stream_plan/"
            f"stacked_stream_plan at the same batch/row_tile/row_block")


# ---------------------------------------------------------------------------
# the pooling step shared by every kernel form
# ---------------------------------------------------------------------------


def _stage_col(src, acc, loc, q, w, *, hot: int):
    """Stage one index: row ``loc`` of ``src`` — a lane column of an (s,
    width) table view, width >= LANES — weighted by ``w`` into staging
    slot ``q`` = bag·hot + h, i.e. lane ``bag`` of ``acc[h]``.

    The aligned 128-lane tile holding the column is loaded and rotated so
    the column lands on lane ``bag``; a lane select writes it there.  A
    slot with bag >= LANES (list padding) matches no lane and writes
    nothing."""
    width = src.shape[-1]
    base = pl.multiple_of(
        jnp.minimum(loc // LANES * LANES, width - LANES), LANES)
    tile = src[:, pl.ds(base, LANES)].astype(jnp.float32)     # (s, LANES)
    bag, h = q // hot, q % hot
    moved = pltpu.roll(tile, jax.lax.rem(bag - (loc - base) + LANES, LANES),
                       1)
    lane = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    acc[h] = jnp.where(lane == bag, moved * w, acc[h])


def _walk(lo, hi, pool, chunk: int):
    """Run ``pool(p)`` over positions [lo, hi): one per step (scalar pool,
    ``chunk`` 1) or ``chunk`` statically unrolled per step (vector pool —
    the last step overhangs ``hi``; callers make overhang harmless)."""
    if chunk == 1:
        def one(p, carry):
            pool(p)
            return carry
        jax.lax.fori_loop(lo, hi, one, 0)
        return

    def step(c, carry):
        base = lo + c * chunk
        for k in range(chunk):
            pool(base + k)
        return carry

    jax.lax.fori_loop(0, pl.cdiv(hi - lo, chunk), step, 0)


def _check_lane_aligned(rows: int, rb: int):
    """The native streamed kernel DMAs whole 128-lane tiles of the (s, R)
    view: every block start and height must be a lane multiple."""
    if rows % LANES or rb % LANES:
        raise ValueError(
            f"native streamed embedding-bag kernel: table height {rows} and "
            f"row_block {rb} must both be multiples of {LANES} (DMAs move "
            f"whole lane tiles of the (s, R) table view) — init_dlrm pads "
            f"the stack; pick row_block accordingly")


# ---------------------------------------------------------------------------
# the streaming core: pre-bucketed indices + a ring of in-flight DMAs
# ---------------------------------------------------------------------------


def _stream_kernel(sid_ref, pos_ref, w_ref, cum_ref, off_ref, nb_ref,
                   tbl_ref, out_ref, buf, acc, sem, *, hot: int, rb: int,
                   rows: int, chunk: int):
    """HBM->VMEM block streaming over the (T, s, R) table view through a
    ring of DMA slots, pooled position by position.

    Plan refs are (1, ·) SMEM rows of this tile: the planned row ids,
    original positions and compacted blocks (padded to whole chunks with
    copies of the last entry, which re-stage the same value), the
    weights in ORIGINAL order (read at ``pos``, so no permuted copy is
    ever made), block offsets and the block count.  tbl_ref lives in
    ANY/HBM; buf is (n_slots, s, width) VMEM with one DMA semaphore per
    slot; acc the (hot, s, LANES) f32 staging accumulator.

    The walk runs over planned positions in chunks of ``chunk``, unrolled
    so their independent loads, rotates and stores overlap however the
    chunk straddles blocks.  Before a chunk whose first position lies in
    block ``first``, every block below ``first + n_slots`` has its copy
    started (their slots belong to blocks already pooled), and every block
    the chunk reaches has its copy waited for; a chunk spans fewer than
    ``n_slots`` blocks, so it never waits for a copy not yet started.
    Each position stages its own row (slot-per-index via ``pos``), and
    the reduce over ``hot`` runs at the end — the reference summation
    order, independent of block arrival order."""
    n_slots, _, width = buf.shape
    nb = nb_ref[0, 0]
    n_chunks = sid_ref.shape[-1] // chunk
    aligned = rows % LANES == 0 and rb % LANES == 0

    def dma(j):
        slot = jax.lax.rem(j, n_slots)
        off = off_ref[0, j]
        t = off // rows
        c = off - t * rows
        if aligned:
            c = pl.multiple_of(c, LANES)
        dst = buf.at[slot] if rb == width else \
            buf.at[slot, :, pl.ds(0, rb)]
        return pltpu.make_async_copy(tbl_ref.at[t, :, pl.ds(c, rb)], dst,
                                     sem.at[slot])

    def start(j, carry):
        dma(j).start()
        return carry

    def wait(j, carry):
        dma(j).wait()
        return carry

    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def step(c, carry):
        started, ready = carry
        p0 = c * chunk
        top = jnp.minimum(cum_ref[0, p0] + n_slots, nb)
        jax.lax.fori_loop(started, top, start, 0)
        need = jnp.minimum(cum_ref[0, p0 + chunk - 1] + 1, nb)
        jax.lax.fori_loop(ready, need, wait, 0)
        for k in range(chunk):
            p = p0 + k
            j = cum_ref[0, p]
            q = pos_ref[0, p]
            _stage_col(buf.at[jax.lax.rem(j, n_slots)], acc,
                       sid_ref[0, p] - off_ref[0, j], q, w_ref[0, q],
                       hot=hot)
        return jnp.maximum(started, top), jnp.maximum(ready, need)

    zero = jnp.zeros((), jnp.int32)
    # a tile with no block (nblk 0) stages nothing and starts no copy
    jax.lax.fori_loop(0, jnp.where(nb > 0, n_chunks, 0), step, (zero, zero))
    out_ref[...] = acc[...].sum(axis=0).astype(out_ref.dtype)


def _stream_rows_jnp(table_flat, plan: StreamPlan, sw, *, nt: int,
                     hot: int, rb: int, out_dtype):
    """Pure-jax emulation of the streamed kernel: the SAME plan (block-
    grouped ids, compacted blocks, clamped last-block windows) driving the
    same block loop, with the per-block pooling vectorized (gather all
    positions from the block, mask to the block's own rows).  Every staged
    position receives exactly one weighted-row contribution and the final
    reduction runs over ``hot`` in the reference order, so the result is
    bit-identical to BOTH kernel pool modes and the jnp oracle in f32.

    This is what ``interpret`` dispatch uses inside the jitted
    multi-device forward, so CPU validation of the streamed path there
    runs the schedule as ordinary ops."""
    _, s = table_flat.shape
    tiles, L = plan.sid.shape

    def one_tile(sid, inv, off, nblk, cum, w):
        def blk_body(j, acc):
            block = jax.lax.dynamic_slice(table_flat, (off[j], 0), (rb, s))
            loc = jnp.clip(sid - off[j], 0, rb - 1)
            rows = jnp.take(block, loc, axis=0)                # (L, s)
            valid = (cum == j).astype(jnp.float32) * w
            return acc + rows.astype(jnp.float32) * valid[:, None]

        acc = jax.lax.fori_loop(0, nblk[0], blk_body,
                                jnp.zeros((L, s), jnp.float32))
        staged = jnp.take(acc, inv, axis=0)                    # unsort
        return staged.reshape(nt, hot, s).sum(axis=1).astype(out_dtype)

    return jax.vmap(one_tile)(plan.sid, plan.inv, plan.off, plan.nblk,
                              plan.cum, sw).reshape(tiles * nt, s)


def _smem_spec(n: int):
    """One tile's (1, n) row of a (tiles, 1, n) plan array, in SMEM: the
    block spans the array's last two dims, as Mosaic's tiling rule asks."""
    return pl.BlockSpec((None, 1, n), lambda i: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _stream_rows(tables, gid, w, *, row_tile: int, rb: int,
                 interpret: bool, out_dtype, dma=None,
                 pool_mode: str = "vector", plan: StreamPlan = None,
                 plan_method: str = "auto"):
    """The streaming core: tables (T, R, s) in HBM, gid (N, hot) int32
    pre-clipped flat row ids t·R + r, w (N, hot) weights -> (N, s) pooled
    bags.  N is padded to a whole number of row tiles internally (pad rows
    carry weight 0 and pool to zero).

    ``dma`` None = the async-copy Pallas kernel on native lowering, the
    pure-jax schedule emulation (:func:`_stream_rows_jnp`) in interpret
    mode; True forces the Pallas kernel (tests validate the DMA pipeline
    itself on CPU this way); False forces the emulation.  ``plan``
    consumes a precomputed :class:`StreamPlan` (geometry-checked) instead
    of building one inline; the emulation and both kernel pool modes all
    execute the same plan, so which executor ran never shows in the
    output."""
    t, rows, s = tables.shape
    total_rows = t * rows
    n, hot = gid.shape
    vector = resolve_pool_mode(pool_mode) == "vector"   # validate up front
    nt, tiles, n_pad, L, nbmax = _stream_geometry(
        total_rows, s, n, hot, row_tile, rb, rows)
    if n_pad != n:
        gid = jnp.pad(gid, ((0, n_pad - n), (0, 0)))
        w = jnp.pad(w, ((0, n_pad - n), (0, 0)))
    if plan is None:
        plan = _stream_plan(gid.reshape(tiles, L), rb, total_rows, nbmax,
                            plan_method, rows)
    else:
        _check_plan(plan, tiles, L, nbmax, rb, total_rows, rows)
    w = w.astype(jnp.float32).reshape(tiles, L)
    use_dma = dma if dma is not None else not interpret
    if not use_dma:
        # the emulation takes its weights in plan order (an O(L) gather)
        sw = jnp.take_along_axis(w, plan.pos, axis=-1)
        return _stream_rows_jnp(tables.reshape(total_rows, s), plan, sw,
                                nt=nt, hot=hot, rb=rb,
                                out_dtype=out_dtype)[:n]
    if not interpret:
        _check_lane_aligned(rows, rb)
    width = max(rb, LANES)
    n_slots = _ring_slots(nbmax, rb, s, jnp.dtype(tables.dtype).itemsize)
    chunk = POOL_CHUNK if vector else 1
    if n_slots < nbmax:
        chunk = min(chunk, n_slots)   # a chunk must span fewer blocks
    # weights are read at their original position inside the kernel, never
    # permuted, so a plan built from indices alone stays valid for any
    # miss-mask the cache produces at serving time
    ext = ((0, 0), (0, -L % chunk))
    sid, pos, cum = (jnp.pad(a, ext, mode="edge")
                     for a in (plan.sid, plan.pos, plan.cum))
    lp = sid.shape[1]
    out = pl.pallas_call(
        functools.partial(_stream_kernel, hot=hot, rb=rb, rows=rows,
                          chunk=chunk),
        grid=(tiles,),
        in_specs=[
            _smem_spec(lp),                         # planned row ids
            _smem_spec(lp),                         # staging slot per id
            _smem_spec(L),                          # weights, original order
            _smem_spec(lp),                         # compacted block per id
            _smem_spec(nbmax),                      # block start rows
            _smem_spec(1),                          # compacted block count
            pl.BlockSpec(memory_space=pl.ANY),      # table stays in HBM
        ],
        out_specs=pl.BlockSpec((None, s, LANES), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((tiles, s, LANES), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((n_slots, s, width), tables.dtype),  # DMA ring
            pltpu.VMEM((hot, s, LANES), jnp.float32),       # staging
            pltpu.SemaphoreType.DMA((n_slots,)),
        ],
        interpret=interpret,
    )(*(a[:, None, :] for a in (sid, pos, w, cum, plan.off, plan.nblk)),
      jnp.swapaxes(tables, 1, 2))
    return out[:, :, :nt].transpose(0, 2, 1).reshape(n_pad, s)[:n]


# ---------------------------------------------------------------------------
# VMEM-resident kernel (small tables; the pre-streaming fast path)
# ---------------------------------------------------------------------------


def _resident_kernel(ids_ref, w_ref, tbl_ref, out_ref, acc, *, hot: int,
                     n: int, chunk: int):
    """Pool one (table, bag-tile) grid step straight out of the resident
    (s, R) table block: index p of the tile's (1, ·) SMEM id/weight rows
    stages into slot p; positions past ``n`` (vector-pool overhang) carry
    weight 0 into bags no output keeps."""
    acc[...] = jnp.zeros(acc.shape, acc.dtype)

    def pool(p):
        _stage_col(tbl_ref, acc, ids_ref[0, p], p, w_ref[0, p], hot=hot)

    _walk(0, n, pool, chunk)
    out_ref[...] = acc[...].sum(axis=0).astype(out_ref.dtype)


def _pad_batch(b: int, bt: int, *arrays):
    """Pad the leading (batch) axis up to a multiple of ``bt`` (masked tail:
    pad rows pool to zero and are sliced off by the caller)."""
    b_pad = -(-b // bt) * bt
    if b_pad == b:
        return (b_pad,) + arrays
    return (b_pad,) + tuple(
        jnp.pad(a, ((0, b_pad - b),) + ((0, 0),) * (a.ndim - 1))
        for a in arrays)


def _stage_tile(tile: int, b: int, hot: int, s: int) -> int:
    """Clamp a batch/row tile so the (tile, hot, s) f32 staging work every
    kernel regime carries stays inside STAGE_VMEM_BYTES."""
    return max(1, min(tile, b, STAGE_VMEM_BYTES // max(hot * s * 4, 1)))


def embedding_bag(table, idx, mask, **kw):
    """table:(R,S) idx:(B,hot) int32 mask:(B,hot) -> (B,S).

    The one-table stack of :func:`embedding_bag_stacked` (same keywords:
    ``batch_tile``, ``row_block``, ``pool_mode``, ``interpret``, ``dma``,
    ``plan``, ``plan_method``)."""
    return embedding_bag_stacked(table[None], idx[:, None], mask[:, None],
                                 **kw)[:, 0]


# ---------------------------------------------------------------------------
# stacked-table form: the whole sparse arsenal in one call
# ---------------------------------------------------------------------------


def _stacked_gid(t: int, r: int, idx):
    """Flat (T·R, s) row-space ids for a stacked (B, T, hot) index tensor:
    global row id = t·R + clip(idx) — a free reshape of the stack."""
    return (jnp.arange(t, dtype=jnp.int32)[None, :, None] * r +
            jnp.clip(idx.astype(jnp.int32), 0, r - 1))


def stacked_stream_plan(t: int, r: int, s: int, itemsize: int, idx, *,
                        batch_tile: int = 64, row_block: int = 0,
                        plan_method: str = "auto"):
    """Precompute :func:`embedding_bag_stacked`'s StreamPlan from indices
    alone (weights never enter the plan), or return None when this
    geometry resolves VMEM-resident (no plan to build).  Built off the
    critical path by ``DLRMEngine``/``build_forward_plans`` and consumed
    via ``embedding_bag_stacked(..., plan=...)``."""
    b, t2, hot = idx.shape
    assert t == t2, (t, t2)
    streamed, rb = resolve_row_block(r, s, itemsize, row_block)
    if not streamed:
        return None
    gid = _stacked_gid(t, r, idx)
    return build_stream_plan(t * r, s, gid.reshape(b * t, hot),
                             row_tile=batch_tile, rb=rb,
                             plan_method=plan_method, rows=r)


def embedding_bag_stacked(tables, idx, mask, *, batch_tile: int = 64,
                          row_block: int = 0, pool_mode: str = "auto",
                          interpret: bool = False, dma=None,
                          plan: StreamPlan = None,
                          plan_method: str = "auto"):
    """tables:(T,R,s) idx:(B,T,hot) int32 mask:(B,T,hot) -> (B,T,s).

    The model-facing form of ``apply_emb``.  Resident regime: one
    ``pallas_call`` over a (table, batch-tile) grid, table dimension
    OUTERMOST so each table block stays VMEM-resident across all its batch
    tiles, and the (B,T,hot,s) broadcast-gather intermediate the pure-jnp
    reference materializes never exists.  Streamed regime (``row_block``):
    the stack is addressed as one flat (T·R, s) row space (global row id =
    t·R + idx) and pooled through the DMA-ring core in per-table blocks
    (lane tiles under auto), so tables of production size run at streaming bandwidth
    instead of failing the residency assumption.  ``pool_mode`` picks the
    scalar walk or the unrolled vector walk in BOTH regimes; ``plan``
    consumes a :func:`stacked_stream_plan` built off the critical path.
    Partial batch tiles are padded internally (any B works)."""
    t, r, s = tables.shape
    b, t2, hot = idx.shape
    assert t == t2, (t, t2)
    idx = idx.astype(jnp.int32)
    item = jnp.dtype(tables.dtype).itemsize
    streamed, rb = resolve_row_block(r, s, item, row_block)
    if streamed:
        gid = _stacked_gid(t, r, idx)
        out = _stream_rows(tables, gid.reshape(b * t, hot),
                           mask.reshape(b * t, hot),
                           row_tile=batch_tile, rb=rb,
                           interpret=interpret, out_dtype=tables.dtype,
                           dma=dma, pool_mode=pool_mode, plan=plan,
                           plan_method=plan_method)
        return out.reshape(b, t, s)
    if plan is not None:
        raise ValueError("plan= only applies to the streamed regime "
                         "(this call resolved VMEM-resident)")
    chunk = POOL_CHUNK if resolve_pool_mode(pool_mode) == "vector" else 1
    bt = min(_stage_tile(batch_tile, b, hot, s), LANES)
    b_pad, idx, mask = _pad_batch(b, bt, idx, mask)
    nbt = b_pad // bt
    n = bt * hot
    lp = -(-n // chunk) * chunk

    def per_tile(a):   # (B_pad, T, hot) -> (T, nbt, 1, lp) SMEM rows
        a = a.transpose(1, 0, 2).reshape(t, nbt, n)
        return jnp.pad(a, ((0, 0), (0, 0), (0, lp - n)))[:, :, None, :]

    # a resident block is small: pad its lanes to whole tiles so every
    # aligned tile load stays inside the block
    rp = max(LANES, -(-r // LANES) * LANES)
    tt = jnp.pad(jnp.swapaxes(tables, 1, 2), ((0, 0), (0, 0), (0, rp - r)))
    smem = pl.BlockSpec((None, None, 1, lp), lambda ti, bi: (ti, bi, 0, 0),
                        memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(_resident_kernel, hot=hot, n=n, chunk=chunk),
        grid=(t, nbt),
        in_specs=[
            smem,                                          # ids
            smem,                                          # weights
            pl.BlockSpec((None, s, rp), lambda ti, bi: (ti, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, s, LANES),
                               lambda ti, bi: (ti, bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((t, nbt, s, LANES), tables.dtype),
        scratch_shapes=[pltpu.VMEM((hot, s, LANES), jnp.float32)],
        interpret=interpret,
    )(per_tile(jnp.clip(idx, 0, r - 1)),
      per_tile(mask.astype(jnp.float32)), tt)
    out = out[..., :bt].transpose(1, 3, 0, 2).reshape(b_pad, t, s)
    return out[:b]


# ---------------------------------------------------------------------------
# ragged-row form: the pool half of the ragged miss-residual exchange
# ---------------------------------------------------------------------------


def embedding_bag_rows(tables, tid, idx, mask, *, row_tile: int = 64,
                       row_block: int = 0, pool_mode: str = "auto",
                       interpret: bool = False, dma=None,
                       plan_method: str = "auto"):
    """tables:(T,R,s) tid:(N,) int32 idx/mask:(N,hot) -> (N,s) masked sums.

    The packed-ragged analogue of :func:`embedding_bag_stacked`: pools ONLY
    the rows that ride the ragged exchange (DESIGN.md §6), each against its
    own table.  Runs on the same streaming core — global row id = tid·R +
    idx flattens the stack into one row space, so a small packed set
    (≤ P·cap rows) DMAs only the row blocks it actually touches even when
    the stack is production-size.  ``row_block`` 0/auto streams each table
    as one block when it fits the VMEM budget (the resident equivalent)
    and falls back to streamed blocks otherwise; ``pool_mode`` picks the
    pooling loop as everywhere else.  (No ``plan=``: the packed row set is
    data-dependent per step, so there is nothing to precompute.)"""
    t, r, s = tables.shape
    # one resolver with the other entry points: -1 raises past the VMEM
    # budget, 0 streams each table as a single block when it fits (the
    # resident equivalent), anything else is validated identically
    _, rb = resolve_row_block(r, s, jnp.dtype(tables.dtype).itemsize,
                              row_block)
    gid = (tid.astype(jnp.int32)[:, None] * r +
           jnp.clip(idx.astype(jnp.int32), 0, r - 1))
    return _stream_rows(tables, gid, mask,
                        row_tile=row_tile, rb=rb, interpret=interpret,
                        out_dtype=tables.dtype, dma=dma,
                        pool_mode=pool_mode, plan_method=plan_method)
