"""alltoallv on a TPU mesh: ragged exchange as counts + bucket-padded payload.

XLA collectives need static shapes, so the paper's variable message sizes
become *padding*: each (source, destination) pair gets a fixed ``cap``-row
bucket plus an exchanged count.  ``dispatch_stats`` quantifies the padding
waste — the TPU-side analogue of the paper's Fig. 6 message-size effects.

Two flavours used by DLRM (models/dlrm.py):
  * ``butterfly_pooled``  — reference-DLRM exchange of POOLED embedding-bag
    vectors: a plain equal-split all_to_all (batch split, table concat).
  * ``alltoallv_raw``     — the paper's Setting-1 style exchange of UNPOOLED
    vectors padded to ``max_hot`` (message raggedness -> padding waste).

Wire codecs (``encode_wire`` / ``decode_wire``) compress the butterfly
payload: bf16 halves the exchanged bytes, int8 with a per-row (per pooled
vector) bf16 scale quarters them — the inference-side analogue of
train/grad_compression.py's data-parallel codecs (no error feedback needed:
each exchanged value is consumed once, not accumulated).  ``wire_stats``
does the byte accounting the cache-aware path is judged on.

The ragged pooled exchange (DESIGN.md §6) composes the pieces: live pooled
rows are packed into cap-padded per-destination buckets
(``pack_ragged_tree``), codec-encoded, shipped with their counts
(``alltoallv_ragged``), and scattered back into a dense layout on the
receive side (``unpack_ragged``) — the exchanged bytes become the
``wire_stats.live_bytes`` number instead of the dense buffer.  Overflowing
a bucket drops rows; every packing path returns the drop count so parity
tests can assert zero and the serving cap autotuner can react.

The fused wire (DESIGN.md §7) collapses the exchange to ONE collective:
``fuse_wire`` bitcasts every payload leaf — codec rows, scales, row ids,
counts — into one contiguous ``(P, slot_bytes)`` uint8 bucket per
destination under a static ``WireLayout`` descriptor, so the whole
exchange is a single ``all_to_all`` (``alltoallv_fused``) and a BLS ring
slot is one flat leaf.  ``ring_exchange`` then decomposes that collective
into P−1 chunked ``ppermute`` rounds: round r+1's shift is issued before
round r's received chunk is consumed, so per-peer defuse/decode/scatter
overlaps the next chunk's flight (the sub-collective completion
granularity the paper's bounded lag is about).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding import partition


@dataclasses.dataclass(frozen=True)
class A2AVStats:
    payload_bytes: int      # bytes actually exchanged (padded buffers)
    useful_bytes: int       # bytes of real (non-padding) rows
    padding_fraction: float


def butterfly_pooled(x, axis: str = "model", wire_dtype: str = "float32"):
    """Reference-DLRM butterfly: x (B, T_local, D) per shard, batch split /
    table concat -> (B / P, T_local * P, D).  Equal splits; raggedness only
    via table-count imbalance which the caller pads into T_local.
    ``wire_dtype`` applies a wire codec around the exchange."""
    payload = encode_wire(x, wire_dtype)
    recv = jax.tree.map(
        lambda a: jax.lax.all_to_all(a, axis, split_axis=0, concat_axis=1,
                                     tiled=True), payload)
    return decode_wire(recv, x.dtype)


# ---------------------------------------------------------------------------
# wire codecs for the pooled exchange
# ---------------------------------------------------------------------------

WIRE_ITEMSIZE = {"float32": 4, "bfloat16": 2, "int8": 1}
# bytes of per-row side data: int8 ships one bf16 scale per pooled vector
WIRE_SCALE_BYTES = {"float32": 0, "bfloat16": 0, "int8": 2}
_WIRE_ALIASES = {None: "float32", "f32": "float32", "bf16": "bfloat16"}


def canon_wire(wire_dtype) -> str:
    """Normalize a wire-dtype spelling to the canonical codec name."""
    wire = _WIRE_ALIASES.get(wire_dtype, wire_dtype)
    if wire not in WIRE_ITEMSIZE:
        raise ValueError(f"unknown wire_dtype {wire_dtype!r}")
    return wire


def encode_wire(x, wire_dtype: str = "float32"):
    """x (..., D) -> codec pytree whose leaves all keep the leading axes of
    ``x`` (so any batch-split collective maps straight over the leaves).

    int8 carries one bf16 scale per pooled vector (per (sample, table) row),
    the grad_compression idiom at per-row granularity: pooled embedding
    magnitudes vary by orders of magnitude across tables, so a per-tensor
    scale would crush the cold tables' precision.  The scale is nudged up
    by one bf16 ulp before the down-cast so quantizing against the stored
    (coarser) scale can never push |q| past 127.
    """
    wire = canon_wire(wire_dtype)
    if wire == "float32":
        return {"q": x}
    if wire == "bfloat16":
        return {"q": x.astype(jnp.bfloat16)}
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                        1e-12) / 127.0
    scale = (scale * (1.0 + 2.0 ** -7)).astype(jnp.bfloat16)
    q = jnp.clip(jnp.round(xf / scale.astype(jnp.float32)),
                 -127, 127).astype(jnp.int8)
    return {"q": q, "scale": scale}


def decode_wire(payload, out_dtype=jnp.float32):
    q = payload["q"]
    if "scale" in payload:
        return (q.astype(jnp.float32) *
                payload["scale"].astype(jnp.float32)).astype(out_dtype)
    return q.astype(out_dtype)


# ---------------------------------------------------------------------------
# fused single-buffer wire (DESIGN.md §7)
# ---------------------------------------------------------------------------

# the fused slot is padded to a word multiple so the uint8 buffer can be
# re-viewed as int32 words by transports that prefer them
WIRE_ALIGN = 4


@dataclasses.dataclass(frozen=True)
class WireField:
    """One leaf of the fused wire slot: ``shape`` is per-destination (no
    leading n_dest axis); ``offset``/``nbytes`` locate its bytes in the
    slot."""
    name: str
    offset: int
    shape: tuple
    dtype: str

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * jnp.dtype(self.dtype).itemsize


@dataclasses.dataclass(frozen=True)
class WireLayout:
    """Static layout descriptor of a fused exchange buffer: ``n_dest``
    slots of ``slot_bytes`` bytes, each holding every payload leaf at a
    fixed offset.  Hashable, so it can close over a jitted stage as a
    trace-time constant."""
    n_dest: int
    fields: tuple  # of WireField, offset-ordered
    slot_bytes: int

    def field(self, name: str) -> WireField:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(f"wire layout has no field {name!r}; "
                       f"have {[f.name for f in self.fields]}")

    @property
    def names(self) -> tuple:
        return tuple(f.name for f in self.fields)

    @property
    def wire_bytes(self) -> int:
        """Bytes the fused exchange physically moves per member, layout
        padding included — ONE (P, slot_bytes) buffer, nothing else."""
        return self.n_dest * self.slot_bytes


def wire_layout(n_dest: int, fields: dict) -> WireLayout:
    """Build a WireLayout from ``{name: (per_dest_shape, dtype)}``.
    Field order is name-sorted (the order ``jax.tree`` flattens a dict),
    offsets are packed back to back, and the slot is padded up to
    ``WIRE_ALIGN`` bytes."""
    out, off = [], 0
    for name in sorted(fields):
        shape, dtype = fields[name]
        f = WireField(name, off, tuple(int(d) for d in shape),
                      str(jnp.dtype(dtype)))
        out.append(f)
        off += f.nbytes
    slot = -(-off // WIRE_ALIGN) * WIRE_ALIGN
    return WireLayout(int(n_dest), tuple(out), slot)


def _to_bytes(a):
    """(n, ...) leaf -> (n, nbytes) uint8 view (bitcast, not a cast)."""
    flat = a.reshape(a.shape[0], -1)
    if flat.dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(flat, jnp.uint8)
    b = jax.lax.bitcast_convert_type(flat, jnp.uint8)  # (n, m, itemsize)
    return b.reshape(flat.shape[0], -1)


def _from_bytes(b, shape, dtype):
    """(n, nbytes) uint8 -> (n, *shape) leaf of ``dtype`` (bitcast)."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 1:
        out = jax.lax.bitcast_convert_type(b, dt)
    else:
        out = jax.lax.bitcast_convert_type(
            b.reshape(b.shape[0], -1, dt.itemsize), dt)
    return out.reshape((b.shape[0],) + tuple(shape))


def fuse_wire(payload: dict, layout: WireLayout):
    """Pack a ``{name: (n_dest, ...)}`` payload into ONE contiguous
    ``(n_dest, slot_bytes)`` uint8 buffer per the layout.  Bitcasts only —
    the bytes on the wire are exactly the codec's bytes, so fuse/defuse
    round-trips bit-identically for every dtype."""
    if sorted(payload) != sorted(layout.names):
        raise ValueError(f"payload fields {sorted(payload)} != layout "
                         f"fields {sorted(layout.names)}")
    parts = []
    for f in layout.fields:
        a = payload[f.name]
        if a.shape[0] != layout.n_dest:
            raise ValueError(
                f"field {f.name!r}: leading dim {a.shape[0]} != n_dest "
                f"{layout.n_dest}")
        if jnp.dtype(a.dtype) != jnp.dtype(f.dtype):
            raise ValueError(f"field {f.name!r}: dtype {a.dtype} != layout "
                             f"{f.dtype}")
        b = _to_bytes(a)
        if b.shape[1] != f.nbytes:
            raise ValueError(f"field {f.name!r}: {b.shape[1]} B != layout "
                             f"{f.nbytes} B (shape {a.shape} vs {f.shape})")
        parts.append(b)
    pad = layout.slot_bytes - sum(f.nbytes for f in layout.fields)
    if pad:
        parts.append(jnp.zeros((layout.n_dest, pad), jnp.uint8))
    return jnp.concatenate(parts, axis=1)


def defuse_wire(buf, layout: WireLayout) -> dict:
    """Unpack a fused buffer back into its ``{name: leaf}`` payload.
    ``buf`` is either ``(n_src, slot_bytes)`` (a whole exchange) or a
    single ``(slot_bytes,)`` chunk (one ``ring_exchange`` round), in which
    case the leaves come back without the leading axis."""
    single = buf.ndim == 1
    if single:
        buf = buf[None]
    if buf.shape[-1] != layout.slot_bytes:
        raise ValueError(f"buffer slot is {buf.shape[-1]} B, layout says "
                         f"{layout.slot_bytes} B")
    out = {}
    for f in layout.fields:
        b = jax.lax.slice_in_dim(buf, f.offset, f.offset + f.nbytes, axis=1)
        leaf = _from_bytes(b, f.shape, f.dtype)
        out[f.name] = leaf[0] if single else leaf
    return out


def slot_id_dtype(n_slots: int):
    """Narrowest signed dtype addressing ``n_slots`` ragged-exchange slots
    (int16 when it fits, int32 fallback) — ids ship narrow and widen only
    after the exchange."""
    return jnp.int16 if n_slots <= 2 ** 15 else jnp.int32


def exchange_wire_layout(*, ragged: bool, n_dest: int, cap: int, bs: int,
                         t_loc: int, embed_dim: int,
                         wire_dtype: str = "float32",
                         emb_dtype=jnp.float32,
                         n_slots: int = 0,
                         delta_bytes: int = 0,
                         mig_bytes: int = 0,
                         rep_bytes: int = 0,
                         wire_check: bool = False) -> WireLayout:
    """The ONE layout both halves of a DLRM exchange agree on.

    ragged: per destination ``cap`` codec rows + narrow slot ids + an
    int32 count.  dense: the destination's full ``(bs, t_loc)`` pooled
    block.  ``emb_dtype`` is what a float32 codec ships verbatim (the
    pooled dtype); lossy codecs fix their own wire dtype.  ``n_slots``
    is the receive-slot address space the ragged ids must cover
    (default bs·t_loc) — it alone picks the id width.

    ``delta_bytes > 0`` adds ONE extra field, ``"xdelta"``: an opaque
    uint8 blob per destination carrying versioned embedding row deltas
    (DESIGN.md §10).  The blob's internal structure is its own
    :func:`delta_wire_layout`; from THIS layout's point of view it is a
    single byte field, so freshness updates ride the existing fused
    buffer and the exchange stays exactly one collective.

    ``mig_bytes > 0`` adds a second opaque field, ``"xmig"``, by the same
    construction (DESIGN.md §11): live resharding ships table rows from
    their current owner to their future owner inside the serving
    exchange.  Its internal structure is :func:`mig_wire_layout`; the
    exchange still issues exactly one collective with both riders
    aboard.

    ``rep_bytes > 0`` adds a third opaque field, ``"xrep"``, again by the
    same construction (DESIGN.md §12): integrity REPAIR rows from the
    host-side authoritative mirror to the owner of a quarantined row.
    Its internal structure is :func:`rep_wire_layout`.

    ``wire_check`` adds a ``"wcs"`` field — ONE uint32 per destination
    slot, stamped by the sender over the slot's remaining bytes
    (:func:`repro.core.integrity.wire_stamp`) and verified at consume in
    both the mono and ring paths.  This is the end-to-end check on the
    serving payload itself (pooled embeddings AND every rider): a flip
    anywhere between fuse and defuse rejects the whole segment."""
    wire = canon_wire(wire_dtype)
    qdt = {"float32": jnp.dtype(emb_dtype), "bfloat16": jnp.bfloat16,
           "int8": jnp.int8}[wire]
    if ragged:
        fields = {"q": ((cap, embed_dim), qdt),
                  "ids": ((cap,), slot_id_dtype(n_slots or bs * t_loc)),
                  "counts": ((1,), jnp.int32)}
        if wire == "int8":
            fields["scale"] = ((cap, 1), jnp.bfloat16)
    else:
        fields = {"q": ((bs, t_loc, embed_dim), qdt)}
        if wire == "int8":
            fields["scale"] = ((bs, t_loc, 1), jnp.bfloat16)
    if delta_bytes:
        fields["xdelta"] = ((int(delta_bytes),), jnp.uint8)
    if mig_bytes:
        fields["xmig"] = ((int(mig_bytes),), jnp.uint8)
    if rep_bytes:
        fields["xrep"] = ((int(rep_bytes),), jnp.uint8)
    if wire_check:
        fields["wcs"] = ((1,), jnp.uint32)
    return wire_layout(n_dest, fields)


def delta_wire_layout(n_dest: int, cap: int, embed_dim: int,
                      emb_dtype=jnp.float32) -> WireLayout:
    """Sub-layout of the versioned row-delta blob that rides the fused
    exchange as its single ``"xdelta"`` field (DESIGN.md §10): per
    destination up to ``cap`` new embedding rows (``dvec``), their flat
    global ids (``dgid`` = table · R_max + row), per-row uint32 checksums
    stamped at the update SOURCE (``dcs`` — corruption anywhere on the
    path is detected at apply time, not trusted), the valid-row count
    (``dcnt``) and the batch's monotone version (``dver``).  Fused and
    defused with the same :func:`fuse_wire`/:func:`defuse_wire` as the
    embedding payload — bitcasts only, so the checksum the source stamped
    is verified against the exact bytes that arrived."""
    return wire_layout(n_dest, {
        "dvec": ((cap, embed_dim), jnp.dtype(emb_dtype)),
        "dgid": ((cap,), jnp.int32),
        "dcs": ((cap,), jnp.uint32),
        "dcnt": ((1,), jnp.int32),
        "dver": ((1,), jnp.int32),
    })


def mig_wire_layout(n_dest: int, cap: int, embed_dim: int,
                    emb_dtype=jnp.float32) -> WireLayout:
    """Sub-layout of the live-resharding blob that rides the fused
    exchange as its single ``"xmig"`` field (DESIGN.md §11): per
    destination (= future owner) up to ``cap`` full-precision embedding
    rows (``mvec``) gathered by the CURRENT owner from its own shard,
    their flat ORIGINAL global ids (``mgid`` = table · R_max + row —
    placement-independent, so banked copies survive a cutover), per-row
    uint32 checksums stamped ON DEVICE by the shipper (``mcs`` — same
    fold as the freshness path's ``row_checksum``, verified host-side
    against the exact bytes that arrived), the valid-row count
    (``mcnt``) and the migration epoch (``mepoch`` — rows from an
    aborted epoch are discarded at the bank).  Same
    :func:`fuse_wire`/:func:`defuse_wire` bitcast discipline as the
    embedding payload and the delta blob."""
    return wire_layout(n_dest, {
        "mvec": ((cap, embed_dim), jnp.dtype(emb_dtype)),
        "mgid": ((cap,), jnp.int32),
        "mcs": ((cap,), jnp.uint32),
        "mcnt": ((1,), jnp.int32),
        "mepoch": ((1,), jnp.int32),
    })


def rep_wire_layout(n_dest: int, cap: int, embed_dim: int,
                    emb_dtype=jnp.float32) -> WireLayout:
    """Sub-layout of the integrity-repair blob that rides the fused
    exchange as its single ``"xrep"`` field (DESIGN.md §12): per
    destination (= owner of a quarantined row) up to ``cap`` known-good
    embedding rows (``rvec``) from the HOST-side authoritative mirror,
    their flat ORIGINAL global ids (``rgid`` = table · R_max + row), and
    per-row uint32 checksums stamped by the mirror over the exact bytes
    that ship (``rcs`` — the same :func:`repro.core.integrity.row_checksum`
    fold as the delta and migration riders, version 0: repairs restore
    bytes, they do not advance versions), plus the valid-row count
    (``rcnt``).  Same :func:`fuse_wire`/:func:`defuse_wire` bitcast
    discipline; the exchange still issues exactly one collective with
    all three riders aboard."""
    return wire_layout(n_dest, {
        "rvec": ((cap, embed_dim), jnp.dtype(emb_dtype)),
        "rgid": ((cap,), jnp.int32),
        "rcs": ((cap,), jnp.uint32),
        "rcnt": ((1,), jnp.int32),
    })


def alltoallv_fused(buf, axis: str = "model"):
    """The whole exchange as ONE collective: buf (P, slot_bytes) uint8,
    destination-major; returns (P, slot_bytes) where row q holds what
    source q sent here.  Counts, ids, scales all ride inside the slot —
    no side collectives (vs the up-to-4 per-leaf ``alltoallv_ragged``
    issues)."""
    with jax.named_scope("exchange"):
        return jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0,
                                  tiled=True)


def ring_exchange(buf, axis: str, n_dest: int, consume, init):
    """Chunked ppermute butterfly with per-peer consumption.

    buf (P, slot_bytes) destination-major; ``consume(carry, src, chunk)``
    folds one source's ``(slot_bytes,)`` chunk into the carry.  Round r
    (r = 1..P−1) ships slot (m+r) mod P with a shift-r ``ppermute`` and
    delivers source (m−r) mod P's chunk; each round's ppermute is ISSUED
    before the previous round's chunk is consumed, so chunk decode/compute
    overlaps the next shift's flight (XLA's latency-hiding scheduler sees
    them data-independent).  The own-destination chunk never touches the
    wire.  Consumption order (m, m−1, …, m−P+1 mod P) differs from the
    monolithic defuse's source order, so ``consume`` must be
    order-independent — the DLRM consumers write disjoint table slices,
    which is also why the result is bit-identical to the monolithic
    exchange."""
    p = int(n_dest)
    m = jax.lax.axis_index(axis)

    def take(i):
        return jax.lax.dynamic_index_in_dim(buf, i, axis=0, keepdims=False)

    # (src, chunk) available for consumption while the next shift flies
    ready = (m, take(m))
    out = init
    for r in range(1, p):
        perm = [(i, (i + r) % p) for i in range(p)]
        with jax.named_scope("exchange"):
            chunk = jax.lax.ppermute(take(jax.lax.rem(m + r, p)), axis,
                                     perm)
        out = consume(out, *ready)
        ready = (jax.lax.rem(m - r + p, p), chunk)
    return consume(out, *ready)


@dataclasses.dataclass(frozen=True)
class WireStats:
    """Byte accounting for one pooled butterfly exchange."""
    dense_bytes: int     # bytes the padded dense exchange moves at this codec
    live_bytes: int      # bytes of rows that carry information (>=1 miss)
    ref_bytes: int       # the f32 dense reference exchange
    live_rows: int
    total_rows: int

    @property
    def reduction_vs_ref(self) -> float:
        return 1.0 - self.live_bytes / max(self.ref_bytes, 1)


def wire_stats(miss_mask, embed_dim: int,
               wire_dtype: str = "float32") -> WireStats:
    """miss_mask (B, T, hot): the residual mask actually pooled onto the
    wire (the full mask when no cache).  A (sample, table) row whose bag is
    entirely cache hits pools to an exact zero and carries no information —
    ``live_bytes`` counts only rows with >=1 surviving index, which is what
    a ragged (cap-padded) exchange would move and what the acceptance
    criterion measures.  ``dense_bytes`` is what the equal-split butterfly
    moves regardless."""
    wire = canon_wire(wire_dtype)
    miss_mask = jax.device_get(miss_mask)
    rows_total = int(miss_mask.shape[0] * miss_mask.shape[1])
    rows_live = int((miss_mask > 0).any(axis=-1).sum())
    item = WIRE_ITEMSIZE[wire]
    scale_bytes = WIRE_SCALE_BYTES[wire]
    return WireStats(
        dense_bytes=rows_total * (embed_dim * item + scale_bytes),
        live_bytes=rows_live * (embed_dim * item + scale_bytes),
        ref_bytes=rows_total * embed_dim * 4,
        live_rows=rows_live,
        total_rows=rows_total,
    )


def alltoallv_raw(send, counts, axis: str = "model"):
    """send: (P, cap, D) padded per-destination buckets; counts: (P,) int32
    valid rows per bucket.  Returns (recv (P, cap, D), recv_counts (P,)).

    recv[q] holds the rows source q sent to this shard, of which
    recv_counts[q] are valid.  Semantically MPI_Alltoallv with bucket
    padding; the single-array form of :func:`alltoallv_ragged`.
    """
    return alltoallv_ragged(send, counts, axis)


def pack_ragged_tree(rows_tree, dest, n_dest: int, cap: int):
    """Scatter a pytree of row arrays (N, ...) sharing the leading axis into
    per-destination buckets (n_dest, cap, ...) + counts + drop count.

    dest (N,) int32; rows with dest outside [0, n_dest) are *excluded* (the
    caller's way of marking dead rows) and never counted as drops.  Rows
    with a valid destination whose bucket is already full ARE drops — the
    static-shape price of raggedness; the returned scalar is the signal the
    parity tests assert zero and the serving cap autotuner consumes.
    """
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    ds = dest[order]
    # bucket d owns sorted positions [bounds[d], bounds[d+1]); excluded
    # rows (dest < 0 / >= n_dest) sort outside every bucket's range.
    # Bucket slots then GATHER their source row — a scatter formulation is
    # semantically identical but serializes on CPU/TPU scatter units.
    bounds = jnp.searchsorted(ds, jnp.arange(n_dest + 1))
    count_all = bounds[1:] - bounds[:-1]
    counts = jnp.minimum(count_all, cap).astype(jnp.int32)
    drops = jnp.sum(count_all - counts).astype(jnp.int32)
    slot = jnp.arange(cap)[None, :]
    src = jnp.where(slot < counts[:, None],
                    bounds[:-1, None] + slot, n)       # n -> zero pad row
    # compose the sort permutation into the gather indices instead of
    # materializing sorted N-row copies of every leaf: only the
    # <= n_dest*cap rows that actually ship are ever touched
    src = jnp.where(src < n, order[jnp.minimum(src, n - 1)], n)
    return _gather_padded(rows_tree, src, n), counts, drops


def _gather_padded(rows_tree, src, n: int):
    """Gather rows ``src`` from every (N, ...) leaf, with index ``n``
    reading a zero pad row (the empty-bucket-slot encoding)."""

    def take(a):
        a_s = jnp.concatenate(
            [a, jnp.zeros((1,) + a.shape[1:], a.dtype)])
        return a_s[src]                                # (*src.shape, ...)

    return jax.tree.map(take, rows_tree)


def pack_ragged(rows, dest, n_dest: int, cap: int):
    """Single-array convenience wrapper around :func:`pack_ragged_tree`:
    rows (N, D) -> (buckets (n_dest, cap, D), counts (n_dest,), drops)."""
    return pack_ragged_tree(rows, dest, n_dest, cap)


def pack_ragged_segments(rows_tree, live, n_dest: int, cap: int):
    """:func:`pack_ragged_tree` specialized to destination-grouped rows:
    row n belongs to destination n // (N / n_dest) and ships iff
    ``live[n]``.  The pooled miss-residual exchange has exactly this
    layout (destination = sample // bs is non-decreasing in the flattened
    (sample, table) order), which lets the pack skip the argsort — the
    dominant pack cost — for a prefix sum + vectorized binary search over
    the live flags.  Same contract: (buckets, counts, drops)."""
    n = live.shape[0]
    l = live.astype(jnp.int32)
    csum = jnp.cumsum(l)
    count_all = l.reshape(n_dest, n // n_dest).sum(axis=1)
    starts = jnp.cumsum(count_all) - count_all
    counts = jnp.minimum(count_all, cap).astype(jnp.int32)
    drops = jnp.sum(count_all - counts).astype(jnp.int32)
    slot = jnp.arange(cap)[None, :]
    valid = slot < counts[:, None]
    # flat index of the g-th live row = first n with cumsum(live) == g+1
    g = starts[:, None] + slot
    src = jnp.where(valid, jnp.searchsorted(csum, g + 1), n)
    return _gather_padded(rows_tree, src, n), counts, drops


def alltoallv_ragged(payload, counts, axis: str = "model"):
    """Tree-shaped alltoallv: every leaf of ``payload`` is a (P, cap, ...)
    per-destination bucket stack; counts (P,) int32 valid rows per bucket.
    Returns (recv pytree, recv_counts) where recv leaf [q] holds what source
    q sent here, of which recv_counts[q] rows are valid.  The counts
    exchange is the (tiny) analogue of the paper's request-size
    negotiation."""
    recv = jax.tree.map(
        lambda a: jax.lax.all_to_all(a, axis, split_axis=0, concat_axis=0,
                                     tiled=True), payload)
    recv_counts = jax.lax.all_to_all(counts.reshape(-1, 1), axis, 0, 0,
                                     tiled=True).reshape(-1)
    return recv, recv_counts


def unpack_ragged(rows, slot_ids, counts, n_slots: int):
    """Scatter received bucket rows back into a dense row layout.

    rows (P, cap, D); slot_ids (P, cap) int32 flat target slots; counts
    (P,) valid rows per source bucket.  Entries beyond a bucket's count are
    dropped.  Slots nothing was sent for stay exactly zero — for the pooled
    miss-residual exchange those are the all-hit (or empty) bags, which
    pool to an exact zero in the dense exchange too, so the scatter is
    lossless.  Returns (n_slots, D)."""
    p, cap = slot_ids.shape
    valid = jnp.arange(cap)[None, :] < counts[:, None]
    tgt = jnp.where(valid, slot_ids, n_slots)          # OOB -> dropped
    flat = rows.reshape(p * cap, *rows.shape[2:])
    out = jnp.zeros((n_slots,) + flat.shape[1:], rows.dtype)
    return out.at[tgt.reshape(-1)].set(flat, mode="drop")


def ragged_wire_bytes(n_dest: int, cap: int, embed_dim: int,
                      wire_dtype: str = "float32", *,
                      n_slots: int) -> int:
    """Bytes ONE member physically moves through the FUSED ragged exchange:
    the single ``(n_dest, slot_bytes)`` buffer — cap-padded codec rows
    (+ per-row scales for int8), the narrow slot ids (int16 when
    ``n_slots`` = bs·t_loc fits, int32 otherwise), the per-destination
    count, and the layout's alignment padding.  Compare against
    ``wire_stats(...).live_bytes`` (the information-theoretic floor) and
    ``dense_wire_bytes`` (what the equal-split butterfly moves)."""
    return exchange_wire_layout(
        ragged=True, n_dest=n_dest, cap=cap, bs=0, t_loc=0,
        embed_dim=embed_dim, wire_dtype=wire_dtype,
        n_slots=n_slots).wire_bytes


def dense_wire_bytes(n_dest: int, bs: int, t_loc: int, embed_dim: int,
                     wire_dtype: str = "float32",
                     emb_dtype=jnp.float32) -> int:
    """Bytes ONE member moves through the fused dense butterfly: the
    single-buffer form of the equal-split exchange (codec rows + int8's
    per-row scales + alignment padding), i.e. the number the ragged
    exchange must undercut to be worth its ids and counts."""
    return exchange_wire_layout(
        ragged=False, n_dest=n_dest, cap=0, bs=bs, t_loc=t_loc,
        embed_dim=embed_dim, wire_dtype=wire_dtype,
        emb_dtype=emb_dtype).wire_bytes


def dispatch_stats(counts, cap: int, row_bytes: int,
                   slot_bytes: int = 0) -> A2AVStats:
    """Padding-waste accounting for one alltoallv call (host-side).
    ``slot_bytes`` (the fused wire's per-destination slot, from a
    ``WireLayout``) makes ``payload_bytes`` the single-buffer bytes the
    fused exchange physically moves — ids, counts and alignment padding
    included — instead of the rows-only estimate ``cap * row_bytes``."""
    counts = jax.device_get(counts)
    n_dest = counts.size
    total_slots = n_dest * cap
    useful = int(counts.sum())
    payload = n_dest * slot_bytes if slot_bytes else total_slots * row_bytes
    return A2AVStats(
        payload_bytes=payload,
        useful_bytes=useful * row_bytes,
        padding_fraction=1.0 - useful * row_bytes / max(payload, 1),
    )
