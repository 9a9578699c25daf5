"""The DMA-streamed embedding-bag kernel (DESIGN.md §1): interpret-mode
parity of the row-blocked streaming core and its ring of in-flight DMAs
against the pure-jnp oracles at rows >> row_block — bit-for-bit in f32,
including non-divisible row counts / batch sizes and indices landing
exactly on block boundaries — plus the row_block resolution policy (lane
tiles under auto), the ragged-row form, the scalar-vs-vector pool modes,
both stream-plan builders against a numpy recount, and the
precomputed-plan path (plan built off the critical path, consumed via
``plan=`` / ``forward_distributed`` / the engine's plan pipeline).
"""
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels import embedding_bag as eb

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def _case(t, r, s, b, hot, seed=0, boundary_rb=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    tbl = jax.random.normal(ks[0], (t, r, s))
    idx = jax.random.randint(ks[1], (b, t, hot), 0, r)
    if boundary_rb:
        # rows landing exactly on streamed-block boundaries, plus the
        # table edges (row 0 and the last row of a non-divisible table)
        rb = boundary_rb
        hits = [0, rb - 1, rb, 2 * rb - 1 if 2 * rb - 1 < r else r - 1,
                r - 1]
        for i, v in enumerate(hits):
            idx = idx.at[i % b, (i // b) % t, i % hot].set(v)
    mask = (jax.random.uniform(ks[2], (b, t, hot)) < 0.6) \
        .astype(jnp.float32)
    return tbl, idx, mask


class TestStreamedStackedParity:
    """Acceptance: streamed == ref bit-for-bit in f32 (interpret mode) for
    rows in {1k, 40k, 100k}, non-divisible row/batch sizes included."""

    @pytest.mark.parametrize("r,rb", [
        (1000, 192),        # non-divisible rows: overlapping final block
        (1000, 1024),       # rb > r: degenerates to one whole-table block
        (40_000, 4096),
        (100_000, 8192),    # rows >> row_block, ~13 blocks
        (100_003, 8192),    # prime-ish row count off every block boundary
    ])
    def test_bit_exact_vs_ref(self, r, rb):
        tbl, idx, mask = _case(2, r, 16, 16, 4, seed=r, boundary_rb=rb)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        got = ops.embedding_bag_stacked_op(tbl, idx, mask, row_block=rb,
                                           impl="interpret")
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("r,rb", [(1000, 192), (40_000, 4096),
                                      (100_000, 8192)])
    def test_dma_pipeline_bit_exact_vs_ref(self, r, rb):
        # the actual make_async_copy ring pipeline, executed by
        # the interpret machinery standalone (dma=True): the DMA schedule
        # itself must be bit-exact, not just the op-level emulation
        tbl, idx, mask = _case(2, r, 16, 16, 4, seed=r + 1, boundary_rb=rb)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        got = eb.embedding_bag_stacked(tbl, idx, mask, row_block=rb,
                                       interpret=True, dma=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_dma_pipeline_matches_emulation(self):
        # one schedule, two executors: async-copy kernel == jnp emulation
        tbl, idx, mask = _case(3, 2000, 16, 37, 4, seed=11, boundary_rb=256)
        via_dma = eb.embedding_bag_stacked(tbl, idx, mask, row_block=256,
                                           interpret=True, dma=True)
        via_jnp = eb.embedding_bag_stacked(tbl, idx, mask, row_block=256,
                                           interpret=True, dma=False)
        assert np.array_equal(np.asarray(via_dma), np.asarray(via_jnp))

    def test_non_divisible_batch_is_padded_internally(self):
        # 37 % 16 != 0 used to hard-assert; the tile tail is now masked
        tbl, idx, mask = _case(3, 500, 8, 37, 3, seed=7)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        for row_block in (0, 128):
            got = ops.embedding_bag_stacked_op(tbl, idx, mask,
                                               batch_tile=16,
                                               row_block=row_block,
                                               impl="interpret")
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                row_block

    def test_streamed_matches_resident_bitwise(self):
        tbl, idx, mask = _case(2, 2000, 16, 24, 4, seed=3, boundary_rb=256)
        resident = ops.embedding_bag_stacked_op(tbl, idx, mask,
                                                row_block=-1, impl="interpret")
        streamed = ops.embedding_bag_stacked_op(tbl, idx, mask,
                                                row_block=256,
                                                impl="interpret")
        assert np.array_equal(np.asarray(resident), np.asarray(streamed))

    def test_single_table_entry_point(self):
        tbl, idx, mask = _case(1, 1000, 16, 37, 4, seed=5, boundary_rb=192)
        want = ref.embedding_bag_ref(tbl[0], idx[:, 0], mask[:, 0])
        for row_block in (0, 192):
            got = ops.embedding_bag_op(tbl[0], idx[:, 0], mask[:, 0],
                                       batch_tile=16, row_block=row_block,
                                       impl="interpret")
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                row_block


class TestRowBlockPolicy:
    def test_auto_is_resident_when_block_fits(self):
        streamed, rb = eb.resolve_row_block(10_000, 64, 4, 0)
        assert not streamed and rb == 10_000

    def test_auto_streams_oversized_tables(self):
        r = 262_144                       # R = 256k: the acceptance size
        streamed, rb = eb.resolve_row_block(r, 64, 4, 0)
        assert streamed
        assert 2 * rb * 64 * 4 <= eb.STREAM_VMEM_BYTES
        assert rb % 8 == 0

    @pytest.mark.parametrize("r", [16_385, 262_144, 1_101_312])
    def test_auto_streams_in_lane_tiles(self, r):
        # a streamed stack's auto fetch unit is one 128-row lane tile,
        # whatever the VMEM budget would hold; a 10,000-row table at the
        # same width still resolves resident
        assert eb.resolve_row_block(r, 64, 4, 0) == (True, eb.LANES)
        assert eb.resolve_row_block(10_000, 64, 4, 0) == (False, 10_000)

    def test_ring_slots_fit_the_stream_budget(self):
        # lane tiles get the full ring; an explicit tall block shrinks it
        # to what STREAM_VMEM_BYTES holds (never below two slots), and a
        # tile that can touch fewer blocks gets no more slots than that
        assert eb._ring_slots(6400, 128, 64, 4) == eb.STREAM_SLOTS
        assert eb.STREAM_SLOTS * 128 * 64 * 4 <= eb.STREAM_VMEM_BYTES
        assert eb._ring_slots(3510, 8192, 64, 4) == 2
        assert eb._ring_slots(3510, 1 << 16, 64, 4) == 2
        assert eb._ring_slots(1, 128, 64, 4) == 1
        assert eb._ring_slots(5, 128, 64, 4) == 5

    def test_positive_row_block_forces_streaming(self):
        assert eb.resolve_row_block(100, 16, 4, 64) == (True, 64)
        # clipped to the table height
        assert eb.resolve_row_block(100, 16, 4, 4096) == (True, 100)

    def test_forced_resident_raises_past_budget(self):
        with pytest.raises(ValueError, match="VMEM budget"):
            eb.resolve_row_block(1 << 20, 64, 4, -1)

    def test_bogus_row_block_rejected(self):
        with pytest.raises(ValueError):
            eb.resolve_row_block(100, 16, 4, -2)

    def test_rows_form_shares_the_resolver(self):
        # every entry point validates row_block identically
        tbl = jnp.zeros((2, 10, 4))
        tid = jnp.zeros((3,), jnp.int32)
        idx = jnp.zeros((3, 2), jnp.int32)
        mask = jnp.ones((3, 2), jnp.float32)
        with pytest.raises(ValueError):
            eb.embedding_bag_rows(tbl, tid, idx, mask, row_block=-2,
                                  interpret=True)

    def test_explicit_block_clips_to_flat_stack_space(self):
        # the stacked streamed regime streams per-table blocks: a forced
        # block height past one table's R clips to R (one block per table)
        tbl, idx, mask = _case(4, 1000, 8, 8, 2, seed=9)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        got = ops.embedding_bag_stacked_op(tbl, idx, mask, row_block=2500,
                                           impl="interpret")
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_stage_tile_bounds_the_staging_accumulator(self):
        # every regime carries a (tile, hot, s) f32 staging buffer; the
        # tile must shrink so it stays inside the stage budget
        assert eb._stage_tile(64, 1000, 256, 128) == \
            eb.STAGE_VMEM_BYTES // (256 * 128 * 4)
        assert eb._stage_tile(64, 8, 4, 16) == 8       # never past b
        # parity survives the clamped tile (resident path, hot large
        # enough that batch_tile=64 would blow the budget)
        tbl, idx, mask = _case(1, 60, 128, 20, 256, seed=13)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        got = ops.embedding_bag_stacked_op(tbl, idx, mask, impl="interpret")
        assert np.array_equal(np.asarray(got), np.asarray(want))


class TestRowsKernel:
    """embedding_bag_rows: the ragged packed-row form on the same
    streaming core (the pool half of the ragged exchange)."""

    @pytest.mark.parametrize("r,rb,n", [
        (1000, 0, 40),          # auto: whole stack in one block
        (40_000, 4096, 40),     # streamed, rows >> row_block
        (40_000, 4096, 37),     # non-divisible row-tile count
    ])
    def test_bit_exact_vs_ref(self, r, rb, n):
        t, s, hot = 3, 16, 4
        ks = jax.random.split(jax.random.PRNGKey(n + r), 4)
        tbl = jax.random.normal(ks[0], (t, r, s))
        tid = jax.random.randint(ks[1], (n,), 0, t)
        idx = jax.random.randint(ks[2], (n, hot), 0, r)
        if rb:
            idx = idx.at[0, 0].set(rb - 1).at[1, 0].set(rb) \
                     .at[2, 0].set(r - 1)
        mask = (jax.random.uniform(ks[3], (n, hot)) < 0.5) \
            .astype(jnp.float32)
        want = ref.embedding_bag_rows_ref(tbl, tid, idx, mask)
        got = ops.embedding_bag_rows_op(tbl, tid, idx, mask, row_tile=16,
                                        row_block=rb, impl="interpret")
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_oob_ids_clip_like_ref(self):
        tbl = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 8))
        tid = jnp.asarray([0, 1, 1], jnp.int32)
        idx = jnp.asarray([[0, 49], [99, -3], [7, 50]], jnp.int32)
        mask = jnp.ones((3, 2), jnp.float32)
        want = ref.embedding_bag_rows_ref(tbl, tid, idx, mask)
        got = ops.embedding_bag_rows_op(tbl, tid, idx, mask, row_block=16,
                                        impl="interpret")
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_dead_rows_pool_to_exact_zero(self):
        # the ragged pack's cap padding: id 0 / mask 0 slots must stay 0
        tbl = jax.random.normal(jax.random.PRNGKey(1), (2, 300, 8))
        tid = jnp.zeros((8,), jnp.int32)
        idx = jnp.zeros((8, 4), jnp.int32)
        mask = jnp.zeros((8, 4), jnp.float32)
        got = ops.embedding_bag_rows_op(tbl, tid, idx, mask, row_block=64,
                                        impl="interpret")
        assert float(jnp.max(jnp.abs(got))) == 0.0


class TestVectorPool:
    """The vectorized chunked-gather pool (DESIGN.md §1): bit-exact f32
    parity against the scalar walk and the jnp oracle across hot factors
    (1 / lane-fraction / non-lane-multiple 33), non-lane-multiple batch
    and segment lengths, all-masked bags, and block-boundary ids — for the
    resident, streamed (real DMA pipeline) and ragged-row kernel forms."""

    @pytest.mark.parametrize("hot", [1, 4, 33])
    def test_resident_scalar_vector_oracle_bit_exact(self, hot):
        # b=37, t=2 -> flat index list of 74*hot, never a POOL_CHUNK
        # multiple; hot=33 also makes every bag straddle a chunk tail
        tbl, idx, mask = _case(2, 500, 16, 37, hot, seed=hot)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        sc = ops.embedding_bag_stacked_op(tbl, idx, mask, batch_tile=16,
                                          pool_mode="scalar", impl="interpret")
        ve = ops.embedding_bag_stacked_op(tbl, idx, mask, batch_tile=16,
                                          pool_mode="vector", impl="interpret")
        assert np.array_equal(np.asarray(sc), np.asarray(want))
        assert np.array_equal(np.asarray(ve), np.asarray(want))

    @pytest.mark.parametrize("hot", [1, 4, 33])
    @pytest.mark.parametrize("plan_method", ["sort", "count"])
    def test_streamed_dma_scalar_vector_oracle_bit_exact(self, hot,
                                                         plan_method):
        # the actual make_async_copy pipeline in both pool modes, with
        # boundary ids: segment lengths are whatever the random ids give,
        # never lane multiples
        tbl, idx, mask = _case(2, 2000, 16, 24, hot, seed=40 + hot,
                               boundary_rb=256)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        for pool in ("scalar", "vector"):
            got = eb.embedding_bag_stacked(
                tbl, idx, mask, row_block=256, pool_mode=pool,
                interpret=True, dma=True, plan_method=plan_method)
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                (pool, hot, plan_method)

    def test_single_table_vector(self):
        tbl, idx, mask = _case(1, 800, 8, 37, 3, seed=7, boundary_rb=128)
        want = ref.embedding_bag_ref(tbl[0], idx[:, 0], mask[:, 0])
        for row_block in (0, 128):
            got = ops.embedding_bag_op(tbl[0], idx[:, 0], mask[:, 0],
                                       batch_tile=16, row_block=row_block,
                                       pool_mode="vector", impl="interpret")
            assert np.array_equal(np.asarray(got), np.asarray(want)), \
                row_block

    def test_rows_form_vector(self):
        ks = jax.random.split(jax.random.PRNGKey(9), 4)
        tbl = jax.random.normal(ks[0], (3, 5000, 8))
        tid = jax.random.randint(ks[1], (37,), 0, 3)
        idx = jax.random.randint(ks[2], (37, 4), 0, 5000)
        mask = (jax.random.uniform(ks[3], (37, 4)) < 0.5) \
            .astype(jnp.float32)
        want = ref.embedding_bag_rows_ref(tbl, tid, idx, mask)
        got = ops.embedding_bag_rows_op(tbl, tid, idx, mask, row_tile=16,
                                        row_block=512, pool_mode="vector",
                                        impl="interpret")
        assert np.array_equal(np.asarray(got), np.asarray(want))
        # and through the real DMA pipeline
        got_dma = eb.embedding_bag_rows(tbl, tid, idx, mask, row_tile=16,
                                        row_block=512, pool_mode="vector",
                                        interpret=True, dma=True)
        assert np.array_equal(np.asarray(got_dma), np.asarray(want))

    def test_all_masked_bags_stay_exact_zero(self):
        tbl, idx, _ = _case(2, 600, 8, 19, 4, seed=3)
        zero = jnp.zeros((19, 2, 4), jnp.float32)
        for pool in ("scalar", "vector"):
            res = ops.embedding_bag_stacked_op(tbl, idx, zero,
                                               pool_mode=pool,
                                               impl="interpret")
            st = eb.embedding_bag_stacked(tbl, idx, zero, row_block=128,
                                          pool_mode=pool, interpret=True,
                                          dma=True)
            assert float(jnp.max(jnp.abs(res))) == 0.0, pool
            assert float(jnp.max(jnp.abs(st))) == 0.0, pool

    def test_bogus_pool_mode_rejected(self):
        tbl, idx, mask = _case(1, 100, 8, 4, 2)
        with pytest.raises(ValueError, match="pool_mode"):
            eb.embedding_bag_stacked(tbl, idx, mask, pool_mode="simd",
                                     interpret=True)


def _ring_case(t, r, rb, b, hot, spread, seed):
    """Stacked inputs whose tile touches fewer blocks than the DMA ring
    holds (``few``: ids in the first three blocks) or many times more
    (``many``: ids over the whole table), with ids on lane-tile edges,
    block edges and table edges (row 0 and the last row)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    tbl = jax.random.normal(ks[0], (t, r, 16))
    hi = min(3 * rb, r) if spread == "few" else r
    idx = jax.random.randint(ks[1], (b, t, hot), 0, hi)
    edges = [0, eb.LANES - 1, eb.LANES, rb - 1, rb, hi - 1, r - 1]
    if spread == "few":
        edges = [e for e in edges if e < hi]
    for i, v in enumerate(edges):
        idx = idx.at[i % b, i % t, (i // t) % hot].set(v)
    mask = (jax.random.uniform(ks[2], (b, t, hot)) < 0.6) \
        .astype(jnp.float32)
    return tbl, idx, mask


class TestLaneTileRing:
    """The streamed kernel's ring of STREAM_SLOTS in-flight copies, run as
    the real make_async_copy pipeline in interpret mode (``dma=True``):
    bit-identical in f32 to the jnp oracle and to the schedule emulation,
    at lane-tile and taller block heights, with fewer touched blocks than
    slots and many more, an empty tile, all-masked bags and the rows
    form."""

    @pytest.mark.parametrize("spread", ["few", "many"])
    @pytest.mark.parametrize("rb", [128, 256, 1024])
    def test_ring_bit_exact_vs_ref_and_emulation(self, rb, spread):
        tbl, idx, mask = _ring_case(2, 40_000, rb, 16, 8, spread, seed=rb)
        plan = eb.stacked_stream_plan(2, 40_000, 16, 4, idx, row_block=rb)
        nblk = int(plan.nblk.max())
        if spread == "few":
            assert nblk < eb.STREAM_SLOTS
        else:
            assert nblk > 4 * eb.STREAM_SLOTS
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        emu = eb.embedding_bag_stacked(tbl, idx, mask, row_block=rb,
                                       interpret=True, dma=False)
        assert np.array_equal(np.asarray(emu), np.asarray(want))
        for pool in ("scalar", "vector"):
            got = eb.embedding_bag_stacked(tbl, idx, mask, row_block=rb,
                                           pool_mode=pool, interpret=True,
                                           dma=True)
            assert np.array_equal(np.asarray(got), np.asarray(want)), pool

    def test_ring_of_three_slots_wraps_bit_exact(self, monkeypatch):
        # an odd ring that wraps dozens of times over one tile's blocks
        monkeypatch.setattr(eb, "STREAM_SLOTS", 3)
        tbl, idx, mask = _ring_case(2, 40_000, 128, 16, 8, "many", seed=3)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        got = eb.embedding_bag_stacked(tbl, idx, mask, row_block=128,
                                       interpret=True, dma=True)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("rb", [128, 256, 1024])
    def test_all_masked_bags_pool_exact_zero(self, rb):
        tbl, idx, _ = _ring_case(2, 40_000, rb, 16, 8, "many", seed=rb + 1)
        zero = jnp.zeros(idx.shape, jnp.float32)
        for pool in ("scalar", "vector"):
            got = eb.embedding_bag_stacked(tbl, idx, zero, row_block=rb,
                                           pool_mode=pool, interpret=True,
                                           dma=True)
            assert float(jnp.max(jnp.abs(got))) == 0.0, pool

    @pytest.mark.parametrize("rb", [128, 256, 1024])
    def test_tile_with_no_blocks_pools_zero(self, rb):
        # nblk = 0: the ring starts and waits for no copy, and the tile's
        # bags come out zero in both executors; the other tiles are exact
        tbl, idx, mask = _ring_case(2, 40_000, rb, 16, 8, "many",
                                    seed=rb + 2)
        plan = eb.stacked_stream_plan(2, 40_000, 16, 4, idx, batch_tile=8,
                                      row_block=rb)
        assert plan.nblk.shape[0] == 4
        plan = plan._replace(nblk=plan.nblk.at[1].set(0))
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        want = np.array(want).reshape(-1, 16)
        want[8:16] = 0.0                   # bags of tile 1
        for kw in ({"dma": False}, {"dma": True, "pool_mode": "scalar"},
                   {"dma": True, "pool_mode": "vector"}):
            got = eb.embedding_bag_stacked(tbl, idx, mask, batch_tile=8,
                                           row_block=rb, interpret=True,
                                           plan=plan, **kw)
            assert np.array_equal(np.asarray(got).reshape(-1, 16), want), kw

    @pytest.mark.parametrize("rb", [128, 256, 1024])
    def test_rows_form_bit_exact(self, rb):
        ks = jax.random.split(jax.random.PRNGKey(rb), 4)
        t, r, n, hot = 3, 40_000, 24, 6
        tbl = jax.random.normal(ks[0], (t, r, 16))
        tid = jax.random.randint(ks[1], (n,), 0, t)
        idx = jax.random.randint(ks[2], (n, hot), 0, r)
        idx = idx.at[0, 0].set(eb.LANES - 1).at[1, 0].set(eb.LANES) \
                 .at[2, 0].set(rb).at[3, 0].set(r - 1).at[4, 0].set(0)
        mask = (jax.random.uniform(ks[3], (n, hot)) < 0.6) \
            .astype(jnp.float32)
        want = ref.embedding_bag_rows_ref(tbl, tid, idx, mask)
        emu = eb.embedding_bag_rows(tbl, tid, idx, mask, row_tile=8,
                                    row_block=rb, interpret=True, dma=False)
        assert np.array_equal(np.asarray(emu), np.asarray(want))
        for pool in ("scalar", "vector"):
            got = eb.embedding_bag_rows(tbl, tid, idx, mask, row_tile=8,
                                        row_block=rb, pool_mode=pool,
                                        interpret=True, dma=True)
            assert np.array_equal(np.asarray(got), np.asarray(want)), pool


class TestPrecomputedPlan:
    """plan= consumption: a StreamPlan built off the critical path drops
    into every executor (emulation, scalar DMA kernel, vector DMA kernel)
    bit-identically, and misuse fails loudly."""

    def test_stacked_plan_all_executors_agree(self):
        tbl, idx, mask = _case(3, 1500, 8, 37, 4, seed=99,
                               boundary_rb=192)
        want = ref.embedding_bag_stacked_ref(tbl, idx, mask)
        plan = eb.stacked_stream_plan(3, 1500, 8, 4, idx, row_block=192)
        for kw in ({"dma": False}, {"dma": True, "pool_mode": "scalar"},
                   {"dma": True, "pool_mode": "vector"}):
            got = eb.embedding_bag_stacked(tbl, idx, mask, row_block=192,
                                           interpret=True, plan=plan, **kw)
            assert np.array_equal(np.asarray(got), np.asarray(want)), kw

    def test_stacked_plan_is_none_for_resident_geometry(self):
        idx = jnp.zeros((8, 2, 4), jnp.int32)
        assert eb.stacked_stream_plan(2, 1000, 16, 4, idx,
                                      row_block=0) is None

    def test_plan_built_for_other_row_block_raises(self):
        # leaf shapes cannot always distinguish two block heights (nbmax
        # clamps to L); the plan's static rb/total_rows metadata must
        # catch the mismatch loudly instead of gathering wrong rows
        tbl, idx, mask = _case(3, 1500, 8, 37, 4, seed=5)
        plan = eb.stacked_stream_plan(3, 1500, 8, 4, idx, row_block=192)
        tampered = plan._replace(rb=plan.rb // 2)
        with pytest.raises(ValueError, match="geometry"):
            eb.embedding_bag_stacked(tbl, idx, mask, row_block=192,
                                     interpret=True, plan=tampered)

    def test_plan_on_resident_call_raises(self):
        tbl, idx, mask = _case(2, 500, 16, 8, 4)
        plan = eb.stacked_stream_plan(2, 500, 16, 4, idx, row_block=64)
        with pytest.raises(ValueError, match="resident"):
            eb.embedding_bag_stacked(tbl, idx, mask, row_block=0,
                                     plan=plan, interpret=True)


def test_forward_distributed_precomputed_plan_and_engine_pipeline():
    """Distributed + serving integration of the plan/compute overlap:
    forward_distributed(plan=build_forward_plans(...)) is bit-identical to
    inline planning across bounds/microbatches (cache on and off), a plan
    combined with the ragged exchange raises, and a plan_pipeline engine
    (plan for flush n+1 dispatched while flush n's step is in flight)
    reproduces the inline engine's CTR stream exactly."""
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.data import synthetic as S
from repro.serving import hot_cache as HC
from repro.serving.engine import DLRMEngine
from repro.sharding import partition

cfg = DLRMConfig(name="t", table_sizes=(100, 50, 80, 60, 90, 40),
                 embed_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
                 max_hot=4, sparse_backend="interpret", row_block=32,
                 exchange="dense")
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4)
b = S.make_batch(cfg, 64, mode="hetero", t_pad=D.padded_tables(cfg, 4),
                 seed=1)
dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
cache = HC.build_from_batch(params["tables"], b.idx, b.mask, 40)
with partition.axis_rules(mesh):
    for bound, mb in [(0, 1), (2, 4)]:
        for c in (None, cache):
            inline = D.forward_distributed(params, cfg, dense, idx, mask,
                                           bound=bound, microbatches=mb,
                                           cache=c)
            plan = D.build_forward_plans(params, cfg, idx,
                                         microbatches=mb, cache=c)
            assert plan is not None
            pre = D.forward_distributed(params, cfg, dense, idx, mask,
                                        bound=bound, microbatches=mb,
                                        cache=c, plan=plan)
            assert jnp.array_equal(inline, pre), (bound, mb, c is None)
    # ragged exchange + precomputed plan is a loud error, and the builder
    # refuses to build one for a ragged-resolving config
    try:
        D.forward_distributed(params, cfg, dense, idx, mask, cache=cache,
                              exchange="ragged", plan=plan)
        raise SystemExit("expected ValueError")
    except ValueError:
        pass
    assert D.build_forward_plans(params, cfg, idx, cache=cache,
                                 exchange="ragged") is None
    assert D.build_forward_plans(params, cfg.replace(sparse_backend="ref"),
                                 idx) is None
    assert D.build_forward_plans(params, cfg.replace(row_block=0),
                                 idx) is None
    # engine-level: pipelined plans change the schedule, never the CTRs
    outs = {}
    t_pad = D.padded_tables(cfg, 4)
    for name, pp in [("inline", False), ("pipelined", True)]:
        eng = DLRMEngine(params, cfg, batch_size=32, bound=2,
                         microbatches=2, plan_pipeline=pp)
        got = []
        for step in range(4):
            bb = S.make_batch(cfg, 32, mode="hetero", seed=7, step=step,
                              t_pad=t_pad)
            for i in range(32):
                r = eng.submit(bb.dense[i], bb.idx[i], bb.mask[i])
                if r is not None:
                    got.append(r)
        tail = eng.drain()
        if tail is not None:
            got.append(tail)
        outs[name] = np.concatenate(got)
        assert eng.stats.batches == 4, eng.stats
    assert outs["inline"].shape == outs["pipelined"].shape
    assert np.array_equal(outs["inline"], outs["pipelined"])
print("OK")
""")


class TestStreamPlan:
    """The XLA-side pre-bucketing: block-grouped segments + compacted block
    list, from either builder (argsort / counting sort)."""

    @pytest.mark.parametrize("method", ["sort", "count"])
    def test_plan_covers_every_position_once(self, method):
        gid = jnp.asarray([[5, 900, 2, 901, 5, 0]], jnp.int32)
        rb, rtot = 128, 1000
        nbmax = min(-(-rtot // rb), 6)
        p = eb._stream_plan(gid, rb, rtot, nbmax, method)
        n = int(p.nblk[0, 0])
        assert n == 2                      # blocks 0 and 7 only — compacted
        # every position belongs to exactly one compacted block, blocks in
        # order, each block's positions one contiguous run, and each id
        # falls inside its block's DMA window
        cum = np.asarray(p.cum[0])
        assert cum[0] == 0 and cum[-1] == n - 1
        assert (np.diff(cum) >= 0).all() and (np.diff(cum) <= 1).all()
        for q in range(6):
            lo = int(p.off[0, cum[q]])
            assert lo <= int(p.sid[0, q]) < lo + rb
        # pos is a bijection and inv is its inverse (staging-slot keys)
        pos = np.asarray(p.pos[0])
        assert sorted(pos.tolist()) == list(range(6))
        assert np.array_equal(np.asarray(p.inv[0])[pos], np.arange(6))

    @pytest.mark.parametrize("method", ["sort", "count"])
    def test_last_block_dma_is_clamped_in_bounds(self, method):
        gid = jnp.asarray([[999, 0]], jnp.int32)
        p = eb._stream_plan(gid, 128, 1000, 2, method)
        offs = np.asarray(p.off[0, :int(p.nblk[0, 0])])
        assert (offs + 128 <= 1000).all() and (offs >= 0).all()

    def test_count_matches_sort_block_structure(self):
        # same compacted blocks, offsets and block runs from both builders
        # (within-block order may differ; nothing consumes it)
        gid = jax.random.randint(jax.random.PRNGKey(0), (3, 64), 0, 1000,
                                 dtype=jnp.int32)
        nbmax = min(-(-1000 // 96), 64)
        ps = eb._stream_plan(gid, 96, 1000, nbmax, "sort")
        pc = eb._stream_plan(gid, 96, 1000, nbmax, "count")
        for f in ("off", "nblk", "cum"):
            assert np.array_equal(np.asarray(getattr(ps, f)),
                                  np.asarray(getattr(pc, f))), f
        # both are bijections over every tile
        for t in range(3):
            for p in (ps, pc):
                assert sorted(np.asarray(p.pos[t]).tolist()) == \
                    list(range(64))

    def test_auto_method_obeys_work_budget(self):
        assert eb._resolve_plan_method("auto", 64, 8) == "count"
        big_L = eb.PLAN_COUNT_WORK  # L * nb past the budget -> sort
        assert eb._resolve_plan_method("auto", big_L, 2) == "sort"
        with pytest.raises(ValueError):
            eb._resolve_plan_method("radix", 64, 8)

    def test_build_stream_plan_matches_stream_rows_geometry(self):
        # a plan built outside must drop into _stream_rows unchanged, and
        # a plan built at the wrong geometry must be rejected loudly
        gid = jax.random.randint(jax.random.PRNGKey(1), (40, 4), 0, 2000,
                                 dtype=jnp.int32)
        plan = eb.build_stream_plan(2000, 16, gid, row_tile=16, rb=256)
        tbl = jax.random.normal(jax.random.PRNGKey(2), (1, 2000, 16))
        w = jnp.ones((40, 4), jnp.float32)
        a = eb._stream_rows(tbl, gid, w, row_tile=16, rb=256,
                            interpret=True, out_dtype=jnp.float32)
        b = eb._stream_rows(tbl, gid, w, row_tile=16, rb=256,
                            interpret=True, out_dtype=jnp.float32,
                            plan=plan)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        bad = eb.build_stream_plan(2000, 16, gid, row_tile=8, rb=256)
        with pytest.raises(ValueError, match="geometry"):
            eb._stream_rows(tbl, gid, w, row_tile=16, rb=256,
                            interpret=True, out_dtype=jnp.float32,
                            plan=bad)

    @pytest.mark.parametrize("nbmax_at", ["below_L", "at_L"])
    @pytest.mark.parametrize("ids", ["random", "skewed", "one_block"])
    def test_sort_plan_matches_numpy_recount(self, ids, nbmax_at):
        tiles, L, rb, t = 3, 64, 128, 2
        rows = 1024 if nbmax_at == "below_L" else 100_000
        nb_total = t * -(-rows // rb)
        nbmax = min(nb_total, L)
        assert (nbmax < L) == (nbmax_at == "below_L")
        rng = np.random.default_rng(len(ids) + rows)
        if ids == "random":
            local = rng.integers(0, rows, (tiles, L))
        elif ids == "skewed":
            local = np.minimum(rng.zipf(1.05, (tiles, L)) - 1, rows - 1)
        else:
            local = rng.integers(2 * rb, 3 * rb, (tiles, L))
        gid = (rng.integers(0, t, (tiles, L)) * rows + local) \
            .astype(np.int32)
        p = eb._stream_plan(jnp.asarray(gid), rb, t * rows, nbmax, "sort",
                            rows)
        for i in range(tiles):
            sid = np.sort(gid[i])
            blk = (sid // rows) * -(-rows // rb) + (sid % rows) // rb
            starts = np.flatnonzero(np.r_[True, blk[1:] != blk[:-1]])
            n = len(starts)
            ends = np.r_[starts[1:], L]
            bid = blk[starts]
            nbt = -(-rows // rb)
            off = (bid // nbt) * rows + np.clip((bid % nbt) * rb, 0,
                                                rows - rb)
            pad = np.zeros(nbmax - n, np.int64)
            assert int(p.nblk[i, 0]) == n
            assert np.array_equal(np.asarray(p.sid[i]), sid)
            assert np.array_equal(np.asarray(p.off[i]), np.r_[off, pad])
            assert np.array_equal(np.asarray(p.cum[i]),
                                  np.repeat(np.arange(n), ends - starts))
            pos = np.asarray(p.pos[i])
            assert np.array_equal(gid[i][pos], sid)
            assert np.array_equal(np.asarray(p.inv[i])[pos], np.arange(L))
        if ids == "one_block":
            assert (np.asarray(p.nblk) <= t).all()

    @pytest.mark.parametrize("rb", [128, 8192])
    def test_sort_plan_lowers_without_while(self, rb):
        # block runs come from a cumsum and offsets from a second sort, not
        # a searchsorted loop: nothing in the plan grows with nbmax, and
        # the plan holds no batched gather either
        rows = 1_101_312
        gid = jax.ShapeDtypeStruct((52, 6400), jnp.int32)
        nbmax = min(26 * -(-rows // rb), 6400)
        hlo = jax.jit(lambda g: eb._stream_plan(
            g, rb, 26 * rows, nbmax, "sort", rows)).lower(gid).as_text()
        assert "sort" in hlo and not re.search(r"\bwhile\(", hlo)
        assert "gather" not in hlo and "scatter" not in hlo

    @pytest.mark.parametrize("rb", [128, 1024])
    def test_fetch_bytes_counts_touched_units(self, rb):
        t, r, s, b, hot = 3, 40_000, 16, 24, 5
        idx = jax.random.randint(jax.random.PRNGKey(rb), (b, t, hot), 0, r)
        plan = eb.stacked_stream_plan(t, r, s, 4, idx, batch_tile=8,
                                      row_block=rb)
        # numpy: distinct (table, block) pairs per 8-bag tile
        gid = (np.arange(t)[None, :, None] * r + np.asarray(idx)) \
            .reshape(-1, 8 * hot)
        units = sum(len(np.unique(g // r * (r // rb + 1) + g % r // rb))
                    for g in gid)
        assert eb.fetch_bytes(plan, s, 4) == (units, units * rb * s * 4)
