"""The DMA-streamed embedding-bag kernel (DESIGN.md §1): interpret-mode
parity of the row-blocked, double-buffered streaming core against the
pure-jnp oracles at rows >> row_block — bit-for-bit in f32, including
non-divisible row counts / batch sizes and indices landing exactly on block
boundaries — plus the row_block resolution policy, the ragged-row form,
the scalar-vs-vector pool modes, the counting-sort stream plan, and the
precomputed-plan path (plan built off the critical path, consumed via
``plan=`` / ``forward_distributed`` / the engine's plan pipeline).
"""
import os
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
        # the actual make_async_copy double-buffer pipeline, executed by
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
        segs = [(int(p.seg0[0, j]), int(p.seg1[0, j])) for j in range(n)]
        covered = sorted(sum([list(range(a, b)) for a, b in segs], []))
        assert covered == list(range(6))   # every position exactly once
        # each segment's ids fall inside its block's DMA window, and the
        # membership mask (cum) agrees with the segment bounds
        for j, (a, b) in enumerate(segs):
            lo = int(p.off[0, j])
            for q in range(a, b):
                assert lo <= int(p.sid[0, q]) < lo + rb
                assert int(p.cum[0, q]) == j
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
        # same compacted blocks, offsets and segment bounds from both
        # builders (within-block order may differ; nothing consumes it)
        gid = jax.random.randint(jax.random.PRNGKey(0), (3, 64), 0, 1000,
                                 dtype=jnp.int32)
        nbmax = min(-(-1000 // 96), 64)
        ps = eb._stream_plan(gid, 96, 1000, nbmax, "sort")
        pc = eb._stream_plan(gid, 96, 1000, nbmax, "count")
        for f in ("off", "seg0", "seg1", "nblk"):
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
