"""Skew-aware table placement + crash-safe online resharding
(DESIGN.md §11): live row migration over the fused wire, atomic
cutover, and rebalance-after-evict.

The invariants under test:
  * **Minimal planning** — LPT under the equal-cardinality constraint;
    keepers keep their physical slots, so the plan ships only the rows
    whose owner actually changes;
  * **Zero extra collectives** — the migration sub-blob ("xmig") and the
    placement gather ride the SAME fused buffer / traced step: one
    all_to_all (mono) / P−1 ppermutes (ring) in the jaxpr, placement
    or not;
  * **Bit-exact serving THROUGH a reshard** — every flush before,
    during, and after a cutover returns byte-identical CTRs vs a plain
    engine on the boot layout, across {mono, ring} × wire codec;
  * **Crash safety at every stage** — a member killed at ship / bank /
    verify / install / commit recovers via evict → replay with zero
    requests lost and real table rows bit-exact on the surviving
    geometry;
  * **Freshness across the cutover** — versioned deltas route to the
    CURRENT owner on both sides of the swap and still converge to the
    apply-all-up-front oracle.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.runtime import placement as plc
from repro.runtime.faults import FaultPlan
from repro.runtime.reshard import MIG_KEYS, MIG_STAGES
from repro.runtime.straggler import CapAutotuner, StragglerMonitor

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


# ---------------------------------------------------------------------------
# PartitionMap: the layout algebra
# ---------------------------------------------------------------------------


class TestPartitionMap:
    def test_identity_roundtrip_and_owner(self):
        pm = plc.PartitionMap.identity(8)
        assert pm.is_identity and pm.t_pad == 8
        assert np.array_equal(pm.perm_array(), pm.inv_array())
        assert [pm.owner_of(t, 4) for t in range(8)] == \
            [0, 0, 1, 1, 2, 2, 3, 3]

    def test_inverse_is_inverse(self):
        pm = plc.PartitionMap((3, 1, 0, 2))
        perm, inv = pm.perm_array(), pm.inv_array()
        assert np.array_equal(perm[inv], np.arange(4))
        assert np.array_equal(inv[perm], np.arange(4))
        assert not pm.is_identity

    def test_owners_follow_slots_not_tables(self):
        # table 3 sits in slot 0 -> member 0 owns it
        pm = plc.PartitionMap((3, 1, 0, 2))
        assert pm.owner_of(3, 2) == 0 and pm.owner_of(0, 2) == 1
        assert np.array_equal(pm.owners(2), [1, 0, 1, 0])

    def test_rejects_non_permutation(self):
        with pytest.raises(ValueError):
            plc.PartitionMap((0, 0, 1, 2))


# ---------------------------------------------------------------------------
# LPT assignment + migration planning
# ---------------------------------------------------------------------------


class TestPlanning:
    def test_lpt_balances_under_equal_cardinality(self):
        loads = np.array([8.0, 7, 6, 5, 4, 3, 2, 1])
        owner, ml = plc.lpt_assign(loads, 4)
        counts = np.bincount(owner, minlength=4)
        assert (counts == 2).all()                      # cardinality
        assert plc.imbalance(ml) == 1.0                 # 9 each

    def test_incumbent_wins_ties(self):
        loads = np.ones(4)
        prefer = np.array([1, 0, 1, 0])
        owner, _ = plc.lpt_assign(loads, 2, prefer=prefer)
        assert np.array_equal(owner, prefer)            # zero moves

    def test_plan_keepers_keep_slots_and_moves_are_minimal(self):
        cur = plc.PartitionMap.identity(4)
        loads = np.array([10.0, 1, 10, 1])   # m0={10,1} m1={10,1}: level
        plan = plc.plan_migration(cur, loads, 2,
                                  table_rows=np.array([5, 5, 5, 5]))
        assert plan.is_noop and plan.new_map is cur

    def test_plan_moves_only_owner_changes(self):
        cur = plc.PartitionMap.identity(4)
        loads = np.array([10.0, 9, 1, 2])    # m0=19 m1=3 -> swap one
        rows = np.array([7, 8, 9, 6])
        plan = plc.plan_migration(cur, loads, 2, table_rows=rows)
        assert not plan.is_noop
        assert plan.imbalance_after < plan.imbalance_before
        moved = {t for t, _, _, _ in plan.moves}
        for ti in range(4):
            if ti not in moved:              # keeper -> same slot
                assert plan.new_map.inv_array()[ti] == \
                    cur.inv_array()[ti]
        assert plan.moved_rows == sum(rows[t] for t in moved)

    def test_min_gain_gates_marginal_wins(self):
        cur = plc.PartitionMap.identity(4)
        loads = np.array([10.0, 9, 8.5, 9.5])
        plan = plc.plan_migration(cur, loads, 2,
                                  table_rows=np.full(4, 3),
                                  min_gain=0.5)
        assert plan.is_noop                  # tiny gain, keep layout

    def test_monster_table_reported_not_split(self):
        cur = plc.PartitionMap.identity(4)
        loads = np.array([100.0, 1, 1, 1])
        plan = plc.plan_migration(cur, loads, 2,
                                  table_rows=np.full(4, 3))
        assert any(t == 0 and ways >= 2 for t, ways in plan.row_splits)

    def test_predicted_makespan_prefers_level_loads(self):
        skew = plc.predicted_makespan([4.0, 1, 1, 1], bound=1)
        flat = plc.predicted_makespan([1.75, 1.75, 1.75, 1.75], bound=1)
        assert flat < skew


class TestLoadModel:
    def test_ewma_and_ready_gate(self):
        lm = plc.TableLoadModel(3, alpha=0.5, min_obs=2)
        assert not lm.ready
        lm.observe([4, 0, 0], row_bytes=2.0)
        lm.observe([0, 4, 0], row_bytes=2.0)
        assert lm.ready
        assert np.allclose(lm.loads, [4.0, 4.0, 0.0])
        lm.reset()
        assert not lm.ready and (lm.loads == 0).all()

    def test_member_loads_respect_placement(self):
        pm = plc.PartitionMap((2, 1, 0, 3))
        ml = plc.member_loads([1.0, 2, 4, 8], pm, 2)
        assert np.array_equal(ml, [6.0, 9.0])  # slots {2,1} | {0,3}


# ---------------------------------------------------------------------------
# Drifting-hotset traffic + the fault-plan builders
# ---------------------------------------------------------------------------


class TestDriftTraffic:
    def test_deterministic_and_phase_sensitive(self):
        from repro.configs.base import DLRMConfig
        from repro.data import synthetic as S
        cfg = DLRMConfig("t", table_sizes=(40, 60, 30), embed_dim=8,
                         n_dense_features=4, bottom_mlp=(16, 8),
                         top_mlp=(16, 1), max_hot=4)
        a = S.make_batch(cfg, 32, mode="drift", seed=1, step=2, phase=0)
        b = S.make_batch(cfg, 32, mode="drift", seed=1, step=2, phase=0)
        c = S.make_batch(cfg, 32, mode="drift", seed=1, step=2, phase=1)
        assert np.array_equal(a.idx, b.idx)
        assert not np.array_equal(a.mask, c.mask)   # hot set moved
        heat0, heat1 = (S.table_heat(3, p, seed=1) for p in (0, 1))
        assert np.argmax(heat0) != np.argmax(heat1) or \
            not np.allclose(heat0, heat1)

    def test_skew_shift_counts_phases(self):
        plan = FaultPlan.none(4, 32).with_skew_shift(5).with_skew_shift(9)
        assert [plan.skew_phase(s) for s in (0, 5, 8, 9, 30)] == \
            [0, 1, 1, 2, 2]

    def test_mig_crash_rejects_unknown_stage(self):
        with pytest.raises(ValueError):
            FaultPlan.none(4, 8).with_mig_crash(0, "teleport")
        for st in MIG_STAGES:
            FaultPlan.none(4, 8).with_mig_crash(0, st, at_step=2)


class TestResets:
    def test_cap_autotuner_reset_keeps_lifetime_drops(self):
        tuner = CapAutotuner(window=4)
        for _ in range(4):
            tuner.observe(12, drops=1)
        assert tuner.total_drops == 4 and len(tuner) == 4
        tuner.reset()
        assert tuner.drops == 0 and not tuner.live
        assert tuner.total_drops == 4         # lifetime counter survives

    def test_straggler_monitor_reset(self):
        mon = StragglerMonitor(window=8)
        mon.observe(0.1)
        mon.observe(0.2)
        assert mon.percentile(0.5) > 0
        mon.reset()
        assert not mon.lat and mon.percentile(0.5) == 0.0

    def test_frontend_flush_ewma_resets_on_layout_change(self):
        from repro.serving.frontend import ServingFrontend

        class _Eng:
            layout_version = 0
        fr = object.__new__(ServingFrontend)
        fr.engine = _Eng()
        fr.ewma_alpha = 0.5
        fr._ewma_flush = 0.5
        fr._layout_seen = 0
        fr._observe_flush(0.7)
        assert fr._ewma_flush == pytest.approx(0.6)
        _Eng.layout_version = 1                 # cutover / eviction
        fr._observe_flush(9.0)                  # spans the swap: skipped
        assert fr._ewma_flush is None and fr._layout_seen == 1
        fr._observe_flush(0.2)
        assert fr._ewma_flush == pytest.approx(0.2)


# ---------------------------------------------------------------------------
# End-to-end: the shared subprocess scaffold
# ---------------------------------------------------------------------------

_PREAMBLE = """
import itertools
import jax, jax.numpy as jnp, numpy as np
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.sharding import partition
from repro.data import synthetic as S
from repro.runtime import elastic
from repro.runtime.faults import FaultPlan, FaultInjector
from repro.serving.engine import DLRMEngine

cfg = DLRMConfig('t', table_sizes=(40, 60, 30, 50, 20, 70), embed_dim=8,
                 n_dense_features=4, bottom_mlp=(16, 8), top_mlp=(16, 1),
                 sparse_backend='ref', max_hot=4)
P, B = 4, 48                 # divides pre- AND post-evict geometry
mesh = elastic.make_mesh_from(jax.devices()[:P], model=P)
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=P)


def drift(step, phase=0, seed=3):
    return S.make_batch(cfg, B, mode='drift', seed=seed, step=step,
                        phase=phase)


def serve(eng, n_flushes, outs=None, faults=None, seed=3):
    for s in range(n_flushes):
        ph = faults.skew_phase(s) if faults is not None else 0
        b = drift(s, ph, seed)
        for r in range(B):
            o = eng.submit(b.dense[r], b.idx[r], b.mask[r])
            if o is not None and outs is not None:
                outs.append(o)


def canon_tables(eng):
    inv = eng.pmap.inv_array()
    return np.asarray(jax.device_get(eng.params['tables']))[inv]


def real_rows_equal(a, b):
    return all(bool((a[t, :n] == b[t, :n]).all())
               for t, n in enumerate(cfg.table_sizes))
"""


def test_rebalance_cutover_stays_bit_exact_and_ledgered():
    """The tentpole end to end: drifting-hotset traffic arms the load
    model, the imbalance trigger starts a reshard, rows ship over the
    fused wire in slice_cap installments while serving continues, and
    the atomic cutover lands — with every flush bit-identical to a
    plain engine on the boot layout, real table rows preserved, and the
    imbalance telemetry mirrored into ServeStats.to_dict()."""
    run_sub(_PREAMBLE + """
eng = DLRMEngine(dict(params), cfg, batch_size=B, bound=1, microbatches=2,
                 rebalance=True, rebalance_threshold=1.05,
                 rebalance_patience=2, mig_slice_cap=4)
ref = DLRMEngine(dict(params), cfg, batch_size=B, bound=1, microbatches=2)
outs, refs = [], []
with partition.axis_rules(mesh):
    for s in range(30):
        b = drift(s)
        for r in range(B):
            o = eng.submit(b.dense[r], b.idx[r], b.mask[r])
            ro = ref.submit(b.dense[r], b.idx[r], b.mask[r])
            if o is not None:
                outs.append(o)
            if ro is not None:
                refs.append(ro)
assert eng.stats.reshards >= 1, 'rebalance never fired'
assert eng.stats.reshard_aborts == 0
assert eng.stats.migrated_rows > 0
assert not eng.pmap.is_identity
a, b_ = np.concatenate(outs), np.concatenate(refs)
assert a.shape == b_.shape and (a == b_).all(), 'CTRs diverged'
assert len(outs) * B == eng.stats.requests        # zero lost requests
assert real_rows_equal(canon_tables(eng),
                       np.asarray(jax.device_get(ref.params['tables'])))
assert eng.layout_version >= 1
assert eng._imb_streak == 0                       # trigger re-armed
d = eng.stats.to_dict()
for k in ('reshards', 'reshard_aborts', 'migrated_rows',
          'imbalance_ratio', 'flush_time_ratio', 'member_rows'):
    assert k in d, k
assert len(d['member_rows']) == P
assert d['imbalance_ratio'] >= 1.0
print('ok')
""")


def test_mid_migration_bit_exact_across_pipeline_and_codec():
    """Double-ownership during the shipping window: a manually started
    reshard with a tiny slice_cap spans many flushes, and EVERY flush —
    migration riders on the wire, old owner still serving — is
    bit-identical to a plain engine, across {mono, ring} × {float32,
    bfloat16} wire codecs."""
    run_sub(_PREAMBLE + """
from repro.runtime import placement as plc

for pipe, wire in [('mono', 'float32'), ('ring', 'float32'),
                   ('mono', 'bfloat16'), ('ring', 'bfloat16')]:
    eng = DLRMEngine(dict(params), cfg, batch_size=B, bound=1,
                     microbatches=2, exchange='dense',
                     exchange_pipeline=pipe, wire_dtype=wire,
                     rebalance=True, rebalance_threshold=10.0,
                     mig_slice_cap=2)     # threshold 10: only manual
    ref = DLRMEngine(dict(params), cfg, batch_size=B, bound=1,
                     microbatches=2, exchange='dense',
                     exchange_pipeline=pipe, wire_dtype=wire)
    outs, refs = [], []
    with partition.axis_rules(mesh):
        # warm one flush on the boot layout first
        serve(eng, 1, outs); serve(ref, 1, refs)
        t_pad = eng.pmap.t_pad
        loads = np.zeros(t_pad)
        loads[:len(cfg.table_sizes)] = [50, 1, 40, 1, 30, 1]
        plan = plc.plan_migration(eng.pmap, loads, P,
                                  table_rows=eng._table_rows(t_pad))
        assert not plan.is_noop
        eng.start_reshard(plan)
        mig_flushes = 0
        for s in range(1, 20):
            if eng.reshard is not None and eng.reshard.active:
                mig_flushes += 1
            b = drift(s)
            for r in range(B):
                o = eng.submit(b.dense[r], b.idx[r], b.mask[r])
                ro = ref.submit(b.dense[r], b.idx[r], b.mask[r])
                if o is not None:
                    outs.append(o)
                if ro is not None:
                    refs.append(ro)
    assert mig_flushes >= 3, (pipe, wire, mig_flushes)  # multi-installment
    assert eng.stats.reshards == 1, (pipe, wire)
    a, b_ = np.concatenate(outs), np.concatenate(refs)
    assert (a == b_).all(), (pipe, wire)
    assert real_rows_equal(canon_tables(eng),
                           np.asarray(jax.device_get(
                               ref.params['tables']))), (pipe, wire)
print('ok')
""")


def test_crash_grid_every_stage_recovers_zero_lost():
    """The acceptance grid: a member killed at EVERY distinct migration
    step — ship, bank, verify, install, and between the two commit
    swaps — plus straggler and update-burst pressure spread across the
    cells and both exchange pipelines.  Every cell recovers via
    evict → replay with zero requests lost, the reshard aborts cleanly
    (rollback is the absence of the swap), and real table rows stay
    bit-exact on the surviving geometry."""
    run_sub(_PREAMBLE + """
cells = [('ship',    'mono', 0, 0),
         ('bank',    'ring', 1, 0),
         ('verify',  'mono', 0, 1),
         ('install', 'ring', 1, 1),
         ('commit',  'mono', 0, 0)]
init_tables = np.asarray(jax.device_get(params['tables']))
for stage, pipe, straggle, burst in cells:
    plan = FaultPlan.none(P, 64).with_mig_crash(1, stage, at_step=0)
    if straggle:
        plan = plan.with_straggler(2, 0.001, from_step=2)
    if burst:
        plan = plan.with_update_burst(3, 2, 2.0)
    eng = DLRMEngine(dict(params), cfg, batch_size=B, bound=1,
                     microbatches=2, exchange='dense',
                     exchange_pipeline=pipe,
                     rebalance=True, rebalance_threshold=1.05,
                     rebalance_patience=2, mig_slice_cap=4,
                     faults=FaultInjector(plan, time_scale=0.0),
                     retry_backoff_s=0.0)
    outs = []
    with partition.axis_rules(mesh):
        serve(eng, 30, outs)
    cell = (stage, pipe, straggle, burst)
    assert eng.stats.reshard_aborts >= 1, cell   # the crash hit a reshard
    assert eng.stats.evictions >= 1, cell
    assert eng.stats.replays >= 1, cell
    assert len(outs) * B == eng.stats.requests, cell    # zero lost
    assert eng._mesh is not None and eng._mesh.shape['model'] == 3, cell
    assert real_rows_equal(canon_tables(eng), init_tables), cell
    # post-evict state: load model re-armed for the new geometry,
    # mandatory rebalance queued (or already executed on the new mesh)
    t_pad3 = D.padded_tables(cfg, 3)
    lm = eng.load_model
    assert lm is None or lm.n_tables == t_pad3, cell
print('ok')
""")


def test_freshness_deltas_route_across_cutover():
    """Versioned row deltas and a live reshard share the wire: deltas
    route to the CURRENT owner on both sides of the atomic swap (and a
    delta landing on an in-flight row patches the banked copy), so the
    drained tables still equal the apply-all-up-front oracle."""
    run_sub(_PREAMBLE + """
from repro.runtime.freshness import FreshnessManager, oracle_tables
N_VER = 6
delta_batches = [S.make_delta_batch(cfg, v, rows_per_version=6, seed=3)
                 for v in range(1, N_VER + 1)]
fm = FreshnessManager(itertools.islice(
    S.delta_stream(cfg, rows_per_version=6, seed=3), N_VER),
    k_fresh=2, slice_cap=4, versions_per_flush=1)
eng = DLRMEngine(dict(params), cfg, batch_size=B, bound=1, microbatches=2,
                 exchange='dense', freshness=fm,
                 rebalance=True, rebalance_threshold=1.05,
                 rebalance_patience=2, mig_slice_cap=4)
outs = []
with partition.axis_rules(mesh):
    serve(eng, 30, outs)
assert eng.stats.reshards >= 1, 'no cutover under the delta stream'
assert fm.fully_committed, (len(fm._sendq), len(fm._apply_buf))
assert fm.delta_rejects == 0 and fm.rollbacks == 0
assert len(outs) * B == eng.stats.requests
want = np.asarray(jax.device_get(
    oracle_tables(params['tables'], delta_batches)))
assert real_rows_equal(canon_tables(eng), want), \\
    'post-cutover tables diverged from the oracle'
print('ok')
""")


def test_jaxpr_migration_and_placement_add_zero_collectives():
    """The wire contract, asserted from the jaxpr: WITH the "xmig"
    migration sub-blob riding the fused buffer AND a non-identity
    placement gather active, a mono step still lowers to exactly one
    all_to_all and a ring step to exactly P−1 ppermutes."""
    run_sub("""
import collections
import jax, jax.numpy as jnp, numpy as np
from repro import compat
from repro.configs.base import DLRMConfig
from repro.models import dlrm as D
from repro.data import synthetic as S
from repro.sharding import partition

def count_collectives(closed):
    c = collections.Counter()
    def walk(jx):
        for eqn in jx.eqns:
            c[eqn.primitive.name] += 1
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        walk(sub.jaxpr)
                    elif hasattr(sub, "eqns"):
                        walk(sub)
    walk(closed.jaxpr)
    return c

cfg = DLRMConfig(name='t', table_sizes=(100, 50, 80, 60, 90, 40),
                 embed_dim=16, bottom_mlp=(32, 16), top_mlp=(32, 1),
                 max_hot=4)
mesh = compat.make_mesh((2, 4), ("data", "model"))
params = D.init_dlrm(jax.random.PRNGKey(0), cfg, n_shards=4)
t_pad = D.padded_tables(cfg, 4)
b = S.make_batch(cfg, 64, mode='hetero', t_pad=t_pad, seed=1)
dense, idx, mask = map(jnp.asarray, (b.dense, b.idx, b.mask))
P, mb, mcap = 4, 2, 4
migration = {
    'mcnt': jnp.zeros((P, mb, 1), jnp.int32),
    'mdst': jnp.zeros((P, mb, mcap), jnp.int32),
    'mepoch': jnp.zeros((P, mb, 1), jnp.int32),
    'mgid': jnp.zeros((P, mb, mcap), jnp.int32),
}
inv = jnp.arange(t_pad, dtype=jnp.int32)[::-1]   # non-identity
with partition.axis_rules(mesh):
    for pipe, want in [('mono', (1, 0)), ('ring', (0, 3))]:
        for mig, ti in [(None, None), (migration, inv)]:
            jx = jax.make_jaxpr(
                lambda p, d, i, m, pipe=pipe, mig=mig, ti=ti:
                D.forward_distributed(p, cfg, d, i, m, microbatches=mb,
                                      exchange='dense',
                                      exchange_pipeline=pipe,
                                      migration=mig, table_inv=ti)
                )(params, dense, idx, mask)
            c = count_collectives(jx)
            got = (c['all_to_all'], c['ppermute'])
            assert got == want, (pipe, mig is not None, dict(c))
print('ok')
""")


def test_rebalance_is_exclusive_with_plan_pipeline():
    run_sub(_PREAMBLE + """
try:
    DLRMEngine(dict(params), cfg, batch_size=B, bound=1, microbatches=2,
               rebalance=True, plan_pipeline=True)
except ValueError as e:
    assert 'rebalance' in str(e)
else:
    raise AssertionError('rebalance + plan_pipeline must be rejected')
print('ok')
""")


def test_serve_example_rebalance_smoke():
    """examples/serve_dlrm_bls.py --rebalance: the demo serves a
    drifting-hotset stream, triggers an online reshard, and prints the
    placement ledger with its own assertions holding."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "examples",
                                      "serve_dlrm_bls.py"),
         "--rebalance", "--batches", "24", "--batch-size", "64",
         "--bound", "1", "--microbatches", "2"],
        env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "placement:" in r.stdout, r.stdout
    assert "reshards=1" in r.stdout or "reshards=" in r.stdout, r.stdout
