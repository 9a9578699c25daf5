"""The plain reference agrees with the program's own single-device forward
on the same weights, and its control (three bf16 passes) does not."""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import reference  # noqa: E402
import traffic  # noqa: E402
import weights  # noqa: E402


def _setup(seed, **over):
    import jax
    from repro.compat import make_mesh
    cfg = json.load(open(os.path.join(HERE, "tests", "data", "tiny.json")))
    cfg.update(over)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    params = weights.make_params(seed, cfg, mesh)
    pool = traffic.make_pool(cfg["table_sizes"], 13, cfg["max_hot"], 96,
                             mode="hetero",
                             t_pad=weights.stack_shape(cfg)[0], seed=seed)
    return cfg, params, pool


def test_weights_are_seeded_and_laid_out_for_the_program():
    import jax
    cfg, a, _ = _setup(2**32 + 5)
    _, b, _ = _setup(2**32 + 5)
    _, c, _ = _setup(5)
    assert a["tables"].shape == weights.stack_shape(cfg) == (8, 128, 16)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert not np.array_equal(np.asarray(a["tables"]),
                              np.asarray(c["tables"]))
    assert float(np.abs(np.asarray(a["bot"][0]["bias"])).max()) > 0


def test_reference_matches_the_programs_forward():
    import jax
    import jax.numpy as jnp
    from repro.models import dlrm
    import run
    cfg, params, pool = _setup(11)
    dcfg = run.program_config(dict(cfg, sparse_backend="ref"))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.nn.sigmoid(dlrm.forward_local(
            params, dcfg, jnp.asarray(pool.dense), jnp.asarray(pool.idx),
            jnp.asarray(pool.mask))))
    got = reference.ctr(params, pool.dense, pool.idx, pool.mask,
                        n_tables=len(cfg["table_sizes"]), block=64)
    assert got.shape == (96,)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)
    assert np.std(got) > 1e-3            # CTRs are not all alike


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_departs_from_the_reference(seed):
    cfg, params, pool = _setup(seed, embed_dim=64, bottom_mlp=[512, 256, 64],
                               top_mlp=[512, 256, 1], max_hot=32)
    kw = dict(n_tables=len(cfg["table_sizes"]), block=96)
    hi = reference.ctr(params, pool.dense, pool.idx, pool.mask, **kw)
    lo = reference.ctr(params, pool.dense, pool.idx, pool.mask,
                       precision="high", **kw)
    again = reference.ctr(params, pool.dense, pool.idx, pool.mask, **kw)
    np.testing.assert_array_equal(hi, again)
    assert np.abs(lo - hi).max() > 3 * np.abs(again - hi).max() + 1e-7
