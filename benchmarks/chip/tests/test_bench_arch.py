"""Each configuration names its architecture file (``archs/<arch>.py``),
and the benchmark reaches the model's equations only through it.

The digests and values written here were recorded from the benchmark before
the architecture had a file of its own (``weights.make_params``,
``reference.ctr``, ``counts``, ``traffic.make_pool`` as they stood): moving
the code changed no weight, reference CTR, count or request.
"""
import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "tests"))

import harness  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402
import test_bench_metrics as tbm  # noqa: E402
import test_bench_run as tbr  # noqa: E402
import traffic  # noqa: E402

BENCH = spec.benchmark()
DATA = os.path.join(HERE, "tests", "data")
INTERFACE = ("program_config", "stack_shape", "make_params", "ctr",
             "request_flops", "pooling_bytes", "reference_block")


def digest(a) -> str:
    return hashlib.sha256(np.ascontiguousarray(np.asarray(a)).tobytes()
                          ).hexdigest()


def config(name: str) -> dict:
    with open(os.path.join(DATA, name + ".json")) as f:
        return json.load(f)


# 1. every configuration resolves; no arch, or an unknown one, names the file

@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_every_configuration_resolves_its_architecture(entry):
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    arch = spec.arch(cfg, spec.ROOT, entry["file"])
    assert cfg["arch"] == "dlrm"
    assert os.path.samefile(arch.__file__,
                            os.path.join(HERE, "archs", "dlrm.py"))
    for fn in INTERFACE:
        assert callable(getattr(arch, fn)), fn
    for w in BENCH["workloads"]:
        if w["config"] == entry["name"]:
            assert spec.cell(w["name"], BENCH)["arch"].__file__ == \
                arch.__file__


def test_an_unknown_architecture_names_the_file():
    with pytest.raises(KeyError, match=r"configs/x\.json.*'nope'.*dlrm"):
        spec.arch(dict(config("tiny"), arch="nope"), spec.ROOT,
                  "configs/x.json")


def test_a_configuration_without_arch_names_the_file(tmp_path):
    """There is no default architecture: the cell does not resolve, and
    the error names the configuration's file."""
    shutil.copytree(HERE, tmp_path / spec.SUBDIR,
                    ignore=shutil.ignore_patterns(".cache", ".archive",
                                                  "__pycache__"))
    entry = BENCH["configs"][0]
    path = tmp_path / entry["file"]
    cfg = json.loads(path.read_text())
    del cfg["arch"]
    path.write_text(json.dumps(cfg))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(BENCH))
    cell = next(w["name"] for w in BENCH["workloads"]
                if w["config"] == entry["name"])
    with pytest.raises(KeyError, match=entry["file"] + " names no 'arch'"):
        spec.cell(cell, spec.benchmark(str(tmp_path)), str(tmp_path))


# 2. the weights and the reference are those of before the move

PARAMS = {
    ("tiny", 2**33 + 2216): {
        "['bot'][0]['bias']": "7bac2456cc347b0cf5a32e893bcb9b5381c9596cc70b4174683b8200251e260e",  # noqa: E501
        "['bot'][0]['kernel']": "c7d8ce48ea47fb49c6b8073b0f9adf046a3b61f7368765decb0c761c7f6682f1",  # noqa: E501
        "['bot'][1]['bias']": "5b5a98ec9d4d0abb198792fb35d3f04e8a93e92abfe77fe4af01095ced864971",  # noqa: E501
        "['bot'][1]['kernel']": "8c75e29b17f2206bd95fcda2a0fd9506d5f1a0c24486037e7ee41039f9946c7e",  # noqa: E501
        "['tables']": "9484bd397fc1ff2dbe701f6d56ca3f04a3d3a7a827294d4e45c7d5375a9c5723",  # noqa: E501
        "['top'][0]['bias']": "d080f9fa847b1520f8f8076be04ee1832044f1b70a139be430b36339c9fafeaf",  # noqa: E501
        "['top'][0]['kernel']": "286eb9238d9766a44a9e6bd5c5a54c36dd6f51f20908287808ff0ac760af5ddd",  # noqa: E501
        "['top'][1]['bias']": "a21f1d8bedf5ad9b1dde061055b9dd082e2300271ff4fa14d69c17b2610edee0",  # noqa: E501
        "['top'][1]['kernel']": "d8ccb3d88c69c9ead19e9da5ec47b3dd4263e98ee11858a7256083d3660fbd7d",  # noqa: E501
    },
    ("tiny-p4", 2**31 + 16): {
        "['bot'][0]['bias']": "4a0582e3cef554598b56e8812e952feb9a97c97014070ec417271a5dbbdfb609",  # noqa: E501
        "['bot'][0]['kernel']": "b9aac32d7436c52a8c3b58d7fe78df0a212646d817ae1640aa125d29dfe89570",  # noqa: E501
        "['bot'][1]['bias']": "d52ae22505fcecd6b15b0a11117bc29349ae229066bc15247685db53dd4d20cc",  # noqa: E501
        "['bot'][1]['kernel']": "8436aab2e282b3cb6db83b0b043e142f159a10a3a324338939e8b1c345bd4626",  # noqa: E501
        "['tables']": "020e835b3589827210ff0904d61faa737e4dd58e0e1b1604cb0c13384b8f799f",  # noqa: E501
        "['top'][0]['bias']": "18d9280582d9fd74a639cc9551c6c6e3763f2a82af6f3dcd4ec742c6c61b11cc",  # noqa: E501
        "['top'][0]['kernel']": "acc7ff9dd898e6985276f0ddef4e29560b9196359a850f95186980d8c7ef3f06",  # noqa: E501
        "['top'][1]['bias']": "567aa4423161c2d50e4dc5ec7fc998b5f0edbbad8e3db1f0c17f8c5b8636194a",  # noqa: E501
        "['top'][1]['kernel']": "f68265258491a8352c3f7651b8519a4d0f6a5f49f556e578ac1cfb84e302c1bb",  # noqa: E501
    },
}

# reference CTRs of a 12-request ``hetero`` pool from the same seed, in
# blocks of 8 (the last one padded)
CTRS = {
    "tiny": [0.4069491922855377, 0.4365501403808594, 0.6919687390327454,
             0.5551183223724365, 0.5997075438499451, 0.4926545321941376,
             0.41877344250679016, 0.49753904342651367, 0.5509247779846191,
             0.3802017867565155, 0.3777141869068146, 0.3512374460697174],
    "tiny-p4": [0.7555219531059265, 0.8039437532424927, 0.5066443085670471,
                0.5150665640830994, 0.5875781178474426, 0.5356295704841614,
                0.41240572929382324, 0.22472794353961945,
                0.43032166361808777, 0.6161463260650635, 0.5467683672904968,
                0.5003919005393982],
}


def _params(name, seed):
    import jax
    from repro.compat import make_mesh
    cfg = config(name)
    arch = spec.arch(cfg)
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    return cfg, arch, arch.make_params(seed, cfg, mesh)


@pytest.mark.parametrize("name,seed", list(PARAMS), ids=lambda x: str(x))
def test_weights_are_those_of_before_the_move(name, seed):
    import jax
    _, _, params = _params(name, seed)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    got = {jax.tree_util.keystr(k): digest(v) for k, v in leaves}
    assert got == PARAMS[name, seed]
    assert all(v.dtype == np.float32 for _, v in leaves)


@pytest.mark.parametrize("name,seed", list(PARAMS), ids=lambda x: str(x))
def test_reference_ctrs_are_those_of_before_the_move(name, seed):
    cfg, arch, params = _params(name, seed)
    pool = traffic.make_pool(cfg["table_sizes"], cfg["n_dense_features"],
                             cfg["max_hot"], 12, mode="hetero",
                             t_pad=arch.stack_shape(cfg)[0], seed=seed)
    got = arch.ctr(params, pool.dense, pool.idx, pool.mask, cfg=cfg, block=8)
    assert got.dtype == np.float32
    np.testing.assert_array_equal(got, np.asarray(CTRS[name], np.float32))


# 3. the counts are those of before the move

VALID = [0, 1, 23, 2600]
COUNTS = {
    "dlrm-kaggle": ([1040768, 1040832, 1042240, 1207168],
                    [6656, 6920, 12728, 693056]),
    "dlrm-alicpp": ([954368, 954432, 955840, 1120768],
                    [5888, 6152, 11960, 692288]),
    "dlrm-kaggle-p4": ([1040768, 1040832, 1042240, 1207168],
                       [6656, 6920, 12728, 693056]),
}


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_counts_are_those_of_before_the_move(entry):
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    arch = spec.arch(cfg)
    flops, nbytes = COUNTS[entry["name"]]
    np.testing.assert_array_equal(arch.request_flops(cfg, VALID), flops)
    np.testing.assert_array_equal(arch.pooling_bytes(cfg, VALID), nbytes)
    assert arch.dense_flops(cfg) == flops[0]


# 4. a second architecture is new files and BENCHMARK.json entries only

PROBE = '''"""archs/dlrm.py, recording each call of its functions."""
import os

import spec

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CALLS = []
_dlrm = spec.arch({"arch": "dlrm"}, ROOT)


def _recorded(name):
    def call(*args, **kwargs):
        CALLS.append(name)
        return getattr(_dlrm, name)(*args, **kwargs)
    return call


for _name in %r:
    globals()[_name] = _recorded(_name)
''' % (INTERFACE,)


def test_a_new_architecture_needs_only_new_files(tmp_path, monkeypatch,
                                                 capsys):
    """Copy the benchmark, add ``archs/probe.py`` (the DLRM architecture,
    recording its calls), a configuration naming it, a cell and their
    entries; the cell is served through the probe and comes out correct,
    and the metric readers take their counts from it.  No file of the
    copy is edited."""
    root = tmp_path / "root"
    sub = root / spec.SUBDIR
    shutil.copytree(HERE, sub, ignore=shutil.ignore_patterns(
        ".cache", ".archive", "__pycache__", "tests"))
    (root / "BENCHMARK.json").write_text(json.dumps(BENCH))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}

    (sub / "archs" / "probe.py").write_text(PROBE)
    cfg = dict(config("tiny"), name="probe-tiny", arch="probe")
    (sub / "configs" / "probe-tiny.json").write_text(json.dumps(cfg))
    (sub / "workloads" / "probe.json").write_text(json.dumps(
        {"rate_rps": 200.0, "pool": 256,
         "frontend": {"admission": "none", "shed": False, "linger_s": 0.0,
                      "slo_s": 3600.0},
         "trace_at_s": 0.2, "trace_s": 0.3, "ctr_gap_limit": 1e-5,
         "why": "test"}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "probe-tiny", "source": "test",
                             "file": f"{spec.SUBDIR}/configs/probe-tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "probe", "config": "probe-tiny",
                               "traffic": "hetero", "chips": 1,
                               "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    tbr.stub_chip(monkeypatch)
    rc = run.main(["--workload", "probe", "--seed", str(tbr.SEED),
                   "--seconds", "1", "--trace", "0"], root=str(root))
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["attempted"] == 200 and res["failed"] == 0

    probe = sys.modules["bench_arch_probe"]
    assert set(probe.CALLS) >= {"program_config", "stack_shape",
                                "make_params", "ctr", "reference_block"}
    r = tbm.toy_run()
    r.root, r.cell = str(root), {"config": tbm.CFG, "arch": probe}
    for name in ("step_mfu", "emb_bag_roofline"):
        assert spec.reader(name, str(root))(r) == tbm.read(name, tbm.toy_run())
    assert {"request_flops", "pooling_bytes"} <= set(probe.CALLS)

    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data, f"{p} was edited"
    assert {p for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts} - set(before) == {
        sub / "archs" / "probe.py", sub / "configs" / "probe-tiny.json",
        sub / "workloads" / "probe.json"}
    for key in ("configs", "workloads"):
        assert bench[key][:-1] == BENCH[key]
    assert {k: v for k, v in bench.items()
            if k not in ("configs", "workloads")} == \
        {k: v for k, v in BENCH.items() if k not in ("configs", "workloads")}


# 5. the fixed mix, and the other mixes as before

SIZES = (1000, 50, 3, 70000, 12)
HOT = [3, 1, 7, 2, 5]


def test_fixed_bags_hold_exactly_their_tables_size():
    a = traffic.make_pool(SIZES, 13, 8, 256, mode="fixed", t_pad=8,
                          hot=HOT, seed=2**31 + 5)
    assert a.idx.shape == a.mask.shape == (256, 8, max(HOT))
    assert a.idx.dtype == np.int32 and a.mask.dtype == np.float32
    for t, (rows, h) in enumerate(zip(SIZES, HOT)):
        np.testing.assert_array_equal(a.mask[:, t].sum(axis=1), h)
        assert (a.mask[:, t, :h] == 1).all()       # the first h positions
        live = a.idx[:, t, :h]
        assert (live >= 0).all() and (live < rows).all()
        assert (a.idx[:, t, h:] == 0).all()
    assert not a.mask[:, len(SIZES):].any()       # padding tables stay empty
    # uniform over the table: the large table's ids spread over its rows
    assert a.idx[:, 3, :2].max() > 35000 > a.idx[:, 3, :2].min()
    b = traffic.make_pool(SIZES, 13, 8, 256, mode="fixed", t_pad=8,
                          hot=HOT, seed=2**31 + 5)
    c = traffic.make_pool(SIZES, 13, 8, 256, mode="fixed", t_pad=8,
                          hot=HOT, seed=2**31 + 6)
    np.testing.assert_array_equal(a.idx, b.idx)
    np.testing.assert_array_equal(a.dense, b.dense)
    assert not np.array_equal(a.idx, c.idx)
    # run.make_pool takes the sizes from the mix
    cell = {"config": {"table_sizes": list(SIZES), "n_dense_features": 13,
                       "max_hot": 8, "chips": 1, "embed_dim": 16},
            "arch": spec.arch({"arch": "dlrm"}),
            "traffic": {"mode": "fixed", "hot": HOT},
            "workload": {"pool": 256}}
    d = run.make_pool(cell, 2**31 + 5)          # t_pad 5 on one chip
    np.testing.assert_array_equal(d.idx, a.idx[:, :5])
    np.testing.assert_array_equal(d.mask, a.mask[:, :5])


@pytest.mark.parametrize("hot", [None, HOT[:-1], HOT + [1]],
                         ids=["none", "short", "long"])
def test_fixed_bags_need_one_size_a_table(hot):
    with pytest.raises(ValueError, match="one bag size a table"):
        traffic.make_pool(SIZES, 13, 8, 16, mode="fixed", t_pad=8, hot=hot,
                          seed=1)


DENSE = "3092d3649fe8730c482acb6732711b39b43e71818681f47ee673fe758fea611f"
POOLS = {
    "hetero": ("8892f99a01c171e16b0e42534f1181856ccde83d42de9e3e21d9584c269aaf38",  # noqa: E501
               "2c1dc92bed2b2bdbba58cdf32d98c272c43e272e1c55f5896ee2be9b319ce2fb"),  # noqa: E501
    "powerlaw": ("40db69305a4a0be73844f69ab1765f100162a56cb40ad1d70fa1c4ccb541b31a",  # noqa: E501
                 "d6a2224137841824259d52e38df27826eec1673e4396ce3c7d8c997e93953162"),  # noqa: E501
    "drift": ("c4e18a7d217dd8660fd2fbfc09f45f6da75b999547a698fd167d0f4da627d554",  # noqa: E501
              "bcdaf2cdf688db1b02ef47ecc56fd77ef91a6c329d6e982c90ce2681e8e2257b"),  # noqa: E501
    "uniform": ("bf25f2b8c50f8a90bcac175427d9a5d9b52b927913ad6ff461e24fc329a2557c",  # noqa: E501
                "d6a2224137841824259d52e38df27826eec1673e4396ce3c7d8c997e93953162"),  # noqa: E501
    "powerlaw_hetero": ("22a30f0da756b036a81e04ac1d08c5edc0b1ac9980ef93c5b7a9d1992f13ee15",  # noqa: E501
                        "020954badd7ca4f53ebd04e984d7e067321035a8ce87ea9672a1e6151b01f83a"),  # noqa: E501
}


@pytest.mark.parametrize("mode", list(POOLS))
def test_other_mixes_draw_as_before(mode):
    p = traffic.make_pool(SIZES, 13, 8, 256, mode=mode, t_pad=8,
                          seed=2**31 + 2216)
    assert (digest(p.dense), digest(p.idx), digest(p.mask)) == \
        (DENSE,) + POOLS[mode]


# each cell's pool as run.make_pool makes it, seed 2**32 + 2216
CELL_POOLS = {
    "kaggle-hetero-p1": (
        "a65c431a49139830a9def8a9eb56035d6328e8d6f8b928b478b802971b6f423d",
        "80be905aecee8f55035c4262ab681fd28b9ea293b589942cedd069732a711262",
        "3baa150ff4d8f5cba4e04f124122db4c274cdec5d079c4f76aa4021417435e1c"),
    "alicpp-zipf-p1": (
        "22f39060c5846cb1a4ec72825bf8621ab8d924c9b8c3d968a418dddf48094bd5",
        "2480dfeb85692e4aead885870f1bd80b3b3f078670feb9d34d8930e8bab21a79",
        "b1bfa59c08bddee7c5c1ea23898ac63f8ba051059636e1527e46e16547bc20f8"),
    "kaggle-drift-p4": (
        "ce771640ea192f66931f36195752099bb4e77b9fc41f2406fa59df02b998b8f5",
        "b9589e40f4bedfa54873c755a7caa76796ef2f66a9babfbc9ed51b2d3c9e5533",
        "f4e4b5279e2d4a10f0ee1057f39533cb5c50b7030ce2fb9b3f97b2f56664e8d4"),
}


@pytest.mark.parametrize("name", list(CELL_POOLS))
def test_each_cells_pool_is_as_before(name):
    p = run.make_pool(spec.cell(name, BENCH), 2**32 + 2216)
    assert (p.dense.dtype, p.idx.dtype, p.mask.dtype) == \
        (np.float32, np.int32, np.float32)
    assert (digest(p.dense), digest(p.idx), digest(p.mask)) == \
        CELL_POOLS[name]


# 6. the window compiles nothing

def test_compile_count_counts_what_jax_builds():
    import jax
    with run.CompileCount() as c:
        jax.jit(lambda x: x * 3.0 + 1.0)(np.arange(5.0, dtype=np.float32))
    assert c.n >= 1
    n = c.n
    jax.jit(lambda x: x * 5.0 - 1.0)(np.arange(5.0, dtype=np.float32))
    assert c.n == n                # the listener is gone after the block


def test_the_window_compiles_nothing_after_warm_up(tmp_path, monkeypatch):
    """On the tiny configuration on the CPU: ``harness.warm_up`` leaves no
    program for ``harness.serve`` to build."""
    import jax
    tbr.stub_chip(monkeypatch)
    root = tbr.make_root(tmp_path, "tiny")
    cell = spec.cell("tiny", spec.benchmark(root), root)
    cfg = cell["config"]
    mesh = run.make_mesh(jax.devices()[:1])
    params = cell["arch"].make_params(tbr.SEED, cfg, mesh)
    pool = run.make_pool(cell, tbr.SEED)
    due, order = run.arrivals(cell, tbr.SEED, 1.0)
    with run.mesh_context(mesh):
        fe = run.make_frontend(cell, params)
        harness.warm_up(fe, pool, cfg["batch"])
        with run.CompileCount() as window:
            win = harness.serve(fe, pool, due, order, 1.0)
    assert np.isfinite(win.ctr).all() and win.flushes.shape[0] > 1
    assert window.n == 0
