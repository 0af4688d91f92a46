"""The architecture seam: the dense architecture's weights, reference and
FLOPs pinned to the numbers they had before ``chipbench/archs/`` existed; an
architecture that lives in new files only builds, serves and is checked; and
placement keys reach the program's entry under their own names."""
import functools
import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import model, reference, run

DENSE = ("smollm-360m", "olmo-1b")
SEED = 2**31 + 1515
DATA = run.HERE / "tests" / "data"

# Recorded from the harness before the dense code moved into
# chipbench/archs/dense.py: sha256 (first 16 hex digits) of each leaf of the
# program tree, at the program's smoke sizes; Reference.loss (plain, then the
# float8 control) as float.hex; served_gaps (served, control) digests; FLOPs at
# the published sizes.
PINS = {'olmo-1b': {'flops': {'decode_token.1537': 2554986496.0,
                       'decode_token.2048': 2621964288.0,
                       'decode_token.513': 2420768768.0,
                       'decode_token.640': 2437414912.0,
                       'prefill.1536': 3769739771904.0,
                       'prefill.512': 1222220185600.0,
                       'train_per_token.1024': 7261913088.0},
             'gaps': {"['control']": '40b9d0bc360dec90', "['gap']": '354a5afadd66b8e3'},
             'loss': ['0x1.6376ca0000000p+2', '0x1.637aa00000000p+2'],
             'weights': {'bfloat16': {"['blocks']['attn']['wk']": '7b4d74ac72721530',
                                      "['blocks']['attn']['wo']": '4232ad03a6c475bf',
                                      "['blocks']['attn']['wq']": '7063d5dceead3b13',
                                      "['blocks']['attn']['wv']": '2ff86212a720da76',
                                      "['blocks']['mlp']['wg']": 'deb150d673aa05e6',
                                      "['blocks']['mlp']['wi']": 'fe0d7bd06b15fb4f',
                                      "['blocks']['mlp']['wo']": '7b1f31b5a7e8298a',
                                      "['embed']['tok']": '92a13f049ab21315'},
                         'float32': {"['blocks']['attn']['wk']": '19f8afe47b288461',
                                     "['blocks']['attn']['wo']": '9b4c9d5a4060de80',
                                     "['blocks']['attn']['wq']": '3b9f9e0583e01a35',
                                     "['blocks']['attn']['wv']": 'b96b5dcaedc89dad',
                                     "['blocks']['mlp']['wg']": '466fcb10f54d8a7d',
                                     "['blocks']['mlp']['wi']": '4745aa4b9d795cab',
                                     "['blocks']['mlp']['wo']": 'b20dd243bb12b678',
                                     "['embed']['tok']": 'f5fe73929f125c8e'}}},
 'smollm-360m': {'flops': {'decode_token.1537': 912508800.0,
                           'decode_token.2048': 975300480.0,
                           'decode_token.513': 786679680.0,
                           'decode_token.640': 802285440.0,
                           'prefill.1536': 1256563998720.0,
                           'prefill.512': 386642411520.0,
                           'train_per_token.1024': 2359670400.0},
                 'gaps': {"['control']": '214d758d01da1ed3', "['gap']": '0287958826f85464'},
                 'loss': ['0x1.64185c0000000p+2', '0x1.63fb100000000p+2'],
                 'weights': {'bfloat16': {"['blocks']['attn']['wk']": '9c4c785785e85aea',
                                          "['blocks']['attn']['wo']": 'e7e12e16cebbfe5b',
                                          "['blocks']['attn']['wq']": '4550b46b65aa64db',
                                          "['blocks']['attn']['wv']": '41c4f8a45c57cfe7',
                                          "['blocks']['ln1']['scale']": 'ea720d4daad2181e',
                                          "['blocks']['ln2']['scale']": '42dfeb524bba3c06',
                                          "['blocks']['mlp']['wg']": 'd0b313b446f7d1e3',
                                          "['blocks']['mlp']['wi']": 'de2af51701aaaf3f',
                                          "['blocks']['mlp']['wo']": 'e7bdb240fb3eb595',
                                          "['embed']['tok']": '0f7a59ad6d3c7105',
                                          "['ln_f']['scale']": 'a02d954029067033'},
                             'float32': {"['blocks']['attn']['wk']": 'ea893d1b012d817f',
                                         "['blocks']['attn']['wo']": '2614c2cc95069749',
                                         "['blocks']['attn']['wq']": 'dcf178d59d0b6283',
                                         "['blocks']['attn']['wv']": '04e9a8f25c56f8f6',
                                         "['blocks']['ln1']['scale']": '03135fcd18e95491',
                                         "['blocks']['ln2']['scale']": '0feb0b72cb801f7c',
                                         "['blocks']['mlp']['wg']": '513e5132f35987d9',
                                         "['blocks']['mlp']['wi']": '87a7ffdb21ce6923',
                                         "['blocks']['mlp']['wo']": '4e594a1c0cc772b6',
                                         "['embed']['tok']": 'a40da9409f56fe0a',
                                         "['ln_f']['scale']": 'c3a27ac9eafd2d7f'}}}}


def digest(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h = hashlib.sha256(f"{a.dtype}{a.shape}".encode() + a.tobytes()).hexdigest()[:16]
        out[jax.tree_util.keystr(path)] = h
    return out


def smoke(name: str):
    from repro.configs import get_smoke_config

    arch, dims, raw = model.load(name)
    return arch, arch.smoke(dims, get_smoke_config(raw["program_arch"]))


@pytest.mark.parametrize("name", DENSE)
def test_dense_trees_are_pinned(name):
    arch, dims = smoke(name)
    for dt, want in PINS[name]["weights"].items():
        assert digest(arch.device_tree(dims, SEED, jnp.dtype(dt))) == want
        host = arch.host_tree(dims, SEED, jnp.dtype(dt))
        assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(host))
        assert digest(host) == want


@pytest.mark.parametrize("name", DENSE)
def test_dense_reference_is_pinned(name):
    arch, dims = smoke(name)
    rng = np.random.default_rng(7)
    toks = jnp.asarray(rng.integers(0, dims.vocab, (2, 33)), jnp.int32)
    w32 = arch.reference_weights(dims, SEED, jnp.float32)
    with jax.default_matmul_precision("highest"):
        losses = [
            float(jax.jit(arch.Reference(dims, quant=q).loss)(w32, toks[:, :-1], toks[:, 1:]))
            for q in (False, True)
        ]
    assert [x.hex() for x in losses] == PINS[name]["loss"]
    w = arch.reference_weights(dims, SEED, jnp.bfloat16)
    prompt, served = rng.integers(0, dims.vocab, 40), rng.integers(0, dims.vocab, 24)
    g, c = reference.served_gaps(arch.Reference(dims), w, prompt, served, 128,
                                 arch.Reference(dims, quant=True))
    assert digest({"gap": g, "control": c}) == PINS[name]["gaps"]


@pytest.mark.parametrize("name", DENSE)
def test_dense_flops_are_pinned(name):
    arch, dims, _ = model.load(name)
    got = {}
    for key in PINS[name]["flops"]:
        fn, n = key.split(".")
        got[key] = getattr(arch, fn)(dims, int(n))
    assert got == PINS[name]["flops"]


# ---------------------------------------------------------------------------
# an architecture from new files only: tests/data/minitron.py (untied head,
# relu2, LayerNorm with a bias, rotary positions), as the program's
# minitron-4b runs it
# ---------------------------------------------------------------------------

NEW = "minitron-4b.decode.host-weights"


@pytest.fixture
def new_root(tmp_path):
    """A root with the benchmark's data files, plus a configuration, its
    architecture and a cell's limits that the benchmark does not have."""
    for d in ("configs", "traffic", "placements", "metrics", "limits"):
        shutil.copytree(run.HERE / d, tmp_path / d)
    (tmp_path / "archs").mkdir()
    shutil.copy(DATA / "minitron.py", tmp_path / "archs" / "minitron.py")
    shutil.copy(DATA / "minitron-4b.json", tmp_path / "configs" / "minitron-4b.json")
    # sound runs read 0.023-0.046 here, the float8 control 0.32-0.41 and the
    # tied head 6.0-7.3 (CPU, smoke sizes, three seeds)
    (tmp_path / "limits" / f"{NEW}.json").write_text(json.dumps({"token_gap": {"limit": 0.2}}))
    bench = run.load_benchmark()
    bench["workloads"] = bench["workloads"] + [
        {"name": NEW, "config": "minitron-4b", "traffic": "decode.host-weights",
         "chips": 1, "why": "added by a test"}
    ]
    return tmp_path, bench


def serve_new(root, bench, control=False):
    import time

    return run.run_cell(NEW, SEED, 1.0, False, control=control, smoke=True, bench=bench,
                        root=root, t_start=time.perf_counter(), log=lambda m: None)


def test_new_architecture_serves_and_is_correct(new_root):
    root, bench = new_root
    cell = run.build_cell(bench, NEW, SEED, smoke=True, root=root)
    assert cell.arch.__file__ == str(root / "archs" / "minitron.py")
    assert cell.arch.Reference.blocked
    r = serve_new(root, bench, control=True)
    assert r["correct"], r["checks"]
    assert r["control"]["control"]["token_gap"] > r["checks"]["token_gap"]["limit"]
    assert r["attempted"] > 0 and r["metrics"]["setup_s"]["value"] > 0
    dev = cell.arch.device_tree(cell.dims, SEED, jnp.bfloat16)
    assert digest(dev) == digest(cell.arch.host_tree(cell.dims, SEED, jnp.bfloat16))


def test_new_architecture_with_its_head_tied_is_not_correct(new_root, monkeypatch):
    root, bench = new_root
    arch = model.load_arch("minitron", root / "archs")
    host_tree = arch.host_tree

    def tied(dims, seed, dtype):
        tree = host_tree(dims, seed, dtype)
        tree["head"]["out"] = np.ascontiguousarray(tree["embed"]["tok"].T)
        return tree

    monkeypatch.setattr(arch, "host_tree", tied)
    r = serve_new(root, bench)
    assert not r["correct"]
    assert r["checks"]["token_gap"]["value"] > 1.0


def test_new_architecture_holds_one_layer_on_the_device(new_root, monkeypatch):
    root, bench = new_root
    cell = run.build_cell(bench, NEW, SEED, smoke=True, root=root)
    before = {id(a) for a in jax.live_arrays()}
    held, device_get = [], jax.device_get

    def counted(tree):
        held.append(sum(
            1 for a in jax.live_arrays() if id(a) not in before and a.dtype == jnp.bfloat16
        ))
        return device_get(tree)

    monkeypatch.setattr(jax, "device_get", counted)
    host = cell.arch.host_tree(cell.dims, SEED, jnp.bfloat16)
    per_layer = len(jax.tree.leaves(host["blocks"]))
    assert len(held) == cell.dims.layers + 1  # the outer leaves, then each layer
    assert 0 < max(held) <= per_layer
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(host))


# ---------------------------------------------------------------------------
# placement keys pass through by name
# ---------------------------------------------------------------------------


class _Stop(Exception):
    pass


def _recorder(entry, seen: dict):
    @functools.wraps(entry)
    def record(*args, **kwargs):
        seen.update(kwargs)
        raise _Stop

    return record


def _mesh():
    return jax.make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1],
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


CELLS = {
    "serve": ("smollm-360m.decode.host-weights", "repro.launch.serve.ServeSession",
              {"param_cache_mb": 0.5}),
    "train": ("smollm-360m.train.host-state", "repro.launch.train.build_trainer",
              {"param_layers_per_group": 1}),
}


@pytest.mark.parametrize("entry", sorted(CELLS))
def test_placement_keys_reach_the_entry_by_name(entry, monkeypatch):
    import importlib

    workload, target, extra = CELLS[entry]
    module, attr = target.rsplit(".", 1)
    module = importlib.import_module(module)
    seen: dict = {}
    monkeypatch.setattr(module, attr, _recorder(getattr(module, attr), seen))
    cell = run.build_cell(run.load_benchmark(), workload, SEED, smoke=True)
    cell.placement = {**cell.placement, **extra}
    with pytest.raises(_Stop):
        cell.setup(_mesh())
    assert {k: seen[k] for k in cell.placement} == cell.placement


@pytest.mark.parametrize("entry", sorted(CELLS))
def test_an_unknown_placement_key_fails_at_set_up(entry):
    workload = CELLS[entry][0]
    cell = run.build_cell(run.load_benchmark(), workload, SEED, smoke=True)
    cell.placement = {**cell.placement, "host_weights_please": True}
    with pytest.raises(ValueError, match="host_weights_please"):
        cell.setup(_mesh())
