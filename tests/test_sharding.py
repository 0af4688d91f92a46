"""Sharding-rule tests: divisibility safety, plan modes, small-mesh
integration (2/4 CPU devices via a subprocess would be needed for >1 device;
here we verify rule outputs + a 1-device end-to-end jit)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.models import transformer
from repro.parallel import sharding as sh
from repro.train import steps as st


class _FakeMesh:
    """Shape-only mesh stand-in for rule tests (no devices needed)."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


def _plan(mode="train", multi_pod=False):
    shape = {"pod": 2, "data": 16, "model": 16} if multi_pod else {"data": 16, "model": 16}
    return sh.ShardingPlan(mesh=_FakeMesh(shape), mode=mode,
                           pod_axis="pod" if multi_pod else None)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_specs_divisibility(arch):
    """Every sharded dim must be divisible by its mesh axes product."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: transformer.init_model(jax.random.PRNGKey(0), cfg))
    plan = _plan()
    specs = sh.param_specs(plan, params)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            n = int(np.prod([plan.mesh.shape[a] for a in axes]))
            assert leaf.shape[dim] % n == 0, (arch, leaf.shape, spec)


@pytest.mark.parametrize("arch", ["qwen2-vl-72b", "qwen3-moe-235b-a22b", "internlm2-20b"])
def test_param_bytes_fit_hbm_train(arch):
    """FSDP plan: params+optimizer state per device must be << 16 GiB."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: st.init_train_state(jax.random.PRNGKey(0), cfg))
    plan = _plan()
    p_specs = sh.param_specs(plan, params[0])
    total = 0
    flat_p = jax.tree.leaves(params[0])
    flat_s = jax.tree.leaves(p_specs, is_leaf=lambda s: isinstance(s, P))
    for leaf, spec in zip(flat_p, flat_s):
        elems = int(np.prod(leaf.shape)) if leaf.shape else 1
        for dim, ax in enumerate(spec):
            if ax is None:
                continue
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            elems //= int(np.prod([plan.mesh.shape[a] for a in axes]))
        total += elems * leaf.dtype.itemsize
    # bf16 params sharded; x7 for f32 master+m+v = optimizer state
    assert total * 7 < 14 * 2 ** 30, f"{arch}: {total*7/2**30:.1f} GiB state"


def test_serve_plan_no_fsdp_on_dense():
    cfg = get_config("internlm2-20b")
    params = jax.eval_shape(lambda: transformer.init_model(jax.random.PRNGKey(0), cfg))
    specs_t = sh.param_specs(_plan("train"), params)
    specs_s = sh.param_specs(_plan("serve"), params)
    wq_t = specs_t["blocks"]["attn"]["wq"]
    wq_s = specs_s["blocks"]["attn"]["wq"]
    assert "data" in jax.tree.leaves(tuple(a for a in wq_t if a))  # fsdp in train
    assert all(a != "data" for a in jax.tree.leaves(tuple(a for a in wq_s if a)))


def test_serve_plan_moe_expert_fsdp():
    """qwen3 expert weights exceed HBM under pure TP: serve keeps data-axis
    sharding on MoE leaves only."""
    cfg = get_config("qwen3-moe-235b-a22b")
    params = jax.eval_shape(lambda: transformer.init_model(jax.random.PRNGKey(0), cfg))
    specs = sh.param_specs(_plan("serve"), params)
    wi = specs["blocks"]["moe"]["wi"]
    assert "model" in [a for a in wi if isinstance(a, str)]
    assert "data" in [a for a in wi if isinstance(a, str)]


def test_cache_specs_decode_seq_sharding():
    cfg = get_config("internlm2-20b")  # kv=8 does not divide model=16
    caches = jax.eval_shape(lambda: transformer.init_caches(cfg, 128, 1024))
    specs = sh.cache_specs_tree(_plan("serve"), caches, 128)
    k_spec = specs["k"]
    # stacked (L, B, T, K, H): batch->data, seq->model
    assert k_spec[1] == "data" and k_spec[2] == "model", k_spec


def test_cache_specs_kv_head_sharding_when_divisible():
    cfg = get_config("olmo-1b")  # kv=16 divides model=16
    caches = jax.eval_shape(lambda: transformer.init_caches(cfg, 128, 1024))
    specs = sh.cache_specs_tree(_plan("serve"), caches, 128)
    assert specs["k"][3] == "model", specs["k"]


def test_batch_specs_nondivisible_replicates():
    plan = _plan()
    batch = {"tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    specs = sh.batch_specs(plan, batch, 1)  # long_500k: batch 1
    assert specs["tokens"] == P(None, None)


def test_multi_pod_batch_axes():
    plan = _plan(multi_pod=True)
    assert plan.batch_axes == ("pod", "data")
    batch = {"tokens": jax.ShapeDtypeStruct((512, 16), jnp.int32)}
    specs = sh.batch_specs(plan, batch, 512)
    assert specs["tokens"][0] == ("pod", "data")


def test_sharder_end_to_end_single_device():
    """Sharder-constrained train step runs on 1 CPU device (constraints are
    no-ops on a trivial mesh but the code path is exercised)."""
    cfg = get_smoke_config("olmo-1b")
    mesh = jax.make_mesh((1, 1), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    plan = sh.make_plan(mesh, "train")
    params, opt = st.init_train_state(jax.random.PRNGKey(0), cfg)
    sharder = sh.make_sharder(plan, params, 2, seq_len=16, seq_shard=True)
    from repro.optim.adamw import AdamWConfig

    step = st.make_train_step(cfg, AdamWConfig(), mesh, sharder)
    batch = {
        "tokens": jnp.ones((2, 16), jnp.int32),
        "targets": jnp.ones((2, 16), jnp.int32),
    }
    with mesh:
        p2, o2, m = jax.jit(step)(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
