"""Step functions: train / prefill / decode, built per (config, optimizer).

These are the functions the launcher jits with the sharding plan's
in/out-shardings and that the dry-run lowers for every (arch x shape x mesh)
cell.  All of them are pure: ``(state..., batch) -> (state..., outputs)``.

The streamed-optimizer path (``make_streamed_opt_updater`` /
``make_streamed_train_step``) is the paper's flagship pattern applied to the
largest state group of training: AdamW moments + f32 master live at the
*host* kind between steps and stream through the
:class:`~repro.core.engine.TransferEngine` group-wise during the update —
coalesced H2D, ``rw`` write-back pipelined off the compute path, prefetch
distance adaptive when ``PrefetchSpec(distance="auto")``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.engine import EngineConfig, TransferEngine
from repro.core.hoststream import HostStreamExecutor, StreamStats
from repro.core.refspec import PrefetchSpec
from repro.core.residency import ResidencyCache
from repro.core.schedcheck import analyze_train_schedule, verify_schedule
from repro.core.spans import span
from repro.core.weightstream import WeightStreamPlan, merge_expert_slice
from repro.models import moe, transformer
from repro.optim.adamw import (
    AdamWConfig,
    adamw_globals,
    adamw_globals_from_norm,
    adamw_init,
    adamw_leaf_update,
    adamw_update,
)

Pytree = Any


def make_train_step(
    cfg: ModelConfig, opt_cfg: AdamWConfig, mesh=None, sharder=None
) -> Callable[[Pytree, Pytree, Pytree], tuple[Pytree, Pytree, dict]]:
    """``(params, opt_state, batch) -> (params, opt_state, metrics)``."""

    def train_step(params, opt_state, batch):
        (loss, aux), grads = jax.value_and_grad(transformer.lm_loss, argnums=1, has_aux=True)(
            cfg, params, batch, mesh, sharder
        )
        if sharder is not None:
            grads = sharder.grads(grads)  # ZeRO grad layout (see Sharder.grads)
        new_params, new_opt, om = adamw_update(
            opt_cfg, grads, opt_state, compute_dtype=cfg.compute_dtype
        )
        metrics = {"loss": loss, **aux, **om}
        return new_params, new_opt, metrics

    return train_step


def make_grad_step(
    cfg: ModelConfig, mesh=None, sharder=None
) -> Callable[[Pytree, Pytree], tuple[jax.Array, dict, Pytree]]:
    """``(params, batch) -> (loss, aux, grads)`` — the forward/backward half
    of the train step, split out so the optimizer half can run through the
    host-streaming engine."""

    def grad_step(params, batch):
        (loss, aux), grads = jax.value_and_grad(
            transformer.lm_loss, argnums=1, has_aux=True
        )(cfg, params, batch, mesh, sharder)
        if sharder is not None:
            grads = sharder.grads(grads)
        return loss, aux, grads

    return grad_step


# ---------------------------------------------------------------------------
# streamed optimizer update (host-resident AdamW state, paper 'rw' streaming)
# ---------------------------------------------------------------------------


def _to_host(x):
    """numpy view of a concrete array; abstract values pass through so the
    driver's ``jax.eval_shape(init_state)`` restore template still works."""
    return x if isinstance(x, jax.core.Tracer) else np.asarray(x)


def host_opt_state(params: Pytree) -> dict:
    """Fresh AdamW state resident at the host kind (numpy leaves).

    This is the home representation the streamed updater maintains: the
    moments never hold device memory between steps.
    """
    dev = adamw_init(params)
    return {
        "leaves": jax.tree.map(_to_host, dev["leaves"]),
        "step": _to_host(dev["step"]),
    }


def _group_bounds(n: int, n_groups: int) -> np.ndarray:
    """Contiguous leaf-group boundaries — shared by the streamed updater and
    the spill partitioner so both see the same groups."""
    return np.linspace(0, n, min(n_groups, n) + 1).astype(int)


def _opt_group_key(i: int) -> str:
    return f"opt_g{i:04d}"


def spill_opt_state(
    host_state: dict,
    store,
    *,
    n_groups: int = 4,
    host_budget_bytes: Optional[int] = None,
) -> dict:
    """Move trailing moment groups to the ``DiskHost`` tier under a host-RAM
    budget.

    Groups (the same contiguous leaf groups the streamed updater transfers)
    are kept in host RAM front-to-back while they fit ``host_budget_bytes``;
    the rest are written to ``store`` (one chunk per group — one disk
    request per group when streamed) and replaced by memory-mapped views.
    ``host_budget_bytes=None`` or 0 spills everything.  Abstract leaves
    (``jax.eval_shape`` templates, driver restore) pass through untouched.
    """
    flat_s, treedef = jax.tree.flatten(
        host_state["leaves"],
        is_leaf=lambda x: isinstance(x, dict) and {"master", "m", "v"} <= set(x),
    )
    if not all(
        isinstance(v, np.ndarray) for s in flat_s for v in jax.tree.leaves(s)
    ):
        return host_state  # abstract template (eval_shape) — nothing to spill
    bounds = _group_bounds(len(flat_s), n_groups)
    budget = host_budget_bytes or 0
    used = 0
    out: list = []
    for i in range(len(bounds) - 1):
        chunk = tuple(flat_s[bounds[i] : bounds[i + 1]])
        nbytes = sum(v.nbytes for s in chunk for v in jax.tree.leaves(s))
        if used + nbytes <= budget:
            used += nbytes
            out.extend(chunk)
        else:
            store.put(_opt_group_key(i), chunk)
            out.extend(store.get(_opt_group_key(i)))
    return {
        "leaves": jax.tree.unflatten(treedef, out),
        "step": host_state["step"],
    }


def make_streamed_opt_updater(
    opt_cfg: AdamWConfig,
    *,
    compute_dtype=jnp.bfloat16,
    n_groups: int = 4,
    prefetch: Optional[PrefetchSpec] = None,
    mode: str = "prefetch",
    engine: Optional[TransferEngine] = None,
    spill_store=None,
    state_shardings: Optional[Pytree] = None,
) -> Callable[..., tuple[Pytree, dict, dict]]:
    """Build ``update(grads, host_state, stats=None) -> (new_params,
    new_host_state, metrics)`` with host-resident optimizer state.

    Parameter leaves are partitioned into ``n_groups`` contiguous groups.
    Per group, the state leaves stream H2D through the engine (coalesced:
    one request per group) while the previous group's update computes;
    gradients are already device-resident and pass through by reference.
    New moments stream back D2H asynchronously (``rw`` write-back) and the
    new master-derived params stay on device.  The math is exactly
    :func:`repro.optim.adamw.adamw_update` (same leaf function, same
    globals); results agree to float32 rounding (the group-wise jit fuses
    differently than a whole-tree program), and the transfer schedule is
    the only structural difference.

    Groups whose ``host_state`` leaves live at the ``DiskHost`` tier
    (memory-mapped spill-store chunks — see :func:`spill_opt_state`) stream
    in through the engine's two-stage disk->host->device pipeline, and
    their updated moments are written back to ``spill_store`` after the
    D2H drain, so the state never occupies more host RAM than the budgeted
    groups plus the engine's staging pools.

    ``state_shardings`` (a pytree congruent with ``host_state["leaves"]``:
    one device ``NamedSharding`` per master/m/v leaf — the sharding plan's
    opt-state specs) places each streamed moment group at its planned
    multi-device layout instead of default single-device placement, via
    the engine's sharding-aware coalescing (one H2D request per
    addressable device per group).
    """
    prefetch = prefetch or PrefetchSpec(buffer_size=n_groups, distance=1)

    @jax.jit
    def _globals(grads, step):
        return adamw_globals(opt_cfg, grads, step)

    @jax.jit
    def _group_update(glob, gs, ss):
        out = [adamw_leaf_update(opt_cfg, glob, g, s) for g, s in zip(gs, ss)]
        new_p = tuple(p.astype(compute_dtype) for p, _ in out)
        new_s = tuple(s for _, s in out)
        return new_p, new_s

    own_engine = engine
    executor_box: list = []  # lazily built so the updater is picklable-ish
    #: per-group sharding lists, keyed by the grads treedef (static across
    #: steps — rebuilt only when the param structure changes)
    group_shardings_cache: dict = {}

    def _executor() -> HostStreamExecutor:
        if not executor_box:
            new_params_box: list = []

            def apply(glob, group):
                new_p, new_s = _group_update(glob, group["g"], group["s"])
                new_params_box.append(new_p)
                return glob, new_s

            ex = HostStreamExecutor(apply, writeback=True, engine=own_engine)
            executor_box.append((ex, new_params_box))
        return executor_box[0]

    def update(grads, host_state, stats: Optional[StreamStats] = None):
        from repro.core.spillstore import is_disk_leaf

        ex, new_params_box = _executor()
        new_params_box.clear()
        step = int(host_state["step"]) + 1
        glob = _globals(grads, step)

        flat_g, treedef = jax.tree.flatten(grads)
        flat_s = treedef.flatten_up_to(host_state["leaves"])
        n = len(flat_g)
        bounds = _group_bounds(n, n_groups)
        groups = [
            {
                "g": tuple(flat_g[bounds[i] : bounds[i + 1]]),
                "s": tuple(flat_s[bounds[i] : bounds[i + 1]]),
            }
            for i in range(len(bounds) - 1)
        ]
        group_shardings = None
        if state_shardings is not None:
            # per-group layouts mirroring the group partition: grads are
            # device-resident (pass-by-reference; None = no placement),
            # moments stage at the plan's opt specs
            group_shardings = group_shardings_cache.get(treedef)
            if group_shardings is None:
                flat_sh = treedef.flatten_up_to(state_shardings)
                group_shardings = [
                    {
                        "g": tuple([None] * (bounds[i + 1] - bounds[i])),
                        "s": tuple(flat_sh[bounds[i] : bounds[i + 1]]),
                    }
                    for i in range(len(bounds) - 1)
                ]
                group_shardings_cache[treedef] = group_shardings

        _, state_outs = ex.run(
            glob,
            groups,
            mode=mode,
            prefetch=prefetch,
            stats=stats,
            group_shardings=group_shardings,
            group_keys=[f"opt/{i}" for i in range(len(groups))],
        )

        # disk-homed groups go back to their home tier: write the updated
        # moments to the spill store and keep only the memmap views
        for i, grp in enumerate(groups):
            if any(is_disk_leaf(v) for s in grp["s"] for v in jax.tree.leaves(s)):
                if spill_store is None:
                    raise RuntimeError(
                        "optimizer state group streamed from the DiskHost "
                        "tier but no spill_store was given to write it back"
                    )
                spill_store.put(_opt_group_key(i), state_outs[i])
                state_outs[i] = spill_store.get(_opt_group_key(i))

        flat_new_p = [p for chunk in new_params_box for p in chunk]
        flat_new_s = [s for chunk in state_outs for s in chunk]
        new_params = treedef.unflatten(flat_new_p)
        new_state = {
            "leaves": treedef.unflatten(flat_new_s),
            "step": np.asarray(step, np.int32),
        }
        metrics = {"grad_norm": glob["grad_norm"], "lr": glob["lr"]}
        return new_params, new_state, metrics

    update.close = lambda: executor_box and executor_box[0][0].close()  # type: ignore[attr-defined]
    return update


def make_streamed_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    mesh=None,
    sharder=None,
    *,
    n_groups: int = 4,
    prefetch: Optional[PrefetchSpec] = None,
    engine: Optional[TransferEngine] = None,
    stats: Optional[StreamStats] = None,
    spill_store=None,
    state_shardings: Optional[Pytree] = None,
) -> Callable[[dict, Pytree], tuple[dict, dict]]:
    """``(state, batch) -> (state, metrics)`` with host-resident optimizer.

    ``state = {"params": device pytree, "opt": host_opt_state(...)}``.  The
    forward/backward half is jitted; the AdamW half streams the host-kind
    moments through the transfer engine (see ``make_streamed_opt_updater``).
    With ``spill_store``, moment groups spilled to the ``DiskHost`` tier
    (see :func:`spill_opt_state`) stream disk->host->device and write back
    to disk.  ``state_shardings`` places the streamed moment groups at the
    sharding plan's opt specs (one coalesced H2D request per device per
    group under a mesh).
    """
    grad_fn = jax.jit(make_grad_step(cfg, mesh, sharder))
    updater = make_streamed_opt_updater(
        opt_cfg,
        compute_dtype=cfg.compute_dtype,
        n_groups=n_groups,
        prefetch=prefetch,
        engine=engine,
        spill_store=spill_store,
        state_shardings=state_shardings,
    )

    def step_fn(state, batch):
        loss, aux, grads = grad_fn(state["params"], batch)
        new_params, new_opt, om = updater(grads, state["opt"], stats=stats)
        metrics = {"loss": loss, **aux, **om}
        return {"params": new_params, "opt": new_opt}, metrics

    step_fn.close = updater.close  # type: ignore[attr-defined]
    return step_fn


# ---------------------------------------------------------------------------
# weight-streamed training / serving (host- or disk-homed model parameters)
# ---------------------------------------------------------------------------


def _abstract_leaf(x) -> bool:
    return isinstance(x, (jax.core.Tracer, jax.ShapeDtypeStruct))


def _opt_state_leaf(p):
    """AdamW state for one host-homed parameter leaf (numpy between steps;
    tracer-safe for the driver's ``eval_shape`` restore template)."""
    if _abstract_leaf(p):
        z = jnp.zeros(jnp.shape(p), jnp.float32)
        return {"master": p.astype(jnp.float32), "m": z, "v": z}
    a = np.asarray(p)
    return {
        "master": np.asarray(a, np.float32),
        "m": np.zeros(a.shape, np.float32),
        "v": np.zeros(a.shape, np.float32),
    }


def _init_group_f32(key: jax.Array, cfg: ModelConfig, plan: WeightStreamPlan, g, shell_box: dict):
    """One home group's f32 init leaves — exactly :func:`transformer.init_model`'s
    values for those leaves, computed without materializing any other layer
    (the group-wise init: at most one layer slice is device-resident at a
    time — ``shell_box`` carries a one-entry slice cache so an expert-split
    layer's E + 1 groups share one init of its slice)."""
    if g.kind in ("layers", "block", "expert"):
        ck = ("slice", g.lo, g.hi)
        if shell_box.get("slice_key") != ck:
            shell_box["slice_key"] = ck
            shell_box["slice"] = transformer.init_model_slice(key, cfg, g.lo, g.hi)
        sl = shell_box["slice"]
        if g.kind == "expert":
            return {n: sl["moe"][n][:, g.expert] for n in plan.expert_names}
        if g.kind == "layers" and plan.expert_stream:
            return plan._strip_experts(sl)
        return sl
    if g.kind == "period":
        p = cfg.scan_period
        return transformer.init_model_period_slice(key, cfg, g.lo // p, g.hi // p)
    if "shell" not in shell_box:
        shell_box["shell"] = transformer.init_model_shell(key, cfg)
    keys = plan.embed_keys if g.kind == "embed" else plan.head_home_keys
    return {k: shell_box["shell"][k] for k in keys}


def init_weight_streamed_params(
    key: jax.Array, cfg: ModelConfig, plan: WeightStreamPlan
) -> dict:
    """Parameter home (compute-dtype, host-numpy leaves) initialized
    group-wise: bitwise-identical to homing ``init_train_state(key, cfg)``
    but only ever one transfer group device-resident — arbitrarily large
    models initialize under the device budget."""
    dt = cfg.compute_dtype
    shell_box: dict = {}
    groups = {}
    for g in plan.groups:
        f32 = _init_group_f32(key, cfg, plan, g, shell_box)
        groups[g.key] = jax.tree.map(
            lambda p: _to_host(p.astype(dt)), f32
        )
    return {"groups": groups}


def init_weight_streamed_state(key: jax.Array, cfg: ModelConfig, plan: WeightStreamPlan) -> dict:
    """``{"params": home, "opt": grouped state}`` with host-numpy leaves
    (the ``pinned_host`` home; callers spill/place for disk/device kinds).

    Initialization is group-wise (see :func:`init_weight_streamed_params`),
    and the AdamW masters come from the **f32** init values — the same
    fidelity as :func:`init_train_state`, whose master is taken before the
    compute-dtype cast."""
    dt = cfg.compute_dtype
    shell_box: dict = {}
    p_groups = {}
    o_groups = {}
    for g in plan.groups:
        f32 = _init_group_f32(key, cfg, plan, g, shell_box)
        p_groups[g.key] = jax.tree.map(lambda p: _to_host(p.astype(dt)), f32)
        o_groups[g.key] = jax.tree.map(_opt_state_leaf, f32)
    step = (
        jnp.zeros((), jnp.int32)
        if any(_abstract_leaf(x) for x in jax.tree.leaves(p_groups))
        else np.zeros((), np.int32)
    )
    return {
        "params": {"groups": p_groups},
        "opt": {"groups": o_groups, "step": step},
    }


def spill_weight_streamed_state(
    plan: WeightStreamPlan, state: dict, store
) -> dict:
    """Re-home a weight-streamed train state at the ``DiskHost`` tier: one
    spill chunk per param group (``wp/<key>``) and one per moment group
    (``wopt/<key>``).  Abstract templates and already-spilled groups pass
    through — the trainer calls this after checkpoint restore to re-impose
    the disk home on the plain host arrays restore hands back."""
    from repro.core.spillstore import is_disk_leaf

    home = plan.spill_home(state["params"], store)
    opt_groups = {}
    for g in plan.groups:
        tree = state["opt"]["groups"][g.key]
        leaves = jax.tree.leaves(tree)
        if any(_abstract_leaf(x) for x in leaves):
            return {"params": home, "opt": state["opt"]}
        if not all(is_disk_leaf(x) for x in leaves):
            store.put(f"wopt/{g.key}", tree)
            tree = store.get(f"wopt/{g.key}")
        opt_groups[g.key] = tree
    return {
        "params": home,
        "opt": {"groups": opt_groups, "step": state["opt"]["step"]},
    }


def _leaf_sqsums(tree: Pytree) -> tuple:
    """Per-leaf squared sums (f32) — the partial terms of
    :func:`repro.optim.adamw.global_norm`, computed group-wise so the full
    gradient tree never has to co-reside."""
    return tuple(
        jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)
    )


def make_weight_streamed_train_step(
    cfg: ModelConfig,
    opt_cfg: AdamWConfig,
    mesh=None,
    sharder=None,
    *,
    plan: WeightStreamPlan,
    prefetch: Optional[PrefetchSpec] = None,
    engine: Optional[TransferEngine] = None,
    stats: Optional[StreamStats] = None,
    opt_stats: Optional[StreamStats] = None,
    spill_store=None,
    param_shardings: Optional[Pytree] = None,
    param_kind: str = "pinned_host",
    residency: Optional[ResidencyCache] = None,
) -> Callable[[dict, Pytree], tuple[dict, dict]]:
    """``(state, batch) -> (state, metrics)`` with host/disk-homed weights.

    ``state = {"params": ..., "opt": ...}`` as built by
    :func:`init_weight_streamed_state` (grouped homes + grouped moments).
    One step runs three streamed passes over the plan's transfer groups:

    forward
        fetch order ``embed, L0..Ln, head``; each group applies its jitted
        stage while the next groups stream in behind it.  The head stage
        computes the loss **and** the head/trunk cotangents (its params are
        in hand, so the head group is fetched exactly once).
    backward
        **reverse** fetch order ``Ln..L0, embed``: each layer group is
        re-fetched and its vjp recomputes the group forward from the saved
        boundary activation (group-granular activation checkpointing), so
        the backward peak residency equals the forward's.  Per-group
        gradients stream back D2H through the engine's pipelined writeback;
        per-leaf squared sums stay on device for the global norm.
    optimizer
        reverse home order (head first — its gradients were born on device
        and are released immediately): each group streams
        ``{grads, moments}`` H2D and its updated ``{params, moments}`` ride
        ONE pipelined D2H drain back to the home kind — the params
        writeback shares the drain with the streamed-AdamW moments.

    ``param_kind`` names the home tier (``pinned_host`` | ``disk_host`` |
    ``device`` — the bitwise baseline: fetch groups pass through the
    engine by reference and updated groups are re-placed on device).  The
    math per group is exactly :func:`repro.optim.adamw.adamw_leaf_update`
    with globals from the streamed norm, and every kind runs the same
    jitted programs on the same values — streamed runs are bitwise-equal
    to the device-resident run (gated in ``benchmarks/weight_stream.py``).

    ``stats`` accounts the parameter fetch passes (forward + backward) —
    its ``peak_inflight_bytes`` is what ``--device-budget-mb`` bounds;
    ``opt_stats`` accounts the optimizer phase separately.

    ``residency`` is the weight-residency group cache (default: one sized
    to the plan's budget slack — see
    :meth:`WeightStreamPlan.residency_capacity_bytes`; inert at zero
    slack).  Landed fetch groups stay device-resident up to its capacity,
    the last K layer groups are PINNED across the forward→backward
    turnaround so the reverse-order backward's first K groups are hits
    instead of re-fetches, and the optimizer phase REFRESHES every cached
    group in place with the post-update device values (the same bits its
    D2H drain writes to the home) — a stale cached group after the
    group-wise optimizer update would silently train on old weights, so a
    step that fails mid-update clears the cache outright.
    """
    if param_kind == "disk_host" and spill_store is None:
        raise ValueError("param_kind='disk_host' requires a spill_store")
    prefetch = prefetch or PrefetchSpec(
        buffer_size=plan.n_groups + 2, distance="auto"
    )
    mode = "on_demand" if prefetch.on_demand else "prefetch"
    pf = None if mode == "on_demand" else prefetch
    if residency is None and param_kind != "device":
        residency = ResidencyCache(plan.residency_capacity_bytes())
    #: device-kind homes already pass through at zero requests — caching
    #: them would only alias the home groups
    cache = residency if param_kind != "device" else None
    cache_reserved = (
        cache.capacity_bytes or 0
    ) if cache is not None and plan.device_budget_bytes is not None else 0
    own_engine = engine is None
    if engine is None:
        engine = TransferEngine(
            EngineConfig(
                max_distance=plan.max_distance_for_budget(
                    cached_bytes=cache_reserved
                )
            )
        )
    elif (
        plan.device_budget_bytes is not None
        and engine.config.max_distance
        > plan.max_distance_for_budget(cached_bytes=cache_reserved)
    ):
        raise ValueError(
            f"engine max_distance={engine.config.max_distance} exceeds the "
            f"device budget's window cap "
            f"{plan.max_distance_for_budget(cached_bytes=cache_reserved)} "
            "(prefetch window + residency cache share the budget); "
            "configure the engine from the plan"
        )
    # static schedule verification at construction: symbolically execute
    # the three phases at the engine's widest window and fail fast on any
    # budget/hazard/pin violation — a schedule bug surfaces here, not 40
    # minutes into a streamed run (see repro.core.schedcheck)
    verify_schedule(
        analyze_train_schedule(
            plan,
            distance=engine.config.max_distance,
            cached=cache is not None,
            cache_capacity=cache.capacity_bytes if cache is not None else None,
            spill=param_kind == "disk_host",
        )
    )
    stats = stats if stats is not None else StreamStats()
    opt_stats = opt_stats if opt_stats is not None else StreamStats()
    f32 = jnp.float32

    # -- group program maps: the step walks plan.units, one jitted stage per
    # unit (a "moe" unit's groups are buffered until its last group lands)
    units = plan.units
    head_idx = plan.n_groups - 1
    #: group index -> (unit position, True when this group completes its
    #: unit in FORWARD fetch order)
    unit_pos: dict = {}
    for u_i, u in enumerate(units):
        for j, gi in enumerate(u.gidx):
            unit_pos[gi] = (u_i, j == len(u.gidx) - 1)
    #: backward program: strict reverse of the middle groups, then embed
    bwd_order = [plan.groups[i] for i in range(head_idx - 1, 0, -1)] + [
        plan.groups[0]
    ]
    #: backward position -> (unit position, True when this group completes
    #: its unit in REVERSE order — i.e. the unit's forward-first group)
    bwd_map: dict = {}
    for pos, g in enumerate(bwd_order[:-1]):
        u_i, _ = unit_pos[g.index]
        bwd_map[pos] = (u_i, g.index == units[u_i].gidx[0])

    #: the forward→backward turnaround pin set: backward consumes groups in
    #: reverse fetch order, so the LAST groups forward fetched are the FIRST
    #: backward wants — pin as many of them as the cache can hold so they
    #: cannot be evicted between the passes (the double-fetch this PR kills)
    pin_keys: frozenset = frozenset()
    if cache is not None:
        picked: list = []
        total = 0
        for g in bwd_order:
            nb = plan.group_bytes(g, fetch=False)
            if cache.capacity_bytes is not None and total + nb > cache.capacity_bytes:
                break
            picked.append(g.key)
            total += nb
        pin_keys = frozenset(picked)

    def _store(g, fetched, *, pinned: bool = False) -> None:
        """Retain a landed fetch group in the residency cache (home part
        only — the tied head's borrowed embed leaf stays with group 0)."""
        if cache is not None:
            cache.put(
                g.key,
                plan.cache_home_tree(g, fetched),
                plan.group_bytes(g, fetch=False),
                pinned=pinned,
            )

    # -- jitted stage programs (identical for every param kind) -------------
    @jax.jit
    def embed_fwd(group, batch):
        x = transformer.embed_stage(cfg, group, batch, sharder=sharder)
        return x, transformer.stage_angles(cfg, batch, x.shape[1])

    @jax.jit
    def group_fwd(group, x, aux, angles):
        return transformer.block_group_train(cfg, group, x, aux, angles, mesh, sharder)

    def _head_loss(group, x, aux, batch):
        return transformer.head_stage_loss(cfg, group, x, aux, batch)

    @jax.jit
    def head_grad(group, x, aux, batch):
        (loss, metrics), (dp, dx) = jax.value_and_grad(
            _head_loss, argnums=(0, 1), has_aux=True
        )(group, x, aux, batch)
        dp_home, dp_embed = plan.split_head_grads(dp)
        return loss, metrics, dp_home, dp_embed, dx, _leaf_sqsums(dp_home)

    @jax.jit
    def group_bwd(group, x_in, angles, ct_x):
        def f(p, x):
            return transformer.block_group_train(
                cfg, p, x, jnp.zeros((), f32), angles, mesh, sharder
            )

        _, vjp = jax.vjp(f, group, x_in)
        dp, dx = vjp((ct_x, jnp.ones((), f32)))
        return dp, dx, _leaf_sqsums(dp)

    # -- per-unit-kind stages beyond the uniform "layers" pair: the moe unit
    # re-merges its expert groups device-side (bitwise-identical to the
    # unsplit slice), period/block units run the hetero scan/unrolled bodies
    @jax.jit
    def moe_fwd(ne, experts, x, aux, angles):
        merged = merge_expert_slice(ne, experts)
        return transformer.block_group_train(cfg, merged, x, aux, angles, mesh, sharder)

    @jax.jit
    def moe_bwd(ne, experts, x_in, angles, ct_x):
        def f(ne_, ex_, x):
            merged = merge_expert_slice(ne_, ex_)
            return transformer.block_group_train(
                cfg, merged, x, jnp.zeros((), f32), angles, mesh, sharder
            )

        _, vjp = jax.vjp(f, ne, experts, x_in)
        dp_ne, dp_ex, dx = vjp((ct_x, jnp.ones((), f32)))
        return dp_ne, dp_ex, dx, _leaf_sqsums((dp_ne, dp_ex))

    @jax.jit
    def period_fwd(group, x, aux, angles):
        return transformer.period_group_train(cfg, group, x, aux, angles, sharder)

    @jax.jit
    def period_bwd(group, x_in, angles, ct_x):
        def f(p, x):
            return transformer.period_group_train(
                cfg, p, x, jnp.zeros((), f32), angles, sharder
            )

        _, vjp = jax.vjp(f, group, x_in)
        dp, dx = vjp((ct_x, jnp.ones((), f32)))
        return dp, dx, _leaf_sqsums(dp)

    def _make_block_fns(g):
        kinds = tuple(
            (name, cfg.block_kind(l))
            for name, l in zip(plan.block_names(g), range(g.lo, g.hi))
        )

        @jax.jit
        def fwd(group, x, aux, angles):
            return transformer.hetero_group_train(
                cfg, kinds, group, x, aux, angles, sharder
            )

        @jax.jit
        def bwd(group, x_in, angles, ct_x):
            def f(p, x):
                return transformer.hetero_group_train(
                    cfg, kinds, p, x, jnp.zeros((), f32), angles, sharder
                )

            _, vjp = jax.vjp(f, group, x_in)
            dp, dx = vjp((ct_x, jnp.ones((), f32)))
            return dp, dx, _leaf_sqsums(dp)

        return fwd, bwd

    unit_fwd: list = []
    unit_bwd: list = []
    for u in units:
        if u.kind == "layers":
            unit_fwd.append(group_fwd)
            unit_bwd.append(group_bwd)
        elif u.kind == "moe":
            unit_fwd.append(moe_fwd)
            unit_bwd.append(moe_bwd)
        elif u.kind == "period":
            unit_fwd.append(period_fwd)
            unit_bwd.append(period_bwd)
        else:  # "block": kinds are static per group, so one jit per unit
            fwd, bwd = _make_block_fns(plan.groups[u.gidx[0]])
            unit_fwd.append(fwd)
            unit_bwd.append(bwd)

    @jax.jit
    def embed_bwd(group, batch, ct_x, extra):
        def f(p):
            return transformer.embed_stage(cfg, p, batch, sharder=sharder)

        _, vjp = jax.vjp(f, group)
        (dp,) = vjp(ct_x)
        if extra is not None:
            # tied/codebook head: the embedding table's gradient is the sum
            # of the gather path and the head path (autodiff would have
            # summed them in the monolithic graph)
            dp = dict(dp)
            dp["embed"] = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), dp["embed"], extra
            )
        return dp, _leaf_sqsums(dp)

    @jax.jit
    def globals_fn(sq_chunks, step):
        gnorm = jnp.sqrt(
            jnp.sum(jnp.stack([s for chunk in sq_chunks for s in chunk]))
        )
        return adamw_globals_from_norm(opt_cfg, gnorm, step)

    @jax.jit
    def opt_group(glob, grads_tree, state_tree):
        flat_g, treedef = jax.tree.flatten(grads_tree)
        flat_s = treedef.flatten_up_to(state_tree)
        out = [adamw_leaf_update(opt_cfg, glob, g, s) for g, s in zip(flat_g, flat_s)]
        new_p = treedef.unflatten([p.astype(cfg.compute_dtype) for p, _ in out])
        new_s = treedef.unflatten([s for _, s in out])
        return new_p, new_s

    # -- streamed phase drivers ---------------------------------------------
    box: dict = {}

    def apply_f(i, carry, group):
        g = plan.groups[i]
        _store(g, group, pinned=g.key in pin_keys)
        if i == 0:
            box["x"], box["angles"] = embed_fwd(group, box["batch"])
            box["aux"] = jnp.zeros((), f32)
            box["acts"] = []
            box["parts"] = []
            return box["x"]
        if i == head_idx:
            loss, metrics, dp_home, dp_embed, dx, sq = head_grad(
                group, box["x"], box["aux"], box["batch"]
            )
            box.update(
                loss=loss, metrics=metrics, dp_head_home=dp_home,
                dp_head_embed=dp_embed, ct=dx, sq=[sq],
            )
            return loss
        u_i, last = unit_pos[i]
        box["parts"].append(group)
        if not last:  # moe unit: buffer until every group of the unit landed
            return box["x"]
        parts, box["parts"] = box["parts"], []
        box["acts"].append(box["x"])  # unit-boundary activation checkpoint
        if units[u_i].kind == "moe":
            box["x"], box["aux"] = unit_fwd[u_i](
                parts[0], tuple(parts[1:]), box["x"], box["aux"], box["angles"]
            )
        else:
            box["x"], box["aux"] = unit_fwd[u_i](
                parts[0], box["x"], box["aux"], box["angles"]
            )
        return box["x"]

    def apply_b(i, carry, group):
        g = bwd_order[i]
        _store(g, group)
        if i == len(bwd_order) - 1:  # embed, last in backward order
            dp, sq = embed_bwd(group, box["batch"], box["ct"], box["dp_head_embed"])
            box["sq"].append(sq)
            return box["ct"], dp
        u_i, trigger = bwd_map[i]
        if not trigger:
            # moe unit: experts arrive (reversed) before the non-expert
            # trigger group; their grads drain at the trigger position, so
            # a scalar placeholder keeps the writeback stream aligned
            box["parts"].append(group)
            return box["ct"], jnp.zeros((), f32)
        x_in = box["acts"][u_i]  # reverse fetch order: unit u_i's boundary
        if units[u_i].kind == "moe":
            experts = tuple(reversed(box["parts"]))
            box["parts"] = []
            dp_ne, dp_ex, dx, sq = unit_bwd[u_i](
                group, experts, x_in, box["angles"], box["ct"]
            )
            box["ct"] = dx
            box["sq"].append(sq)
            return dx, {"ne": dp_ne, "ex": dp_ex}
        dp, dx, sq = unit_bwd[u_i](group, x_in, box["angles"], box["ct"])
        box["ct"] = dx
        box["sq"].append(sq)
        return dx, dp

    def apply_o(i, carry, group):
        new_p, new_s = opt_group(box["glob"], group["g"], group["s"])
        if cache is not None:
            # writeback invalidation, done as an update-in-place: the
            # optimizer just made every cached copy of this group stale,
            # and ``new_p`` here is the exact device value whose D2H drain
            # becomes the new home bytes — refreshing with it keeps the
            # cache bitwise-identical to a re-fetch of the new home
            g = o_order[i]
            cache.refresh(
                g.key,
                plan.cache_home_tree(g, new_p),
                plan.group_bytes(g, fetch=False),
            )
        return carry, {"p": new_p, "s": new_s}

    ex_f = HostStreamExecutor(apply_f, indexed=True, engine=engine)
    ex_b = HostStreamExecutor(apply_b, indexed=True, writeback=True, engine=engine)
    ex_o = HostStreamExecutor(apply_o, indexed=True, writeback=True, engine=engine)

    sh_fwd = plan.group_shardings(param_shardings)
    sh_home = plan.home_group_shardings(param_shardings)
    sh_bwd = None
    sh_o = None
    if param_shardings is not None:
        sh_bwd = [sh_fwd[g.index] for g in bwd_order]
        opt_sh = [
            jax.tree.map(
                lambda s: {"master": s, "m": s, "v": s},
                h,
                is_leaf=lambda s: isinstance(s, jax.sharding.NamedSharding),
            )
            for h in sh_home
        ]
        order = [head_idx] + [g.index for g in bwd_order]
        sh_o = [{"g": sh_home[j], "s": opt_sh[j]} for j in order]

    #: phase-O group order: head first (its grads were born on device at the
    #: head stage and pass by reference — consumed and released immediately)
    o_order = [plan.groups[-1]] + bwd_order

    def _rehome(g, p_new, s_new, idx):
        if param_kind == "disk_host":
            spill_store.put(plan.spill_key(g), p_new)
            spill_store.put(f"wopt/{g.key}", s_new)
            return spill_store.get(plan.spill_key(g)), spill_store.get(f"wopt/{g.key}")
        if param_kind == "device":
            sh = sh_home[idx] if sh_home is not None else None
            if sh is None:
                return jax.device_put(p_new), jax.device_put(s_new)
            opt_sh = jax.tree.map(
                lambda s: {"master": s, "m": s, "v": s},
                sh,
                is_leaf=lambda s: isinstance(s, jax.sharding.NamedSharding),
            )
            return jax.device_put(p_new, sh), jax.device_put(s_new, opt_sh)
        return p_new, s_new  # pinned_host: the drained numpy IS the home

    def _step_body(state, batch):
        home, opt = state["params"], state["opt"]
        box.clear()
        box["batch"] = batch

        # phase F: forward fetch order [embed, L0..Ln, head].  With a cache
        # the fetch sequence is thunks resolved at submit time, so each
        # submit sees the residency state the moment the transfer would go
        # out (e.g. the embed group landed two submits ago → the tied head's
        # table leaf is borrowed instead of re-read over the link).
        if cache is not None:
            fwd_groups = plan.fetch_thunks_forward(home, cache)
        else:
            fwd_groups = plan.fetch_groups_forward(home)
        with span("train.fwd"):
            ex_f.run(
                jnp.zeros(()), fwd_groups, mode=mode, prefetch=pf, stats=stats,
                group_shardings=sh_fwd,
                group_keys=[g.key for g in plan.groups],
            )

        # phase B: reverse fetch order [middle reversed, embed]; grads drain
        # D2H.  The pinned turnaround set makes the first fetches cache hits.
        if cache is not None:
            bwd_groups = [
                (lambda g=g: plan.fetch_group(home, g, cache)) for g in bwd_order
            ]
        else:
            bwd_groups = [fwd_groups[g.index] for g in bwd_order]
        with span("train.bwd"):
            _, grad_outs = ex_b.run(
                box["ct"], bwd_groups, mode=mode, prefetch=pf, stats=stats,
                group_shardings=sh_bwd,
                group_keys=[g.key for g in bwd_order],
            )

        step_no = int(np.asarray(opt["step"])) + 1
        box["glob"] = globals_fn(tuple(box["sq"]), step_no)

        # phase O: {grads, moments} H2D, {params, moments} one D2H drain.
        # A moe unit drained all its grads at its trigger position — split
        # them back out so every group (experts included) updates on its own
        grads_by_key = {plan.groups[-1].key: box["dp_head_home"]}
        grads_by_key[plan.groups[0].key] = grad_outs[-1]
        for pos, g in enumerate(bwd_order[:-1]):
            u_i, trigger = bwd_map[pos]
            if not trigger:
                continue
            u = units[u_i]
            if u.kind == "moe":
                out = grad_outs[pos]
                grads_by_key[plan.groups[u.gidx[0]].key] = out["ne"]
                for e_j, gi in enumerate(u.gidx[1:]):
                    grads_by_key[plan.groups[gi].key] = out["ex"][e_j]
            else:
                grads_by_key[g.key] = grad_outs[pos]
        o_groups = [
            {"g": grads_by_key[g.key], "s": opt["groups"][g.key]} for g in o_order
        ]
        with span("train.opt"):
            _, o_outs = ex_o.run(
                jnp.zeros(()), o_groups, mode=mode, prefetch=pf, stats=opt_stats,
                group_shardings=sh_o,
                group_keys=[g.key for g in o_order],
            )

        new_home: dict = {}
        new_opt: dict = {}
        with span("train.rehome"):
            for g, out in zip(o_order, o_outs):
                p_new, s_new = _rehome(g, out["p"], out["s"], g.index)
                new_home[g.key] = p_new
                new_opt[g.key] = s_new

        glob = box["glob"]
        metrics = {
            "loss": box["loss"], **box["metrics"],
            "grad_norm": glob["grad_norm"], "lr": glob["lr"],
        }
        new_state = {
            "params": {"groups": new_home},
            "opt": {"groups": new_opt, "step": np.asarray(step_no, np.int32)},
        }
        # release the step's device scratch (boundary activations, head
        # grads, cotangents) — it must not outlive the step into the
        # checkpoint/data gap, where the residency model doesn't count it
        box.clear()
        return new_state, metrics

    def step_fn(state, batch):
        with span("train.step", step=int(np.asarray(state["opt"]["step"])) + 1):
            if cache is None:
                return _step_body(state, batch)
            try:
                return _step_body(state, batch)
            except BaseException:
                # a step that died mid-optimizer leaves some cached groups
                # refreshed and some stale — indistinguishable from outside,
                # so the only safe cache is an empty one
                cache.clear()
                raise
            finally:
                cache.unpin_all()

    def close():
        for ex in (ex_f, ex_b, ex_o):
            ex.close()
        if own_engine:
            engine.close()
        if cache is not None:
            cache.clear()  # release the resident device copies

    step_fn.close = close  # type: ignore[attr-defined]
    step_fn.param_stats = stats  # type: ignore[attr-defined]
    step_fn.opt_stats = opt_stats  # type: ignore[attr-defined]
    step_fn.engine = engine  # type: ignore[attr-defined]
    step_fn.residency = cache  # type: ignore[attr-defined]
    return step_fn


def make_weight_streamed_prefill_step(
    cfg: ModelConfig,
    plan: WeightStreamPlan,
    batch_size: int,
    seq_len: int,
    mesh=None,
    sharder=None,
    *,
    engine: TransferEngine,
    prefetch: Optional[PrefetchSpec] = None,
    stats: Optional[StreamStats] = None,
    param_shardings: Optional[Pytree] = None,
    residency: Optional[ResidencyCache] = None,
) -> Callable[[dict, Pytree], tuple[jax.Array, Pytree]]:
    """``(home, batch) -> (last-token logits, caches)`` with the params
    streamed group-wise; each layer group fills its stacked cache slice and
    the full cache is concatenated once at the end.

    ``residency`` keeps landed groups device-resident across calls: serve
    params are immutable, so there is no invalidation — a resident group
    passes through the engine at zero requests on every later prefill or
    decode step until the LRU evicts it."""
    prefetch = prefetch or PrefetchSpec(
        buffer_size=plan.n_groups + 2, distance="auto"
    )
    mode = "on_demand" if prefetch.on_demand else "prefetch"
    pf = None if mode == "on_demand" else prefetch
    head_idx = plan.n_groups - 1
    unit_pos = {}
    for u_i, u in enumerate(plan.units):
        for j, gi in enumerate(u.gidx):
            unit_pos[gi] = (u_i, j == len(u.gidx) - 1)

    @jax.jit
    def embed_fwd(group, batch):
        x = transformer.embed_stage(cfg, group, batch, sharder=sharder)
        return x, transformer.stage_angles(cfg, batch, x.shape[1])

    @jax.jit
    def group_prefill(group, x, angles):
        n = jax.tree.leaves(group)[0].shape[0]
        cache = transformer.init_cache_group(
            cfg, n, batch_size, seq_len, cfg.compute_dtype
        )
        return transformer.block_group_prefill(cfg, group, cache, x, angles, sharder)

    @jax.jit
    def moe_prefill(ne, experts, x, angles):
        # prefill overlaps the all-expert fetch with compute: the merged
        # slice is bitwise-identical to the unsplit layer group's
        merged = merge_expert_slice(ne, experts)
        n = jax.tree.leaves(merged)[0].shape[0]
        cache = transformer.init_cache_group(
            cfg, n, batch_size, seq_len, cfg.compute_dtype
        )
        return transformer.block_group_prefill(cfg, merged, cache, x, angles, sharder)

    @jax.jit
    def head_fwd(group, x, last_pos):
        xl = jax.lax.dynamic_slice_in_dim(x, last_pos, 1, axis=1)
        return transformer.head_stage_logits(cfg, group, xl)

    @jax.jit
    def concat0(slices):
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *slices)

    box: dict = {}

    def apply(i, carry, group):
        if residency is not None:
            g = plan.groups[i]
            residency.put(
                g.key, plan.cache_home_tree(g, group),
                plan.group_bytes(g, fetch=False),
            )
        if i == 0:
            box["x"], box["angles"] = embed_fwd(group, box["batch"])
            box["slices"] = []
            box["parts"] = []
            return box["x"]
        if i == head_idx:
            box["logits"] = head_fwd(group, box["x"], box["last_pos"])
            return box["logits"]
        u_i, last = unit_pos[i]
        box["parts"].append(group)
        if not last:
            return box["x"]
        parts, box["parts"] = box["parts"], []
        if plan.units[u_i].kind == "moe":
            box["x"], sl = moe_prefill(
                parts[0], tuple(parts[1:]), box["x"], box["angles"]
            )
        else:
            box["x"], sl = group_prefill(parts[0], box["x"], box["angles"])
        box["slices"].append(sl)
        return box["x"]

    ex = HostStreamExecutor(apply, indexed=True, engine=engine)
    sh_fwd = plan.group_shardings(param_shardings)

    def prefill(home, batch, last_pos=None):
        box.clear()
        box["batch"] = batch
        if last_pos is None:
            # static last position == the batch's sequence length - 1
            # (exact-length prompts; bitwise-identical to the x[:, -1:]
            # slice this path used before bucketed prefill existed)
            seq = jax.tree.leaves(batch)[0].shape[-1]
            last_pos = jnp.asarray(seq - 1, jnp.int32)
        box["last_pos"] = last_pos
        groups = (
            plan.fetch_thunks_forward(home, residency)
            if residency is not None
            else plan.fetch_groups_forward(home)
        )
        ex.run(
            jnp.zeros(()), groups, mode=mode,
            prefetch=pf, stats=stats, group_shardings=sh_fwd,
            group_keys=[g.key for g in plan.groups],
        )
        logits, caches = box["logits"], concat0(tuple(box["slices"]))
        box.clear()  # don't retain the per-group cache slices between calls
        return logits, caches

    prefill.close = ex.close  # type: ignore[attr-defined]
    prefill.residency = residency  # type: ignore[attr-defined]
    return prefill


def make_weight_streamed_decode_step(
    cfg: ModelConfig,
    plan: WeightStreamPlan,
    mesh=None,
    sharder=None,
    *,
    engine: TransferEngine,
    prefetch: Optional[PrefetchSpec] = None,
    stats: Optional[StreamStats] = None,
    param_shardings: Optional[Pytree] = None,
    paged: bool = True,
    residency: Optional[ResidencyCache] = None,
    route_experts: bool = True,
    expert_stats: Optional[StreamStats] = None,
) -> Callable[..., tuple[jax.Array, Pytree]]:
    """Streamed-params decode step.

    ``paged=True``: ``(home, view, batch, pos) -> (logits, caches)`` over a
    pager page view (assembly is the same separate jit as
    :func:`make_paged_decode_step`, so paging composes unchanged).
    ``paged=False``: ``(home, caches, batch, pos)`` over a dense cache.
    Per step the fetch groups stream in forward order while each layer
    group decodes against its static cache slice; the updated slices are
    concatenated back into the dense cache.

    Route-aware expert streaming (``plan.expert_stream``): the pipeline
    fetches only each MoE layer's non-expert group; the stage runs the
    router first (:func:`transformer.block_decode_pre_moe`), then only the
    routed experts' groups are fetched through the engine — resident
    experts (the expert-granular LRU in ``residency``) pass through at zero
    link bytes.  ``route_experts=False`` fetches all E experts through the
    SAME path (the bench's all-expert baseline).  Expert fetch traffic is
    accounted in ``expert_stats`` (its per-tier
    ``requests_per_fetched_device_group`` stays 1.0: one coalesced request
    per fetched expert group per device); the jitted apply re-traces per
    distinct routed-subset size, which is bounded by ``moe_top_k``·batch.
    The routed output is bitwise-equal to the all-expert and
    device-resident runs: the gather of the routed rows happens before any
    arithmetic, so the subset stage computes on the exact same values.
    """
    from repro.core import kvpager

    prefetch = prefetch or PrefetchSpec(
        buffer_size=plan.n_groups + 2, distance="auto"
    )
    mode = "on_demand" if prefetch.on_demand else "prefetch"
    pf = None if mode == "on_demand" else prefetch
    if plan.expert_stream and expert_stats is None:
        expert_stats = StreamStats()
    #: the pipeline program: expert groups are fetched on demand AFTER the
    #: router runs, so only each unit's first group rides the fetch pipeline
    #: (identical to plan.groups when no unit spans multiple groups)
    prog = (
        [plan.groups[0]]
        + [plan.groups[u.gidx[0]] for u in plan.units]
        + [plan.groups[-1]]
    )
    head_pos = len(prog) - 1
    bounds = [(u.lo, u.hi) for u in plan.units]

    @jax.jit
    def split(caches):
        return tuple(
            jax.tree.map(
                lambda a: jax.lax.slice_in_dim(a, lo, hi, axis=0), caches
            )
            for lo, hi in bounds
        )

    @jax.jit
    def embed_dec(group, batch, pos):
        x = transformer.embed_stage(cfg, group, batch, pos=pos, sharder=sharder)
        return x, transformer.stage_angles(cfg, batch, 1, pos=pos)

    @jax.jit
    def group_dec(group, cache_slice, x, angles, pos):
        return transformer.block_group_decode(
            cfg, group, cache_slice, x, angles, pos, sharder
        )

    @jax.jit
    def ne_dec(group, cache_slice, x, angles, pos):
        return transformer.block_decode_pre_moe(
            cfg, group, cache_slice, x, angles, pos, sharder
        )

    @jax.jit
    def moe_apply(parts, ids, top_w, top_i, x_attn, h2):
        # gather-then-cast over the fetched subset: the stacked rows are the
        # same bytes the full (L, E, d, f) home holds, so this is bitwise-
        # equal to moe.moe_decode over the unsplit layer group
        stack = {
            n: jnp.concatenate([t[n] for t in parts], axis=0)
            for n in plan.expert_names
        }
        local = jnp.searchsorted(ids, top_i)
        y = moe.decode_apply(cfg, stack, top_w, local, h2)
        x = x_attn + y
        if sharder is not None:
            x = sharder.acts(x)
        return x

    @jax.jit
    def head_dec(group, x):
        return transformer.head_stage_logits(cfg, group, x)

    @jax.jit
    def concat0(slices):
        return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *slices)

    assemble = jax.jit(kvpager.assemble_view)
    sh_all = plan.group_shardings(param_shardings)
    sh_prog = [sh_all[g.index] for g in prog] if sh_all is not None else None
    box: dict = {}

    def _fetch_experts(home, gs):
        """Fetch routed expert groups through the engine (submit-all, then
        wait in order), with the executor's submit/wait accounting mirrored
        into ``expert_stats``; landed groups enter the residency LRU."""
        st = expert_stats
        futs = []
        live = 0
        for g in gs:
            tree = residency.lookup(g.key) if residency is not None else None
            if residency is not None and getattr(residency, "sanitize", False):
                residency.sanitize_home(
                    g.key, home["groups"][g.key], hit=tree is not None
                )
            if tree is None:
                tree = home["groups"][g.key]
            sh = sh_all[g.index] if sh_all is not None else None
            fut = engine.submit_group(
                g.index, tree, device_shardings=sh, key=g.key
            )
            if st is not None:
                st.n_transfers += 1
                st.n_groups += 1
                st.h2d_requests += fut.n_requests
                st.bytes_h2d += fut.nbytes
                st.disk_requests += fut.disk_requests
                st.bytes_disk += fut.disk_nbytes
                st.n_devices = max(st.n_devices, fut.n_devices)
                st.n_device_groups += fut.n_devices
                if fut.is_resident:
                    st.cache_hits += 1
                else:
                    st.cache_misses += 1
                    st.unique_group_fetches += 1
                    st.fetched_device_groups += fut.n_devices
                live += fut.nbytes
                st.peak_inflight_bytes = max(st.peak_inflight_bytes, live)
            futs.append((g, fut))
        parts = []
        for g, fut in futs:
            try:
                with span("stream.wait", group=g.index):
                    w = fut.wait()
            except BaseException:
                if st is not None:
                    st.retries += fut.retries
                    st.give_ups += 1
                engine.settle()
                raise
            if st is not None:
                st.retries += fut.retries
                st.transfer_wait_s += w
                st.wait_per_group.append(w)
                st.disk_wait_s += fut.disk_wait_s
                st.disk_wait_per_group.append(fut.disk_wait_s)
            with span("stream.unpack", group=g.index):
                landed = fut.group()
            if residency is not None:
                residency.put(
                    g.key, landed, plan.group_bytes(g, fetch=False)
                )
            parts.append(landed)
        return parts

    def apply(i, carry, group):
        g = prog[i]
        if residency is not None:
            residency.put(
                g.key, plan.cache_home_tree(g, group),
                plan.group_bytes(g, fetch=False),
            )
        if i == 0:
            box["x"], box["angles"] = embed_dec(group, box["batch"], box["pos"])
            box["new_slices"] = []
            return box["x"]
        if i == head_pos:
            box["logits"] = head_dec(group, box["x"])
            return box["logits"]
        u = plan.units[i - 1]
        if u.kind == "moe":
            x_attn, h2, top_w, top_i, sl = ne_dec(
                group, box["slices"][i - 1], box["x"], box["angles"], box["pos"]
            )
            if route_experts:
                ids = np.unique(np.asarray(jax.device_get(top_i))).astype(
                    np.int32
                )
            else:
                ids = np.arange(cfg.n_experts, dtype=np.int32)
            eg = plan.experts_for_layer(u.lo)
            parts = _fetch_experts(box["home"], [eg[e] for e in ids])
            box["x"] = moe_apply(
                tuple(parts), jnp.asarray(ids), top_w, top_i, x_attn, h2
            )
            box["new_slices"].append(sl)
            return box["x"]
        box["x"], sl = group_dec(
            group, box["slices"][i - 1], box["x"], box["angles"], box["pos"]
        )
        box["new_slices"].append(sl)
        return box["x"]

    ex = HostStreamExecutor(apply, indexed=True, engine=engine)

    def decode(home, caches, batch, pos):
        box.clear()
        box["batch"] = batch
        box["pos"] = pos
        box["home"] = home
        box["slices"] = split(caches)
        if residency is not None:
            groups = [
                (lambda g=g: plan.fetch_group(home, g, residency)) for g in prog
            ]
        else:
            groups = [plan.fetch_group(home, g) for g in prog]
        ex.run(
            jnp.zeros(()), groups, mode=mode,
            prefetch=pf, stats=stats, group_shardings=sh_prog,
            group_keys=[g.key for g in prog],
        )
        logits, new_caches = box["logits"], concat0(tuple(box["new_slices"]))
        # a serving session calls this every step: dropping the old/new
        # slice views here keeps cross-step cache residency at ONE dense
        # cache, not three, while the pager prefetches the next cold set
        box.clear()
        return logits, new_caches

    if paged:
        def paged_decode(home, view, batch, pos):
            return decode(home, assemble(view), batch, pos)

        paged_decode.close = ex.close  # type: ignore[attr-defined]
        paged_decode.dense = decode  # type: ignore[attr-defined]
        paged_decode.residency = residency  # type: ignore[attr-defined]
        paged_decode.expert_stats = expert_stats  # type: ignore[attr-defined]
        return paged_decode
    decode.close = ex.close  # type: ignore[attr-defined]
    decode.residency = residency  # type: ignore[attr-defined]
    decode.expert_stats = expert_stats  # type: ignore[attr-defined]
    return decode


def make_prefill_step(
    cfg: ModelConfig, batch_size: int, seq_len: int, mesh=None, sharder=None
) -> Callable[[Pytree, Pytree], tuple[jax.Array, Pytree]]:
    """``(params, batch) -> (last-token logits, caches)``.

    Caches are created inside the step (zeros) so the step's out-shardings
    place them; context length is the shape's ``seq_len``.

    ``last_pos`` (optional traced int32 scalar): the last *real* prompt
    position when the batch is right-padded into a length bucket — the
    serve path's bounded-compile prefill returns that position's logits
    instead of the pad tail's.
    """

    def prefill_step(params, batch, last_pos=None):
        caches = transformer.init_caches(cfg, batch_size, seq_len, cfg.compute_dtype)
        return transformer.prefill(
            cfg, params, batch, caches, mesh, sharder, last_pos=last_pos
        )

    return prefill_step


def make_decode_step(
    cfg: ModelConfig, mesh=None, sharder=None
) -> Callable[[Pytree, Pytree, Pytree, jax.Array], tuple[jax.Array, Pytree]]:
    """``(params, caches, batch, pos) -> (logits, caches)`` — one new token
    against a populated decode state (KV cache / recurrent state)."""

    def decode_step(params, caches, batch, pos):
        return transformer.decode_step(cfg, params, batch, caches, pos, sharder)

    return decode_step


def make_paged_decode_step(
    cfg: ModelConfig, mesh=None, sharder=None, *, donate_cache: bool = True
) -> Callable[[Pytree, Any, Pytree, jax.Array], tuple[jax.Array, Pytree]]:
    """``(params, view, batch, pos) -> (logits, caches)`` over a paged KV
    cache (see :mod:`repro.core.kvpager`).

    ``view`` is the pager's per-slot tuple of page pytrees; ``pos`` is the
    (B,) vector of per-slot context positions.  Assembly (pure page
    concatenation) is a *separate* jit from the decode executable, so the
    paged step runs the exact same decode program as
    :func:`make_decode_step` on the exact same cache values — paged and
    unpaged decode are bitwise-equal by construction.  The assembled dense
    view is donated into the step (``donate_cache``): it is a per-step
    transient, never the pager's retained hot pages (concatenation always
    produces a fresh buffer).
    """
    from repro.core import kvpager

    decode_fn = jax.jit(
        make_decode_step(cfg, mesh, sharder),
        donate_argnums=(1,) if donate_cache else (),
    )
    assemble = jax.jit(kvpager.assemble_view)

    def paged_decode_step(params, view, batch, pos):
        return decode_fn(params, assemble(view), batch, pos)

    paged_decode_step.decode_fn = decode_fn  # type: ignore[attr-defined]
    paged_decode_step.assemble = assemble  # type: ignore[attr-defined]
    return paged_decode_step


def init_train_state(
    key: jax.Array, cfg: ModelConfig
) -> tuple[Pytree, Pytree]:
    """(bf16 params, AdamW state with f32 master) for a fresh run."""
    params_f32 = transformer.init_model(key, cfg)
    opt_state = adamw_init(params_f32)
    params = jax.tree.map(lambda p: p.astype(cfg.compute_dtype), params_f32)
    return params, opt_state


def abstract_train_state(cfg: ModelConfig) -> tuple[Pytree, Pytree]:
    """ShapeDtypeStruct pytrees of (params, opt_state) — no allocation."""
    def build():
        return init_train_state(jax.random.PRNGKey(0), cfg)

    return jax.eval_shape(build)


def abstract_params(cfg: ModelConfig) -> Pytree:
    def build():
        p = transformer.init_model(jax.random.PRNGKey(0), cfg)
        return jax.tree.map(lambda x: x.astype(cfg.compute_dtype), p)

    return jax.eval_shape(build)


def abstract_caches(cfg: ModelConfig, batch: int, seq_len: int) -> Pytree:
    return jax.eval_shape(
        lambda: transformer.init_caches(cfg, batch, seq_len, cfg.compute_dtype)
    )
