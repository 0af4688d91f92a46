"""End-to-end training launcher.

Wires every layer of the framework together: config -> mesh -> sharding plan
-> jitted train step -> prefetching loader -> fault-tolerant driver with
checkpointing.  Runs real training on whatever devices exist (CPU smoke
configs here; the same code path jits for pods), e.g.::

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \\
      --steps 50 --batch 8 --seq 64 --checkpoint-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data.loader import PrefetchLoader
from repro.data.synthetic import SyntheticConfig, synthetic_batch
from repro.optim.adamw import AdamWConfig
from repro.parallel import sharding as sh
from repro.runtime.driver import DriverConfig, TrainDriver
from repro.train import steps as st


def _shardings(mesh, specs):
    return sh.named_shardings(mesh, specs)


def build_trainer(
    cfg,
    mesh,
    *,
    global_batch: int,
    seq_len: int,
    opt_cfg: AdamWConfig,
    driver_cfg: DriverConfig,
    seed: int = 0,
    fail_at=None,
    prefetch_distance: int = 2,
    policy=None,
    stream_opt: bool = False,
    opt_stream_groups: int = 4,
    spill_dir=None,
    host_budget_mb=None,
    param_kind: str = "device",
    device_budget_mb=None,
    param_layers_per_group=None,
    expert_stream: bool = False,
    transfer_retries: int = 1,
    verify_schedule: bool = False,
):
    """Assemble (driver, jitted step) for a config on a mesh.

    ``policy`` (repro.core.memkind.PlacementPolicy) chooses the memory kind
    of each state group — the paper's one-line placement change.  With
    ``HOST_OPT`` the AdamW state lives at the pinned-host kind between
    steps; the runtime streams it to the device for the update and back
    (on the CPU, which cannot execute host placements, the kinds fall back
    to device with identical program topology; other backends raise, see
    memkind docs).

    ``stream_opt`` upgrades a host-kind optimizer policy from bulk
    step-boundary copies to the transfer-engine streamed update: moments
    live on the host as numpy groups and stream through
    ``repro.core.engine.TransferEngine`` (coalesced, pipelined write-back,
    ``distance="auto"``) during the update itself.

    With a ``DISK_OPT`` policy (or an explicit ``spill_dir``), moment
    groups that do not fit ``host_budget_mb`` spill to a ``DiskHost``
    :class:`~repro.core.spillstore.SpillStore` and stream through the
    engine's two-stage disk->host->device pipeline — optimizer state
    larger than host RAM, same update values.

    ``param_kind`` (``--param-kind``) extends the hierarchy to the model
    **weights**: ``pinned_host``/``disk_host`` home the params (and their
    AdamW moments) off-device and stream them layer-group-wise through the
    engine for the forward pass, the reverse-order backward pass, and the
    optimizer update (see ``repro.core.weightstream``), with
    ``device_budget_mb`` bounding peak streamed device residency — models
    of arbitrarily large size under an explicit device budget.  This path
    subsumes ``--stream-opt`` (the moments ride the same groups).

    ``transfer_retries`` sets the engine's transient-fault budget
    (``EngineConfig.max_attempts``): H2D/D2H/disk-stage faults retry with
    exponential backoff before surfacing, re-fetching from the intact cold
    home — retried schedules stay bitwise-equal.

    Resuming a weight-streamed run is **elastic**: the launcher fingerprints
    the mesh into every checkpoint, and when the latest checkpoint's weight
    grouping no longer matches the (re-derived) plan it is re-partitioned
    in place by streaming (``repro.runtime.elastic``) before restore.
    """
    from repro.core import memkind as mk
    from repro.core import spillstore as st_mod
    from repro.core.engine import EngineConfig, TransferEngine
    from repro.core.hoststream import StreamStats
    from repro.core.refspec import PrefetchSpec
    from repro.core.spillstore import SpillStore

    policy = policy or mk.ALL_DEVICE
    plan = sh.make_plan(mesh, mode="train")
    params_abs, opt_abs = st.abstract_train_state(cfg)
    p_specs = sh.param_specs(plan, params_abs)
    o_specs = sh.opt_state_specs(plan, p_specs, params_abs)
    p_sh, o_sh = _shardings(mesh, p_specs), _shardings(mesh, o_specs)
    sharder = sh.make_sharder(
        plan, params_abs, global_batch, seq_len=seq_len, seq_shard=True
    )

    step_fn = st.make_train_step(cfg, opt_cfg, mesh, sharder)
    jitted = jax.jit(
        step_fn,
        in_shardings=(p_sh, o_sh, None),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1),
    )

    sc = SyntheticConfig(cfg.vocab_size, seq_len, global_batch, seed=seed)
    loader = PrefetchLoader(
        lambda step: synthetic_batch(cfg, sc, step), distance=prefetch_distance
    )

    def _opt_home(opt):
        """Place the optimizer state at its policy kind (host offload)."""
        if policy.opt_state.jax_kind == "device":
            return opt
        home = jax.tree.map(
            lambda s: mk.sharding_for(mesh, s.spec, policy.opt_state),
            o_sh,
            is_leaf=lambda s: isinstance(s, jax.sharding.NamedSharding),
        )
        return jax.device_put(opt, home)

    def init_state():
        params, opt = st.init_train_state(jax.random.PRNGKey(seed), cfg)
        with mesh:
            params = jax.device_put(params, p_sh)
            opt = jax.device_put(opt, o_sh)
            opt = _opt_home(opt)
        return {"params": params, "opt": opt}

    def wrapped_step(state, batch):
        with mesh:
            opt = jax.device_put(state["opt"], o_sh)  # stream in from home kind
            params, opt, metrics = jitted(state["params"], opt, batch)
            opt = _opt_home(opt)  # stream back (paper 'rw' write-back)
        return {"params": params, "opt": opt}, metrics

    log = logging.getLogger("repro.train")
    if expert_stream and param_kind == "device":
        raise ValueError(
            "--expert-stream streams routed experts from a weight home; "
            "it requires --param-kind pinned_host or disk_host"
        )
    if param_kind != "device":
        from repro.core.weightstream import (
            PARAM_KINDS,
            WeightStreamPlan,
            weight_stream_support,
        )

        if param_kind not in PARAM_KINDS:
            raise ValueError(
                f"unknown --param-kind {param_kind!r}; expected one of {PARAM_KINDS}"
            )
        support = weight_stream_support(cfg)
        if not support:
            raise ValueError(f"--param-kind {param_kind}: {support.reason}")
        if stream_opt:
            log.warning(
                "--stream-opt is subsumed by --param-kind %s: the AdamW "
                "moments are homed with the params and stream through the "
                "same groups",
                param_kind,
            )
        plan = WeightStreamPlan(
            cfg,
            st.abstract_params(cfg),
            layers_per_group=param_layers_per_group,
            device_budget_mb=device_budget_mb,
            expert_stream=expert_stream,
        )
        log.info(
            "weight streaming: %s program, %d groups (%d layers/group), "
            "total %.1f MB, peak(d=1) %.1f MB, max distance %d",
            plan.layout,
            plan.n_groups,
            plan.layers_per_group,
            plan.total_param_bytes / 1e6,
            plan.peak_device_bytes(1) / 1e6,
            plan.max_distance_for_budget(),
        )
        # weight-residency cache: the budget slack above the prefetch
        # window keeps recently fetched groups device-resident, so the
        # backward re-walk (and the next step's forward) hits instead of
        # re-fetching — window + cache still never exceed the budget
        from repro.core.residency import ResidencyCache

        residency = ResidencyCache(plan.residency_capacity_bytes())
        log.info(
            "weight residency cache: %s capacity",
            "unbounded"
            if residency.capacity_bytes is None
            else f"{residency.capacity_bytes / 1e6:.1f} MB",
        )
        if verify_schedule:
            # --verify-schedule: print the static analysis (the streamed
            # step re-runs it at construction and fails fast regardless)
            from repro.core import schedcheck

            report = schedcheck.analyze_train_schedule(
                plan,
                distance=plan.max_distance_for_budget(),
                cache_capacity=residency.capacity_bytes,
                spill=param_kind == "disk_host",
            )
            print(report)
            schedcheck.verify_schedule(report)
        engine = TransferEngine(
            EngineConfig(
                max_distance=plan.max_distance_for_budget(),
                max_attempts=transfer_retries,
            )
        )
        param_stats = StreamStats()
        param_store = None
        if param_kind == "disk_host":
            ephemeral = spill_dir is None
            if ephemeral:
                import tempfile

                spill_dir = tempfile.mkdtemp(prefix="repro-spill-wp-")
            param_store = SpillStore(spill_dir, ephemeral=ephemeral)

        from repro.runtime import elastic as el

        run_meta = {
            "mesh": el.mesh_fingerprint(mesh),
            "param_kind": param_kind,
            "weight_groups": plan.grouping(),
        }
        # elastic resume: if the latest checkpoint was written under a
        # different grouping (re-meshed budget, changed group size), stream-
        # repartition it in place before the driver restores
        resharded = el.ensure_plan_matches_checkpoint(
            driver_cfg.checkpoint_dir, plan, mesh=mesh, run_meta=run_meta
        )
        if resharded and param_store is not None:
            el.prune_stale_spill(param_store, plan)
        streamed = st.make_weight_streamed_train_step(
            cfg,
            opt_cfg,
            mesh,
            sharder,
            plan=plan,
            engine=engine,
            stats=param_stats,
            spill_store=param_store,
            # groups stage at the sharding plan's param specs under a mesh
            param_shardings=p_sh if mesh.devices.size > 1 else None,
            param_kind=param_kind,
            residency=residency,
        )

        def init_state_ws():
            state = st.init_weight_streamed_state(
                jax.random.PRNGKey(seed), cfg, plan
            )
            if param_store is not None:
                state = st.spill_weight_streamed_state(plan, state, param_store)
            return state

        def wrapped_step_ws(state, batch):
            if param_store is not None and not plan.is_spilled(state["params"]):
                # checkpoint restore hands back plain host arrays — the
                # disk home must be re-imposed or the weights sit in RAM
                state = st.spill_weight_streamed_state(plan, state, param_store)
            with mesh:
                return streamed(state, batch)

        def on_restart_ws(_n):
            # restart restores an older checkpoint (or re-inits), so cached
            # device copies no longer match the home — a failure *outside*
            # the step (checkpoint commit, watchdog) skips the step's own
            # failure clear, so the restart hook must drop them too
            residency.clear()
            # a kill mid-drain can leave D2H tickets pending; the restored
            # step must never drain them into its outputs, and the hazard
            # sanitizer would (correctly) flag the re-fetch of their groups
            engine.discard_writebacks()

        driver = TrainDriver(
            driver_cfg,
            wrapped_step_ws,
            loader,
            init_state_ws,
            fail_at=fail_at,
            engine=engine,
            stream_stats=param_stats,
            spill_store=param_store,
            run_meta=run_meta,
            on_restart=on_restart_ws,
        )
        return driver

    from repro.runtime import elastic as el

    run_meta = {"mesh": el.mesh_fingerprint(mesh), "param_kind": param_kind}

    if stream_opt and policy.opt_state.jax_kind == "device":
        log.warning(
            "--stream-opt ignored: policy %r keeps optimizer state on "
            "device; use --policy host_opt (or host_all) to stream it",
            policy.name,
        )
    if not policy.params.jax_addressable or not policy.kv_cache.jax_addressable:
        # this launcher only streams *optimizer state* from disk; disk-kind
        # params/kv resolve to their staging kind (host), which must not
        # pass silently for someone expecting larger-than-RAM weights
        log.warning(
            "policy %r places params/kv at the DiskHost tier, but the "
            "trainer has no disk-params streaming path: they fall back to "
            "the host staging kind (use @offload(...).stream_host(policy="
            "DISK_PARAMS) for disk-resident weights)",
            policy.name,
        )
    if not stream_opt and not policy.opt_state.jax_addressable:
        log.warning(
            "policy %r without --stream-opt never touches disk: the "
            "DiskHost kind resolves to its host staging kind for bulk "
            "step-boundary copies; pass --stream-opt to stream the "
            "moments through the spill store",
            policy.name,
        )
    if stream_opt and policy.opt_state.jax_kind != "device":
        # engine-streamed optimizer: moments stay host numpy between steps;
        # under a DISK_OPT policy (or a host policy with an explicit
        # spill_dir + budget) groups beyond the host-RAM budget live on
        # disk and stream disk->host->device
        engine = TransferEngine(EngineConfig(max_attempts=transfer_retries))
        stream_stats = StreamStats()
        spill_store = None
        use_spill = not policy.opt_state.jax_addressable or (
            spill_dir is not None and host_budget_mb is not None
        )
        if spill_dir is not None and host_budget_mb is None and not use_spill:
            log.warning(
                "--spill-dir ignored: policy %r is host-resident and no "
                "--host-budget-mb overflow threshold was given",
                policy.name,
            )
        if use_spill:
            ephemeral = spill_dir is None
            if ephemeral:
                import tempfile

                spill_dir = tempfile.mkdtemp(prefix="repro-spill-opt-")
            # a run-private temp store is ephemeral: no per-put durability
            # cost on the train hot path, deleted by the driver's close()
            spill_store = SpillStore(spill_dir, ephemeral=ephemeral)
        streamed = st.make_streamed_train_step(
            cfg,
            opt_cfg,
            mesh,
            sharder,
            n_groups=opt_stream_groups,
            prefetch=PrefetchSpec(
                buffer_size=opt_stream_groups + 1, distance="auto"
            ),
            engine=engine,
            stats=stream_stats,
            spill_store=spill_store,
            # moments stage at the plan's opt specs (sharded coalescing:
            # one H2D request per device per group under --model-parallel)
            state_shardings=o_sh["leaves"],
        )

        budget_bytes = int(host_budget_mb * 1e6) if host_budget_mb else 0

        def _spilled(opt):
            return st.spill_opt_state(
                opt,
                spill_store,
                n_groups=opt_stream_groups,
                host_budget_bytes=budget_bytes,
            )

        def init_state_streamed():
            params, _ = st.init_train_state(jax.random.PRNGKey(seed), cfg)
            with mesh:
                params = jax.device_put(params, p_sh)
            opt = st.host_opt_state(params)
            if spill_store is not None:
                opt = _spilled(opt)
            return {"params": params, "opt": opt}

        def wrapped_step_streamed(state, batch):
            if spill_store is not None and not any(
                st_mod.is_disk_leaf(x)
                for x in jax.tree.leaves(state["opt"]["leaves"])
            ):
                # checkpoint restore hands back plain host arrays — the
                # budget must be re-imposed or the whole state sits in RAM
                state = {**state, "opt": _spilled(state["opt"])}
            with mesh:
                return streamed(state, batch)

        driver = TrainDriver(
            driver_cfg,
            wrapped_step_streamed,
            loader,
            init_state_streamed,
            fail_at=fail_at,
            engine=engine,
            stream_stats=stream_stats,
            spill_store=spill_store,
            run_meta=run_meta,
        )
        return driver

    driver = TrainDriver(
        driver_cfg,
        wrapped_step,
        loader,
        init_state,
        fail_at=fail_at,
        run_meta=run_meta,
    )
    return driver


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--checkpoint-dir", default="checkpoints")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--policy",
        default="all_device",
        choices=[
            "all_device", "host_opt", "host_params", "host_all",
            "disk_opt", "disk_params",
        ],
        help="memory-kind placement policy (paper memory kinds; disk_* "
        "spill to the DiskHost tier)",
    )
    ap.add_argument(
        "--stream-opt",
        action="store_true",
        help="stream host-kind optimizer state through the transfer engine "
        "(coalesced + pipelined write-back + adaptive prefetch distance)",
    )
    ap.add_argument(
        "--spill-dir",
        default=None,
        help="directory for the DiskHost spill store (default: a temp dir "
        "when a disk policy is active)",
    )
    ap.add_argument(
        "--host-budget-mb",
        type=float,
        default=None,
        help="host-RAM budget for streamed optimizer state; moment groups "
        "beyond it spill to the DiskHost tier (0/unset with a disk "
        "policy: spill everything)",
    )
    from repro.core.weightstream import PARAM_KINDS

    ap.add_argument(
        "--param-kind",
        default="device",
        choices=PARAM_KINDS,
        help="home tier of the model weights: host/disk kinds stream the "
        "params (and their AdamW moments) layer-group-wise through the "
        "transfer engine for forward, reverse-order backward, and the "
        "optimizer update",
    )
    ap.add_argument(
        "--device-budget-mb",
        type=float,
        default=None,
        help="device-residency budget for streamed weights: picks the "
        "layer-group size and caps the prefetch window so streamed "
        "params never exceed it",
    )
    ap.add_argument(
        "--param-layers-per-group",
        type=int,
        default=None,
        help="layers per weight transfer group (default: largest count "
        "fitting --device-budget-mb, else n_layers/4)",
    )
    ap.add_argument(
        "--expert-stream",
        action="store_true",
        help="split MoE experts into per-expert fetch groups (train "
        "overlaps all-expert fetch with compute; requires a streamed "
        "--param-kind and an MoE arch)",
    )
    ap.add_argument(
        "--fail-at",
        default=None,
        help="comma-separated step numbers at which to inject one failure "
        "each (chaos testing: exercises restart + restore)",
    )
    ap.add_argument(
        "--max-restarts",
        type=int,
        default=3,
        help="restart budget; the budget resets after checkpoint-every "
        "consecutive healthy steps",
    )
    ap.add_argument(
        "--transfer-retries",
        type=int,
        default=3,
        help="transfer-engine attempt budget for transient H2D/D2H/disk "
        "faults (1 = fail fast, legacy behavior)",
    )
    ap.add_argument(
        "--history-out",
        default=None,
        help="write the per-step metric history as JSON to this path "
        "(chaos tests diff loss series across runs bitwise)",
    )
    ap.add_argument(
        "--verify-schedule",
        action="store_true",
        help="statically verify the streamed-weight schedule before "
        "running (print the per-phase occupancy/hazard analysis, fail "
        "fast on any violation; see repro.core.schedcheck)",
    )
    args = ap.parse_args()
    if args.verify_schedule and args.param_kind == "device":
        ap.error("--verify-schedule requires a streamed --param-kind "
                 "(pinned_host or disk_host)")

    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(message)s")
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    # elastic: degrade the model axis instead of asserting when the device
    # count changed since the job was first launched
    from repro.runtime.elastic import elastic_local_mesh

    mesh = elastic_local_mesh(model=args.model_parallel)
    opt_cfg = AdamWConfig(
        peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps
    )
    driver_cfg = DriverConfig(
        total_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        max_restarts=args.max_restarts,
    )
    fail_at = (
        {int(s) for s in args.fail_at.split(",") if s.strip()}
        if args.fail_at
        else None
    )
    from repro.core import memkind as mk

    driver = build_trainer(
        cfg,
        mesh,
        global_batch=args.batch,
        seq_len=args.seq,
        opt_cfg=opt_cfg,
        driver_cfg=driver_cfg,
        seed=args.seed,
        fail_at=fail_at,
        policy=mk.get_policy(args.policy),
        stream_opt=args.stream_opt,
        spill_dir=args.spill_dir,
        host_budget_mb=args.host_budget_mb,
        param_kind=args.param_kind,
        device_budget_mb=args.device_budget_mb,
        param_layers_per_group=args.param_layers_per_group,
        expert_stream=args.expert_stream,
        transfer_retries=args.transfer_retries,
        verify_schedule=args.verify_schedule,
    )
    t0 = time.time()
    driver.run()
    dt = time.time() - t0
    if args.history_out:
        import json

        with open(args.history_out, "w") as f:
            json.dump(driver.history, f)
    losses = [h["loss"] for h in driver.history if "loss" in h]
    span = f"loss {losses[0]:.3f} -> {losses[-1]:.3f}" if losses else "no new steps"
    print(
        f"trained {args.arch} ({'smoke' if args.smoke else 'full'}) "
        f"{len(driver.history)} steps in {dt:.1f}s; {span}"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
