"""Elastic re-meshing: resume a job on a different device count.

Checkpoints store unsharded leaves (see ``repro.checkpoint``), so the
*device* side of elasticity reduces to choosing a new mesh and re-deriving
shardings from the same logical rules.  Policy: keep the model axis (TP
degree is an architectural choice — it must divide heads/ffn), shrink/grow
the data axis; drop the pod axis when only one pod survives.

The *host/disk* side does not reduce so neatly: the streamed trainer homes
params and moments as **layer-group chunks** (checkpoint leaves named
``params__groups__g001_layers_000_002__...``, spill-store chunks keyed
``wp/<group>`` / ``wopt/<group>``), and a re-mesh that re-derives the
device budget — or an operator that changes ``--param-layers-per-group`` —
changes the partition itself.  :func:`reshard_grouped_checkpoint` migrates
a grouped checkpoint between partitions **by streaming**: old leaves are
memory-mapped, sliced/concatenated per *new* layer group, and written
through :meth:`CheckpointManager.save_streamed` — peak memory is one new
group's largest leaf, never the full tree.  Spill chunks re-partition for
free on the next step (restore hands plain arrays; the streamed step
re-spills group-wise under the new plan); :func:`prune_stale_spill` drops
the dead chunks of the old grouping from durable stores.

The driver's restart loop is wired through :func:`check_restart_mesh`: on
every restart it re-derives the elastic mesh shape for the *live* device
count and raises :class:`RemeshRequired` when the count changed — compiled
programs and layouts cannot be rebuilt in-process, so the recovery path is
a relaunch, which re-runs the reshard-on-resume check above (the forced
2↔1-device subprocess tests exercise exactly this path).
"""
from __future__ import annotations

import logging
import re
from typing import Any, Optional

log = logging.getLogger("repro.elastic")

Pytree = Any

#: checkpoint leaf-name separator (matches repro.checkpoint.manager._SEP)
_SEP = "__"

_GROUP_KEY_RE = re.compile(
    r"g(\d{3,})_(embed|head|"
    r"(layers|expert|period|block)_(\d{3,})_(\d{3,})(?:_e(\d{2,}))?)"
)


def elastic_mesh_shape(
    n_devices: int,
    *,
    model: int = 16,
    prefer_pods: bool = True,
    pod_size: Optional[int] = None,
) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """Largest (pod, data, model) shape that fits ``n_devices``.

    >>> elastic_mesh_shape(512, model=16)      # healthy 2-pod job
    ((2, 16, 16), ('pod', 'data', 'model'))
    >>> elastic_mesh_shape(480, model=16)      # lost 2 hosts (8 chips each)
    ((30, 16), ('data', 'model'))
    >>> elastic_mesh_shape(256, model=16)
    ((16, 16), ('data', 'model'))
    """
    if model < 1:
        raise ValueError(f"model axis must be >= 1, got {model}")
    if n_devices < model:
        raise ValueError(
            f"{n_devices} devices cannot host a model axis of {model}"
        )
    if n_devices % model != 0:
        raise ValueError(f"{n_devices} devices not divisible by model={model}")
    rest = n_devices // model
    if prefer_pods and pod_size:
        chips_per_pod = pod_size
        # a pod must hold whole model groups, or the (pod, data, model)
        # product silently loses devices (pod_size=24, model=16 used to
        # yield a 32-device mesh for 48 devices)
        if (
            chips_per_pod % model == 0
            and chips_per_pod >= model
            and n_devices % chips_per_pod == 0
            and n_devices // chips_per_pod > 1
        ):
            pods = n_devices // chips_per_pod
            data = chips_per_pod // model
            return (pods, data, model), ("pod", "data", "model")
    if prefer_pods and rest % 16 == 0 and rest // 16 > 1:
        return (rest // 16, 16, model), ("pod", "data", "model")
    return (rest, model), ("data", "model")


# ---------------------------------------------------------------------------
# mesh identity + construction over the live device set
# ---------------------------------------------------------------------------


def mesh_fingerprint(mesh) -> dict:
    """JSON-serializable identity of a mesh (checkpoint/run metadata): a
    changed fingerprint on resume means shardings were re-derived and
    host/disk homes may need re-partitioning."""
    return {
        "n_devices": int(mesh.devices.size),
        "shape": [int(s) for s in mesh.devices.shape],
        "axes": [str(a) for a in mesh.axis_names],
    }


def elastic_local_mesh(model: int = 1):
    """Mesh over whatever devices exist *now*, via :func:`elastic_mesh_shape`.

    Unlike ``make_local_mesh`` (which asserts divisibility), the requested
    model axis degrades to the largest degree the surviving device count
    can host — the 2-device → 1-device resume keeps working instead of
    crashing on ``2 % 2 != 0``."""
    import jax
    from jax.sharding import AxisType

    n = len(jax.devices())
    m = max(1, min(model, n))
    while n % m:
        m -= 1
    if m != model:
        log.warning(
            "elastic mesh: model axis %d does not fit %d device(s); "
            "degraded to %d",
            model, n, m,
        )
    shape, axes = elastic_mesh_shape(n, model=m, prefer_pods=False)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


class RemeshRequired(RuntimeError):
    """The live device count no longer matches the mesh this process
    compiled for.  In-process restart cannot recover (programs and layouts
    are baked for the old mesh); relaunching re-derives everything — and
    the resume path re-partitions host/disk-homed state by streaming."""


def check_restart_mesh(expected: dict) -> None:
    """Called by the driver's restart loop: re-derive the elastic mesh for
    the live device count and raise :class:`RemeshRequired` if it changed
    since ``expected`` (a :func:`mesh_fingerprint`)."""
    import jax

    n = len(jax.devices())
    if n == expected.get("n_devices"):
        return
    model = 1
    axes = expected.get("axes") or []
    shape = expected.get("shape") or []
    if "model" in axes:
        model = int(shape[axes.index("model")])
    m = max(1, min(model, n))
    while n % m:
        m -= 1
    new_shape, new_axes = elastic_mesh_shape(n, model=m, prefer_pods=False)
    raise RemeshRequired(
        f"device count changed under a live job: compiled for "
        f"{expected.get('n_devices')} devices {tuple(shape)}, now {n}; "
        f"relaunch to re-mesh as {new_shape} {new_axes} — resume will "
        f"re-derive shardings and re-partition host/disk-homed state by "
        f"streaming"
    )


# ---------------------------------------------------------------------------
# streamed checkpoint re-partition (grouped weight-stream checkpoints)
# ---------------------------------------------------------------------------


def parse_group_key(key: str) -> Optional[dict]:
    """Parse a weight-stream group key (``g000_embed`` /
    ``g001_layers_000_002`` / ``g003_period_000_004`` /
    ``g002_block_002_003`` / ``g002_expert_001_002_e03`` / ``g004_head``)
    into its kind + layer bounds (+ expert index, -1 for non-expert kinds);
    None for names that are not group keys."""
    m = _GROUP_KEY_RE.fullmatch(key)
    if m is None:
        return None
    if m.group(2) == "embed":
        return {"key": key, "kind": "embed", "lo": 0, "hi": 0, "expert": -1}
    if m.group(2) == "head":
        return {"key": key, "kind": "head", "lo": 0, "hi": 0, "expert": -1}
    kind = m.group(3)
    expert = m.group(6)
    if (kind == "expert") != (expert is not None):
        return None  # the _eNN suffix is exactly the expert kinds' marker
    return {
        "key": key,
        "kind": kind,
        "lo": int(m.group(4)),
        "hi": int(m.group(5)),
        "expert": int(expert) if expert is not None else -1,
    }


def reshard_grouped_checkpoint(
    ckpt,
    plan,
    *,
    step: Optional[int] = None,
    extra_meta: Optional[dict] = None,
) -> bool:
    """Stream-repartition a grouped (weight-streamed) checkpoint into
    ``plan``'s grouping, in place, at the same step.

    The old partition is recovered from the stored leaf *names*
    (``{params|opt}__groups__<gkey>__<subpath>``), so checkpoints written
    before run metadata existed reshard too.  For each **new** layer group
    ``[lo, hi)``, every overlapping old group's stacked leaves are loaded
    memory-mapped, sliced along axis 0, and concatenated — one output leaf
    in memory at a time; embed/head and non-group leaves (``opt__step``)
    pass through byte-identical under their (possibly renumbered) new
    keys.  Values are never transformed, only re-partitioned, which is why
    the resumed loss series stays bitwise-equal.

    Returns True when a reshard was performed; False when there is nothing
    to do (no checkpoint, not grouped, or the partition already matches).
    """
    import numpy as np

    if step is None:
        step = ckpt.latest_step()
    if step is None:
        return False
    meta = ckpt.load_meta(step)
    names = [leaf["name"] for leaf in meta["leaves"]]
    dtypes = {leaf["name"]: leaf["dtype"] for leaf in meta["leaves"]}

    # recover the old partition from leaf names
    old_groups: dict[str, dict] = {}
    subs: dict[tuple[str, str], list[str]] = {}
    passthrough: list[str] = []
    for name in names:
        parts = name.split(_SEP)
        g = (
            parse_group_key(parts[2])
            if len(parts) >= 4 and parts[0] in ("params", "opt") and parts[1] == "groups"
            else None
        )
        if g is None:
            passthrough.append(name)
            continue
        old_groups[parts[2]] = g
        subs.setdefault((parts[0], parts[2]), []).append(_SEP.join(parts[3:]))
    if not old_groups:
        return False  # not a grouped checkpoint
    new_keys = {g.key for g in plan.groups}
    if set(old_groups) == new_keys:
        return False  # same partition — nothing to re-shard

    mid_kinds_old = frozenset(
        g["kind"] for g in old_groups.values() if g["kind"] not in ("embed", "head")
    )
    mid_kinds_new = frozenset(
        g.kind for g in plan.groups if g.kind not in ("embed", "head")
    )
    if mid_kinds_old != mid_kinds_new:
        raise ValueError(
            f"checkpoint step {step} was written with a "
            f"{sorted(mid_kinds_old)} group program but the plan builds "
            f"{sorted(mid_kinds_new)} — kind-family changes "
            "(e.g. toggling --expert-stream) cannot be streamed between "
            "partitions; resume with the original flags, or export the "
            "params and re-import under the new program"
        )
    if "expert" in mid_kinds_old:
        # expert programs force layers_per_group=1, so their keys are a
        # function of the config alone — differing key sets mean the model
        # (n_experts / n_layers) changed, which no reshard can bridge
        raise ValueError(
            f"checkpoint step {step} and the plan both use expert-split "
            "groups but their group keys differ — the MoE shape changed; "
            "re-grouping cannot change the model"
        )
    #: stacked middle kinds reslice along axis 0 ("period" in period-unit
    #: coordinates); named "block" groups redistribute whole block subtrees
    stacked_kind = (
        "layers" if "layers" in mid_kinds_old
        else ("period" if "period" in mid_kinds_old else None)
    )
    old_stacked = sorted(
        (g for g in old_groups.values() if g["kind"] == stacked_kind),
        key=lambda g: g["lo"],
    )
    scale = plan.scan_period if stacked_kind == "period" else 1
    old_blocks = {k: g for k, g in old_groups.items() if g["kind"] == "block"}
    old_embed = next(
        (k for k, g in old_groups.items() if g["kind"] == "embed"), None
    )
    old_head = next(
        (k for k, g in old_groups.items() if g["kind"] == "head"), None
    )
    span = max(
        (
            g["hi"]
            for g in old_groups.values()
            if g["kind"] not in ("embed", "head")
        ),
        default=0,
    )
    if span != plan.n_layers:
        raise ValueError(
            f"checkpoint step {step} covers {span} layers but the plan has "
            f"{plan.n_layers} — re-grouping cannot change the model"
        )

    new_embed = plan.groups[0].key
    new_head = plan.groups[-1].key

    def _load(name: str):
        return ckpt.load_leaf(step, name, dtype=dtypes.get(name), mmap=True)

    def leaves():
        # `tops` iterates the state roots that home grouped leaves: a
        # params-only checkpoint (serve export) has no ("opt", gkey) subs
        for top in ("params", "opt"):
            for old_key, new_key in ((old_embed, new_embed), (old_head, new_head)):
                for sub in subs.get((top, old_key), []):
                    yield (
                        _SEP.join((top, "groups", new_key, sub)),
                        _load(_SEP.join((top, "groups", old_key, sub))),
                    )
            if old_stacked:
                layer_subs = subs.get((top, old_stacked[0]["key"]), [])
                for ng in plan.groups:
                    if ng.kind != stacked_kind:
                        continue
                    for sub in layer_subs:
                        parts = []
                        for og in old_stacked:
                            lo, hi = max(ng.lo, og["lo"]), min(ng.hi, og["hi"])
                            if lo >= hi:
                                continue
                            arr = _load(
                                _SEP.join((top, "groups", og["key"], sub))
                            )
                            parts.append(
                                arr[
                                    (lo - og["lo"]) // scale
                                    : (hi - og["lo"]) // scale
                                ]
                            )
                        out = (
                            np.ascontiguousarray(parts[0])
                            if len(parts) == 1
                            else np.concatenate(
                                [np.asarray(p) for p in parts], axis=0
                            )
                        )
                        yield _SEP.join((top, "groups", ng.key, sub)), out
            if old_blocks:
                # each sub is "<block_name>__<rest>": whole named blocks
                # move to whichever new group homes that block name
                sub_home = {}
                for old_key in old_blocks:
                    for sub in subs.get((top, old_key), []):
                        sub_home[sub] = old_key
                for ng in plan.groups:
                    if ng.kind != "block":
                        continue
                    names = set(plan.block_names(ng))
                    for sub, old_key in sub_home.items():
                        if sub.split(_SEP)[0] not in names:
                            continue
                        yield (
                            _SEP.join((top, "groups", ng.key, sub)),
                            _load(_SEP.join((top, "groups", old_key, sub))),
                        )
        for name in passthrough:
            yield name, _load(name)

    log.info(
        "re-sharding checkpoint step %d: %d old groups -> %d new groups "
        "(layers_per_group=%d), one group leaf at a time",
        step, len(old_groups), plan.n_groups, plan.layers_per_group,
    )
    ckpt.save_streamed(
        step,
        leaves(),
        extra_meta=extra_meta,
        treedef=meta.get("treedef", "resharded"),
    )
    return True


def ensure_plan_matches_checkpoint(
    checkpoint_dir,
    plan,
    *,
    mesh=None,
    run_meta: Optional[dict] = None,
) -> bool:
    """Launcher-side resume check: if the latest checkpoint's weight
    grouping differs from ``plan``'s (an elastic re-mesh re-derived the
    budget, or the operator changed the group size), stream-repartition it
    in place before the driver restores.  Logs the mesh change (shardings
    re-derive from the new mesh on their own — checkpoint leaves are
    unsharded).  Returns True when a reshard was performed."""
    from pathlib import Path

    from repro.checkpoint.manager import CheckpointManager

    if not Path(checkpoint_dir).exists():
        return False
    ckpt = CheckpointManager(checkpoint_dir, keep=0)  # keep=0: never prunes
    step = ckpt.latest_step()
    if step is None:
        return False
    saved = ckpt.load_meta(step).get("extra") or {}
    if (
        mesh is not None
        and saved.get("mesh")
        and saved["mesh"] != mesh_fingerprint(mesh)
    ):
        log.warning(
            "elastic re-mesh: checkpoint step %d was written on mesh %s, "
            "resuming on %s — shardings re-derive from the new mesh; "
            "host/disk-homed groups re-partition below if the grouping "
            "changed",
            step, saved["mesh"], mesh_fingerprint(mesh),
        )
    return reshard_grouped_checkpoint(ckpt, plan, step=step, extra_meta=run_meta)


def prune_stale_spill(store, plan) -> int:
    """Drop spill chunks keyed by a *previous* grouping (``wp/``/``wopt/``
    keys not in ``plan``) from a durable store, so re-meshes do not
    accumulate dead chunk files.  Returns the number removed."""
    valid = {plan.spill_key(g) for g in plan.groups}
    valid |= {f"wopt/{g.key}" for g in plan.groups}
    stale = [
        k
        for k in list(store.keys())
        if (k.startswith("wp/") or k.startswith("wopt/")) and k not in valid
    ]
    for k in stale:
        store.delete(k)
    if stale:
        log.info(
            "pruned %d stale spill chunk(s) left by a previous grouping",
            len(stale),
        )
    return len(stale)
