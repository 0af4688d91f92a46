#!/usr/bin/env python3
"""Bring-up smoke run of the main path on TPU, at smollm-360m's published
widths and full depth (32 layers, d=960, vocab 49152), random weights from
a seed.

It drives the code the command-line tools use -- ``build_trainer`` from
``repro.launch.train`` and ``ServeSession`` from ``repro.launch.serve`` --
in this one process, one phase after another:

  a. device-resident train, 4 steps, batch 8, seq 1024 (the reference);
  b. train with the weights and their AdamW moments homed in pinned host
     memory and streamed under a device budget below the weights' size,
     checkpointed at step 2 and resumed to step 4 through the restore path;
  c. the same with the homes in the disk spill store, 2 steps;
  d. paged serving with weights and KV pages homed in pinned host memory
     and streamed: 8 requests of 512 prompt tokens and 32 generated tokens
     through 4 slots, against the same requests served device-resident.

The streamed phases run first and the device-resident ones last, so each
phase's ``peak_bytes_in_use`` (a process high-water mark) is its own
wherever it raised the mark.  Each phase prints one line with its wall
time, device memory and transfer counters; the comparisons follow.

  python chip_smoke.py            # phases a-d on one chip
  python chip_smoke.py --chips 4  # streamed train on a 4-way model axis
                                  # against the same step on one device

With no TPU, or outside a checkout of this repository, it exits nonzero
before any phase and prints no result.  Otherwise the last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  Scratch
files (checkpoints, spill stores) live in ``.chip_smoke/`` beside this
script and are removed at the end.
"""
from __future__ import annotations

import argparse
import gc
import json
import logging
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
RUN_DIR = ROOT / ".chip_smoke"

ARCH = "smollm-360m"
SEED = 0
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 4
#: streamed weights are 724 MB in bf16 (1.45 GB in f32): 512 MB forces
#: the forward/backward passes to stream them group by group
TRAIN_BUDGET_MB = 512.0
SERVE_SLOTS, SERVE_REQUESTS = 4, 8
SERVE_PROMPT, SERVE_GEN, SERVE_PAGE = 512, 32, 128
#: shared by the KV hot window and the streamed weight window (724 MB)
SERVE_BUDGET_MB = 384.0

#: Streamed and device-resident train run different jitted programs (per-
#: group stages vs one scanned step), so their reductions round apart and
#: AdamW's second step amplifies that: on the CPU at the smoke config the
#: losses are equal for steps 0-1 and part by 3e-5 relative at steps 2-3.
#: 1e-3 leaves room for the chip's bf16 fusions while a skipped or stale
#: group update, which moves the loss by the step's own progress, does not
#: fit.  The gradient norm is reported, not gated.
LOSS_RTOL = 1e-3
#: Greedy tokens may part only at a near-tie: where the reference's logits
#: for the two tokens lie within one bf16 epsilon (2**-7) of the largest
#: logit's magnitude, the bf16 matmuls that produce them can order the two
#: either way.
TOKEN_TIE_RTOL = 2.0**-7


class SmokeFailure(RuntimeError):
    """A comparison outside its tolerance."""


def _log(name: str, **fields) -> None:
    print(f"phase {name}: {json.dumps(fields, default=float)}", flush=True)


def _device_memory(prev_peak: int) -> dict:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    live = sum(x.nbytes for x in jax.live_arrays())
    return {
        "peak_bytes_in_use": peak,
        "raised_peak": peak > prev_peak,
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "live_array_bytes": int(live),
    }


def _stream_report(stats) -> dict:
    if stats is None or not stats.n_groups:
        return {}
    h2d = stats.per_tier()["h2d"]
    out = {
        "groups": stats.n_groups,
        "h2d_requests": stats.h2d_requests,
        "h2d_bytes": stats.bytes_h2d,
        "req_per_fetched_device_group": h2d["requests_per_fetched_device_group"],
        "transfer_wait_s": stats.transfer_wait_s,
        "unique_group_fetches": stats.unique_group_fetches,
        "cache_hits": stats.cache_hits,
        "cache_misses": stats.cache_misses,
        "peak_inflight_bytes": stats.peak_inflight_bytes,
    }
    if stats.disk_requests:
        out.update(disk_requests=stats.disk_requests, disk_bytes=stats.bytes_disk)
    return out


class Phases:
    """Runs phases in order, each timed and reported on its own line."""

    def __init__(self) -> None:
        self.peak = 0

    def run(self, name: str, fn):
        t0 = time.perf_counter()
        result, report = fn()
        wall = time.perf_counter() - t0
        gc.collect()
        mem = _device_memory(self.peak)
        self.peak = max(self.peak, mem["peak_bytes_in_use"])
        _log(name, wall_s=wall, **mem, **report)
        return result


def train(cfg, mesh, *, steps, ckpt_dir, param_kind="device", budget_mb=None,
          spill_dir=None, checkpoint_every=0):
    """One ``build_trainer`` run; returns ({step: metrics}, report)."""
    import jax

    from repro.launch.train import build_trainer
    from repro.optim.adamw import AdamWConfig
    from repro.runtime.driver import DriverConfig

    driver = build_trainer(
        cfg,
        mesh,
        global_batch=TRAIN_BATCH,
        seq_len=TRAIN_SEQ,
        # one schedule for every run, so a resumed run continues it
        opt_cfg=AdamWConfig(peak_lr=3e-3, warmup_steps=1, total_steps=TRAIN_STEPS),
        driver_cfg=DriverConfig(
            total_steps=steps,
            checkpoint_every=checkpoint_every,
            checkpoint_dir=str(ckpt_dir),
            max_restarts=0,  # a compile error or OOM surfaces at once
        ),
        seed=SEED,
        param_kind=param_kind,
        device_budget_mb=budget_mb,
        spill_dir=None if spill_dir is None else str(spill_dir),
    )
    step_s: list = []
    inner = driver.step_fn

    def timed_step(state, batch):
        t0 = time.perf_counter()
        state, metrics = inner(state, batch)
        jax.block_until_ready(metrics)
        step_s.append(time.perf_counter() - t0)
        return state, metrics

    driver.step_fn = timed_step
    driver.run()
    history = {int(h["step"]): h for h in driver.history}
    report = {
        "steps": sorted(history),
        "loss": [history[s]["loss"] for s in sorted(history)],
        "step_s": step_s,
        **_stream_report(driver.stream_stats),
    }
    return (history, driver), report


def compare_train(name, got: dict, ref: dict, loss_rtol: float) -> dict:
    """Per-step losses within ``loss_rtol`` (0: bitwise) of the reference."""
    steps = sorted(got)
    worst = {"loss": 0.0, "grad_norm": 0.0}
    bitwise = True
    for s in steps:
        for key in worst:
            a, b = got[s][key], ref[s][key]
            bitwise &= a == b
            rel = abs(a - b) / max(abs(b), 1e-30)
            worst[key] = max(worst[key], rel)
            if key == "loss" and not rel <= loss_rtol:
                raise SmokeFailure(
                    f"{name}: step {s} loss {a!r} vs reference {b!r} "
                    f"(relative {rel:.3e} > {loss_rtol:.0e})"
                )
    return {
        "loss_rtol": loss_rtol,
        "steps": steps,
        "bitwise": bool(bitwise),
        "max_rel_loss": worst["loss"],
        "max_rel_grad_norm": worst["grad_norm"],
    }


def serve_session(cfg, mesh, prompts, **kw):
    """Serve ``prompts`` through a paged ``ServeSession``; returns the
    (n, SERVE_GEN) greedy tokens and the session (closed)."""
    import numpy as np

    from repro.launch.serve import ServeSession

    with ServeSession(
        cfg,
        mesh,
        slots=SERVE_SLOTS,
        max_len=SERVE_PROMPT + SERVE_GEN,
        page_len=SERVE_PAGE,
        seed=SEED,
        **kw,
    ) as session:
        rids = [session.submit(p, SERVE_GEN) for p in prompts]
        session.warmup()
        out = session.run()
    return np.stack([out[r] for r in rids]), session


def _logits(cfg, mesh, session, context):
    """The reference session's next-token logits after ``context``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.train import steps as st

    prefill = jax.jit(
        st.make_prefill_step(cfg, 1, session.max_len, mesh, session.sharder)
    )
    logits, _ = prefill(
        session.params, {"tokens": jnp.asarray(context[None], jnp.int32)}
    )
    return np.asarray(logits, np.float32).reshape(-1)


def compare_tokens(cfg, mesh, ref_session, prompts, got, ref) -> dict:
    """Tokens agree until each request's first divergence, which must be a
    near-tie in the reference's own logits."""
    import numpy as np

    parts = []
    for i, (g, r) in enumerate(zip(got, ref)):
        diff = np.flatnonzero(g != r)
        if not diff.size:
            continue
        j = int(diff[0])
        logits = _logits(cfg, mesh, ref_session, np.concatenate([prompts[i], r[:j]]))
        gap = float(logits[r[j]] - logits[g[j]])
        tol = TOKEN_TIE_RTOL * float(np.abs(logits).max())
        parts.append({"request": i, "position": j, "logit_gap": gap, "tie_tol": tol})
        if not abs(gap) <= tol:
            raise SmokeFailure(
                f"serve: request {i} parts from the reference at token {j} "
                f"({int(g[j])} vs {int(r[j])}) with a logit gap {gap:.4g} "
                f"> {tol:.4g}"
            )
    return {
        "requests": len(got),
        "bitwise": not parts,
        "bitwise_requests": len(got) - len(parts),
        "divergences": parts,
    }


def one_chip(cfg) -> None:
    """Phases a-d and their comparisons."""
    import jax
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.runtime.elastic import elastic_local_mesh

    train_mesh = elastic_local_mesh(model=1)
    serve_mesh = make_local_mesh()
    phases = Phases()
    ckpt_b = RUN_DIR / "ckpt_b"

    half = TRAIN_STEPS // 2
    hist_b1, _ = phases.run("b1 streamed train, checkpoint", lambda: train(
        cfg, train_mesh, steps=half, ckpt_dir=ckpt_b, param_kind="pinned_host",
        budget_mb=TRAIN_BUDGET_MB, checkpoint_every=half,
    ))
    hist_b2, _ = phases.run("b2 streamed train, resume", lambda: train(
        cfg, train_mesh, steps=TRAIN_STEPS, ckpt_dir=ckpt_b,
        param_kind="pinned_host", budget_mb=TRAIN_BUDGET_MB,
        checkpoint_every=half,
    ))
    if sorted(hist_b2) != list(range(half, TRAIN_STEPS)):
        raise SmokeFailure(f"resume ran steps {sorted(hist_b2)}, not {half}..")
    shutil.rmtree(ckpt_b, ignore_errors=True)
    hist_b = {**hist_b1, **hist_b2}

    hist_c, _ = phases.run("c disk-tier train", lambda: train(
        cfg, train_mesh, steps=half, ckpt_dir=RUN_DIR / "ckpt_c",
        param_kind="disk_host", budget_mb=TRAIN_BUDGET_MB,
        spill_dir=RUN_DIR / "spill_c",
    ))
    shutil.rmtree(RUN_DIR / "ckpt_c", ignore_errors=True)

    prompts = np.asarray(
        jax.random.randint(
            jax.random.PRNGKey(SEED + 1),
            (SERVE_REQUESTS, SERVE_PROMPT), 1, cfg.vocab_size,
        ),
        np.int32,
    )

    def streamed_serve():
        tokens, s = serve_session(
            cfg, serve_mesh, prompts, kv_kind="pinned_host",
            param_kind="pinned_host", device_budget_mb=SERVE_BUDGET_MB,
        )
        report = {
            "tokens": int(tokens.size),
            "decode_steps": s.n_steps,
            "kv": _stream_report(s.stats),
            "weights": _stream_report(s.param_stats),
            "kv_peak_resident_bytes": s.pager.peak_resident_bytes,
        }
        return tokens, report

    tok_d = phases.run("d streamed paged serve", streamed_serve)

    def reference_serve():
        tokens, s = serve_session(cfg, serve_mesh, prompts)
        return (tokens, s), {"tokens": int(tokens.size), "decode_steps": s.n_steps}

    tok_ref, ref_session = phases.run("d-ref device-resident paged serve", reference_serve)
    _log("compare d vs d-ref", **compare_tokens(
        cfg, serve_mesh, ref_session, prompts, tok_d, tok_ref
    ))
    del ref_session  # its device-resident weights would inflate a's peak

    hist_a, _ = phases.run("a device-resident train", lambda: train(
        cfg, train_mesh, steps=TRAIN_STEPS, ckpt_dir=RUN_DIR / "ckpt_a",
    ))

    _log("compare b vs a", **compare_train("streamed train", hist_b, hist_a, LOSS_RTOL))
    # the disk home runs the very programs of b on the very values
    _log("compare c vs b", **compare_train("disk-tier train", hist_c, hist_b, 0.0))


def four_chips(cfg) -> None:
    """Phase b's streamed train on a 4-way model axis against one device."""
    import jax

    from repro.core.engine import ShardedGroupLayout
    from repro.runtime.elastic import elastic_local_mesh

    devices = jax.devices()
    if len(devices) != 4:
        raise SmokeFailure(f"--chips 4 needs 4 devices, found {len(devices)}")
    one = jax.make_mesh(
        (1, 1), ("data", "model"), devices=devices[:1],
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    four = elastic_local_mesh(model=4)
    phases = Phases()
    kw = dict(steps=TRAIN_STEPS // 2, param_kind="pinned_host", budget_mb=TRAIN_BUDGET_MB)
    hist_1, _ = phases.run("b on 1 device", lambda: train(
        cfg, one, ckpt_dir=RUN_DIR / "ckpt_1", **kw
    ))
    hist_4, driver = phases.run("b on a 4-way model axis", lambda: train(
        cfg, four, ckpt_dir=RUN_DIR / "ckpt_4", **kw
    ))
    shutil.rmtree(RUN_DIR, ignore_errors=True)

    h2d = driver.stream_stats.per_tier()["h2d"]
    if h2d["requests_per_fetched_device_group"] != 1.0:
        raise SmokeFailure(f"H2D requests per fetched (device, group): {h2d}")
    # the engine's layout cache holds one plan per group structure
    layouts = list(driver.engine._layouts.values())
    sharded = [lo for lo in layouts if isinstance(lo, ShardedGroupLayout)]
    want = sorted(d.id for d in devices)
    placement = {
        "layouts": len(layouts),
        "sharded_layouts": len(sharded),
        "unplaced_leaves": sum(lo.n_unplaced for lo in sharded),
        "devices_per_layout": sorted({len(lo.devices) for lo in sharded}),
    }
    if not sharded or len(sharded) != len(layouts):
        raise SmokeFailure(f"groups not staged per device: {placement}")
    if placement["unplaced_leaves"]:
        raise SmokeFailure(f"leaves fell back to device 0: {placement}")
    if any(sorted(d.id for d in lo.devices) != want for lo in sharded):
        raise SmokeFailure(f"a group's shards miss a device: {placement}")
    _log("placement on 4 devices", requests_per_fetched_device_group=1.0, **placement)
    _log("compare 4-way vs 1 device",
         **compare_train("4-way train", hist_4, hist_1, LOSS_RTOL))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.configs import get_config
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: not inside a checkout of this repository: {e}",
              file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r})", file=sys.stderr)
        return 3
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if args.chips != len(devices):
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 4

    logging.basicConfig(level=logging.INFO, format="%(levelname)s %(message)s")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {json.dumps(device)}", flush=True)
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    RUN_DIR.mkdir()
    try:
        cfg = get_config(ARCH)
        (four_chips if args.chips == 4 else one_chip)(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(RUN_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
