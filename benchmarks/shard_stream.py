"""Sharding-aware coalesced streaming vs the per-leaf fallback (A/B).

The paper's §5.1 result is that the offload penalty scales with *request
count*; PR 1's coalescer collapsed it to 1 request/group — but only for
default placement.  Any ``--model-parallel`` run passed explicit
``device_shardings`` and silently fell back to the seed's
one-``device_put``-per-leaf schedule.  This suite pins the recovered
collapse on a forced 2-device host mesh under the modeled Epiphany-class
link:

``per_leaf``
    ``EngineConfig(coalesce=False)`` with explicit shardings — the old
    fallback: one request per (host leaf, addressable shard).
``sharded``
    the default engine with the same shardings — ONE coalesced H2D
    request per (addressable device, group), leaves assembled with
    ``jax.make_array_from_single_device_arrays``.

Gates (the ISSUE 4 acceptance): sharded streaming costs exactly 1 request
per (device, group); the per-leaf fallback costs one per (leaf, shard);
steady-state transfer wait is >= 2x lower; all schedules bitwise-equal to
eager sharded placement.  Emits ``results/bench/BENCH_shard.json``.

On the CPU the forced device count must precede JAX init, so ``main()``
re-execs itself in a child process with ``XLA_FLAGS`` set (the aggregator
runs suites in-process with JAX already initialised).  On any other
backend the parent holds the chip, so the suite runs in-process over
``jax.devices()``.
"""
from __future__ import annotations

import os
import subprocess
import sys

_CHILD_FLAG = "REPRO_SHARD_BENCH_CHILD"

#: weight parts per group — the per-leaf schedule costs
#: (1 + N_W_PARTS) x n_devices requests/group, the engine n_devices
N_W_PARTS = 12
N_GROUPS = 16
N_DEVICES = 2


def _run(n_devices: int) -> list[dict]:
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks import common as C
    from repro.core.engine import EngineConfig, PAPER_EPIPHANY_LINK
    from repro.core.hoststream import HostStreamExecutor, StreamStats
    from repro.core.refspec import AUTO, PrefetchSpec

    smoke = bool(os.environ.get("REPRO_BENCH_SMOKE"))
    repeats = 3 if smoke else 5
    devs = jax.devices()
    assert len(devs) == n_devices, devs
    mesh = jax.make_mesh(
        (1, n_devices), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )

    # small leaves: the link's per-request cost (the term coalescing
    # collapses) dominates the serial bandwidth term
    rng = np.random.default_rng(0)
    groups = [
        {
            "x": rng.standard_normal((8, 48)).astype(np.float32),
            "w": tuple(
                rng.standard_normal((48, 32)).astype(np.float32)
                for _ in range(N_W_PARTS)
            ),
        }
        for _ in range(N_GROUPS)
    ]
    shardings = {
        "x": NamedSharding(mesh, P()),
        "w": tuple(
            NamedSharding(mesh, P(None, "model")) for _ in range(N_W_PARTS)
        ),
    }

    @jax.jit
    def apply(carry, g):
        w = jnp.concatenate(g["w"], axis=1)
        return carry + g["x"] @ w

    carry0 = jnp.zeros((8, 32 * N_W_PARTS), jnp.float32)
    spec = PrefetchSpec(buffer_size=N_GROUPS + 2, distance=AUTO)

    # bitwise reference: eager sharded placement, same apply
    eager_groups = [jax.device_put(g, shardings) for g in groups]
    with HostStreamExecutor(apply, device_shardings=shardings) as ex:
        ref, _ = ex.run(carry0, eager_groups, mode="eager")
    ref = np.asarray(ref)

    configs = {
        "per_leaf": EngineConfig(coalesce=False, link=PAPER_EPIPHANY_LINK),
        "sharded": EngineConfig(link=PAPER_EPIPHANY_LINK),
    }
    rows = []
    for name, cfg in configs.items():
        ex = HostStreamExecutor(apply, device_shardings=shardings, engine_config=cfg)
        st = StreamStats()
        t = C.timed(
            lambda: ex.run(carry0, groups, mode="prefetch", prefetch=spec, stats=st)[0],
            stats=st,
            repeats=repeats,
        )
        out, _ = ex.run(carry0, groups, mode="prefetch", prefetch=spec)
        np.testing.assert_array_equal(np.asarray(out), ref)  # bitwise, any schedule
        ex.close()
        waits = list(st.wait_per_group)
        steady = waits[len(waits) // 2 :]
        per = max(st.n_runs, 1)
        rows.append(
            {
                "config": name,
                "n_devices": st.n_devices,
                "n_leaves": 1 + N_W_PARTS,
                "requests_per_group": st.requests_per_group,
                "requests_per_device_group": st.per_tier()["h2d"][
                    "requests_per_device_group"
                ],
                "h2d_requests": st.h2d_requests // per,
                "bytes_h2d": st.bytes_h2d // per,
                "transfer_wait_s": st.transfer_wait_s / per,
                "steady_wait_per_group_s": float(np.median(steady)),
                "total_s": t["median_s"],
                "total_min_s": t["min_s"],
                "final_distance": st.distance_trace[-1] if st.distance_trace else None,
                "bitwise_equal_to_eager_sharded": True,
            }
        )

    by = {r["config"]: r for r in rows}
    by["sharded"]["wait_collapse_vs_per_leaf"] = (
        by["per_leaf"]["steady_wait_per_group_s"]
        / max(by["sharded"]["steady_wait_per_group_s"], 1e-9)
    )
    by["per_leaf"]["wait_collapse_vs_per_leaf"] = 1.0
    C.print_table(
        f"sharded coalesced streaming vs per-leaf fallback "
        f"({n_devices}-device mesh, paper link)",
        rows,
        ["config", "n_devices", "requests_per_group", "requests_per_device_group",
         "steady_wait_per_group_s", "transfer_wait_s", "total_s",
         "wait_collapse_vs_per_leaf"],
    )
    C.save_rows("BENCH_shard", rows)
    return rows


def _gate(rows: list[dict]) -> int:
    by = {r["config"]: r for r in rows}
    eng, leaf = by["sharded"], by["per_leaf"]
    one_per_device = eng["requests_per_device_group"] == 1.0
    storm = leaf["requests_per_group"] == (1 + N_W_PARTS) * eng["n_devices"]
    collapse = eng["wait_collapse_vs_per_leaf"]
    print(
        f"requests/group: sharded {eng['requests_per_group']:.0f} "
        f"(= n_devices) vs per-leaf {leaf['requests_per_group']:.0f} "
        f"(= n_leaves x n_shards); steady-wait collapse {collapse:.1f}x "
        f"(gate: >= 2x)"
    )
    return 0 if one_per_device and storm and collapse >= 2.0 else 1


def main() -> int:
    if os.environ.get(_CHILD_FLAG):
        return _gate(_run(N_DEVICES))
    import jax

    if jax.default_backend() != "cpu":
        # this process now holds the chip: a child could not get it
        return _gate(_run(len(jax.devices())))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={N_DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env[_CHILD_FLAG] = "1"
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run([sys.executable, "-m", "benchmarks.shard_stream"], env=env)
    return proc.returncode


if __name__ == "__main__":
    raise SystemExit(main())
