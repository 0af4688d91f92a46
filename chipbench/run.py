#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is built from its files by the names
``BENCHMARK.json`` gives: ``chipbench/configs/<config>.json`` (which names
its architecture, ``chipbench/archs/<arch>.py``; ``dense`` by default),
``chipbench/traffic/<traffic>.json`` (which names its entry and its
placement, ``chipbench/placements/<name>.json``), one reader per metric in
``chipbench/metrics/<metric>.py`` and the limits of ``correct`` in
``chipbench/limits/<workload>.json``.

One process: set-up (compile cache, the program's session or trainer, the
benchmark's weights homed as the placement says, warm-up of the cell's own
shapes), then ``--seconds`` of measured work, then the comparison with the
plain reference once the program's state is freed.  With ``--trace 1`` the
window is profiled and the per-layer metrics are printed instead of the
end-to-end ones.  ``--control 1`` also reads the float8 control (see
``chipbench/reference.py``) and prints its numbers; the benchmark's own runs
leave it off.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), with the
compared numbers and their limits last, under ``checks``; the same numbers
end stderr.  With no TPU, or fewer chips than the cell asks for, it exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent
for p in (CHECKOUT / "src", CHECKOUT):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import check, counters, model, traffic  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.spans import span  # noqa: E402

EXIT_NO_CHIP = 3
EXIT_NO_PROGRAM = 2


def load_benchmark(path: Path = CHECKOUT / "BENCHMARK.json") -> dict:
    return json.loads(Path(path).read_text())


def workload_entry(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def metrics_for(bench: dict, workload: str, traced: bool) -> list:
    """The metric entries a run of ``workload`` reports."""
    entries = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in entries if workload in m.get("workloads", [workload])]


def reader(name: str, root: Path = HERE):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = Path(root) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench.metrics.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def build_cell(bench: dict, workload: str, seed: int, *, smoke: bool = False,
               root: Path = HERE):
    """The cell object of ``workload``, found by the names in ``bench``
    among the files under ``root``."""
    from repro.configs import get_config, get_smoke_config

    wl = workload_entry(bench, workload)
    arch, dims, raw = model.load(wl["config"], root)
    mix = traffic.load(wl["traffic"], Path(root) / "traffic")
    placement = traffic.load_placement(mix["placement"], Path(root) / "placements")
    if smoke:
        pcfg = get_smoke_config(raw["program_arch"])
        dims = arch.smoke(dims, pcfg)
    else:
        pcfg = get_config(raw["program_arch"])
        arch.check_program(dims, pcfg)
    if mix["entry"] == "train":
        from chipbench.train_cell import TrainCell as Cell
    elif mix["entry"] == "serve":
        from chipbench.serve_cell import ServeCell as Cell
    else:
        raise ValueError(f"unknown entry {mix['entry']!r} in traffic {wl['traffic']!r}")
    return Cell(arch, dims, pcfg, mix, placement, seed)


class _CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self) -> None:
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and "backend_compile" in event:
            self.count += 1


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    control: bool = False,
    smoke: bool = False,
    bench: dict | None = None,
    t_start: float = T_START,
    log=print,
    root: Path = HERE,
) -> dict:
    """One run of ``workload``, built from the files under ``root``; returns
    the result object."""
    import jax

    bench = bench or load_benchmark()
    wl = workload_entry(bench, workload)
    devices = jax.devices()[: wl["chips"]]
    mesh = jax.make_mesh(
        (1, len(devices)), ("data", "model"), devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    compiles = _CompileCounter()
    cell = build_cell(bench, workload, seed, smoke=smoke, root=root)
    cell.setup(mesh)
    for name, t in cell.phase.seconds.items():
        log(f"set-up phase {name}: {t:.3f} s")
    setup_s = time.perf_counter() - t_start - cell.excluded_s
    setup_peak = _peak_bytes(devices[0])
    log(f"set-up: {setup_s:.3f} s, peak_bytes_in_use {setup_peak}")

    before = counters.snapshot(cell.stats())
    trace_dir = tempfile.TemporaryDirectory(prefix="chipbench-trace-") if traced else None
    compiles.active = True
    if traced:
        jax.profiler.start_trace(trace_dir.name)
    try:
        with span("bench.window"):
            run = cell.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
        compiles.active = False
    run["counters"] = counters.delta(before, counters.snapshot(cell.stats()))
    run["peak_bytes"] = _peak_bytes(devices[0])
    log(f"window: {run['window_s']:.3f} s, {run['steps']} steps, {compiles.count} "
        f"compilations, peak_bytes_in_use {run['peak_bytes']} (set-up {setup_peak})")
    if "step_s" in run:
        log(f"window steps (s): {json.dumps([round(t, 4) for t in run['step_s']])}")
    cell.close()
    gc.collect()

    run.update(kind=cell.kind, setup_s=setup_s, trace=None)
    kind = devices[0].device_kind
    run["peaks"] = _peaks(kind, smoke)
    result_extra = {}
    if traced:
        events = tr.load(tr.find(trace_dir.name))
        reduced = tr.reduce(events, tr.window_of(events, "bench.window"))
        trace_dir.cleanup()
        run["trace"] = reduced
        result_extra["breakdown"] = reduced["breakdown"]

    metrics = {}
    for m in metrics_for(bench, workload, traced):
        value = reader(m["name"], root)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    with span("bench.check"):
        numbers, ctrl = _compare(cell, control, log)
    log(f"check: {time.perf_counter() - t_check:.3f} s")
    correct, checks = check.judge(numbers, check.limits(workload, Path(root) / "limits"))
    device = {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices()), "memory_peak_bytes": run["peak_bytes"],
        "device_kind": kind,
    }
    if traced:
        device.update(busy_s=run["trace"]["busy_s"], window_s=run["trace"]["window_s"])
    attempted = run["steps"] if cell.kind == "train" else run["requests"]
    result = {
        "correct": correct, "attempted": attempted, "failed": 0,
        "metrics": metrics, "device": device, **result_extra,
    }
    uncompared = {k: v for k, v in numbers.items() if k not in checks}
    if uncompared:
        result["readings"] = uncompared
    if ctrl is not None:
        result["control"] = ctrl
    result["checks"] = checks
    return result


def _peaks(kind: str, smoke: bool):
    from chipbench import flops

    if smoke:  # CPU tests: a table entry stands in so the arithmetic runs
        return {"bf16_flops_per_s": 1e12}
    return flops.peaks(kind)


def _compare(cell, control: bool, log):
    """``(numbers, control readings or None)`` of the cell against the
    reference, computed now that the program's state is freed."""
    if cell.kind == "train":
        ref = cell.reference_readings()
        prog = cell.program_readings()
        for who, r in (("program", prog), ("reference", ref)):
            shown = {k: v for k, v in r.items() if not k.endswith(".leaves")}
            log(f"readings: {who} {json.dumps(shown)}")
        numbers = check.train_numbers(prog, ref)
        ctrl = None
        if control:
            ctrl = {
                "control": check.train_numbers(cell.reference_readings(quant=True), ref),
                "half_batch": check.train_numbers(
                    cell.reference_readings(half_batch=True), ref
                ),
            }
        return numbers, ctrl
    served, ctrl_gaps = cell.gaps(quant=control)
    numbers = check.serve_numbers(served)
    return numbers, ({"control": check.serve_numbers(ctrl_gaps)} if control else None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_benchmark()
    wl = workload_entry(bench, args.workload)
    try:
        import repro  # noqa: F401  (the program under test)
    except ImportError as e:
        print(f"chipbench: the program is not in this checkout: {e}", file=sys.stderr)
        return EXIT_NO_PROGRAM
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX platform {devices[0].platform!r})", file=sys.stderr)
        return EXIT_NO_CHIP
    if len(devices) < wl["chips"]:
        print(f"chipbench: {args.workload} needs {wl['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return EXIT_NO_CHIP

    _enable_compile_cache()
    log = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    result = run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        control=bool(args.control), bench=bench, log=log,
    )
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def _enable_compile_cache() -> None:
    """JAX's persistent cache in ``.jax_cache`` at the checkout's root (or
    where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


if __name__ == "__main__":
    raise SystemExit(main())
