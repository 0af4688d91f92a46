"""A training cell: the program's streamed train step, driven back to back.

Set-up builds one trainer with ``repro.launch.train.build_trainer``, homes
the benchmark's weights and a fresh AdamW state where the placement says,
and drives ``driver.step_fn`` through the first ``checked_steps`` steps
(the first compiles).  The same step function and state then run the
window.  What those first steps produced is read off the program's state
as it goes, and compared with the reference once the window has closed.

The weights and the reference come from the architecture the configuration
names; every key of the placement file goes to ``build_trainer`` under its
own name.
"""
from __future__ import annotations

import tempfile
import time

import numpy as np

from chipbench import reference, traffic, weights
from chipbench.spans import Phases, span

#: what the program's optimizer state keeps per leaf
_OPT_KEYS = ("master", "m", "v")


def _closure_object(fn, attr: str):
    """The object with attribute ``attr`` among the free variables of ``fn``
    (the launcher keeps the streamed step inside its wrapper)."""
    for cell in fn.__closure__ or ():
        obj = cell.cell_contents
        if hasattr(obj, attr):
            return obj
    raise LookupError(f"no {attr!r} behind {fn!r}")


class TrainCell:
    kind = "train"

    def __init__(self, arch, dims, pcfg, mix: dict, placement: dict, seed: int) -> None:
        self.arch = arch
        self.dims, self.pcfg, self.mix, self.placement = dims, pcfg, mix, placement
        self.seed = seed
        self.opt = mix["optimizer"]
        self.phase = Phases()
        self.excluded_s = 0.0  # time spent reading the check, not set-up
        self.readings: dict = {}

    # -- set-up ----------------------------------------------------------------
    def setup(self, mesh) -> None:
        import jax
        import jax.numpy as jnp
        from repro.core.weightstream import WeightStreamPlan
        from repro.launch.train import build_trainer
        from repro.optim.adamw import AdamWConfig
        from repro.runtime.driver import DriverConfig
        from repro.train import steps as st

        o = self.opt
        opt_cfg = AdamWConfig(
            peak_lr=o["peak_lr"], warmup_steps=o["warmup_steps"],
            total_steps=o["total_steps"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
            min_lr_ratio=o["min_lr_ratio"],
        )
        kwargs = traffic.entry_kwargs(build_trainer, self.placement)
        self._ckpt_dir = tempfile.TemporaryDirectory(prefix="chipbench-ckpt-")
        with self.phase("build"):
            self.driver = build_trainer(
                self.pcfg, mesh,
                global_batch=self.mix["batch"], seq_len=self.mix["seq_len"],
                opt_cfg=opt_cfg,
                driver_cfg=DriverConfig(
                    total_steps=1 << 30, checkpoint_every=0,
                    checkpoint_dir=self._ckpt_dir.name, max_restarts=0,
                ),
                seed=self.seed, **kwargs,
            )
            self.streamed = _closure_object(self.driver.step_fn, "opt_stats")
            abstract = st.abstract_params(self.pcfg)
            # the trainer's own plan, from the placement keys that set it
            self.plan = WeightStreamPlan(
                self.pcfg, abstract,
                layers_per_group=kwargs.get("param_layers_per_group"),
                device_budget_mb=kwargs.get("device_budget_mb"),
                expert_stream=kwargs.get("expert_stream", False),
            )
        with self.phase("weights"):
            tree32 = self.arch.device_tree(self.dims, self.seed, jnp.float32)
            weights.check_layout(tree32, abstract)
            tree16 = jax.jit(
                lambda t: jax.tree.map(lambda x: x.astype(jnp.bfloat16), t)
            )(tree32)
            host32 = jax.device_get(tree32)
            host16 = jax.device_get(tree16)
            del tree32, tree16
            # the masters start as views of ``host32``; the copy keeps the
            # initial weights whatever the program does with its homes
            self.init32 = jax.tree.map(np.copy, host32)
            zeros = lambda a: np.zeros(a.shape, np.float32)  # noqa: E731
            opt_groups = {
                g.key: jax.tree.map(
                    lambda a: {"master": a, "m": zeros(a), "v": zeros(a)},
                    self.plan.home_group(host32, g),
                )
                for g in self.plan.groups
            }
            state = {
                "params": self.plan.init_home(host16),
                "opt": {"groups": opt_groups, "step": np.zeros((), np.int32)},
            }
            template = jax.eval_shape(self.driver.init_state)
            if jax.tree.structure(template) != jax.tree.structure(state):
                raise ValueError("homed state is not the trainer's state layout")
        self.batch = traffic.train_batch_fn(self.mix, self.dims.vocab, self.seed)
        self.state = state
        self.losses = []
        for i in range(self.mix["checked_steps"]):
            with self.phase(f"step{i}"):
                self.state, metrics = self._step(i)
                self.losses.append(float(metrics["loss"]))
            if i == 0:
                self._read(
                    lambda s: s["m"], "grad_norm", scale=1.0 / (1.0 - o["b1"]), keep=True
                )
        self._read(lambda s: s["master"], "delta_norm", minus=self.init32)
        self.next_step = self.mix["checked_steps"]

    def _step(self, i: int):
        tokens, targets = self.batch(i)
        return self.driver.step_fn(self.state, {"tokens": tokens, "targets": targets})

    def _read(self, pick, name: str, *, scale: float = 1.0, minus=None,
              keep: bool = False) -> None:
        """Per-leaf norms (reference layout) of one part of the program's
        optimizer state, read now, before later steps replace it; ``keep``
        also keeps the scaled leaves (as ``<name>.leaves``)."""
        import jax

        t0 = time.perf_counter()
        is_state = lambda x: isinstance(x, dict) and set(x) == set(_OPT_KEYS)  # noqa: E731
        home = {
            "groups": {
                k: jax.tree.map(pick, tree, is_leaf=is_state)
                for k, tree in self.state["opt"]["groups"].items()
            }
        }
        full = self.arch.from_program(self.plan.assemble(home), self.dims)
        base = self.arch.from_program(minus, self.dims) if minus is not None else None
        norms, kept = {}, {}
        for k, a in full.items():
            a = np.asarray(a, np.float32)
            if base is not None:
                a = a - np.asarray(base[k], np.float32)
            norms[k] = float(np.linalg.norm(a.ravel())) * scale
            if keep:
                kept[k] = a * np.float32(scale)
        self.readings[name] = norms
        if keep:
            self.readings[f"{name}.leaves"] = kept
        self.excluded_s += time.perf_counter() - t0

    # -- the window ------------------------------------------------------------
    def stats(self) -> dict:
        return {
            "weights": self.driver.stream_stats,
            "opt": self.streamed.opt_stats,
        }

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        n, ends = 0, []
        while True:
            with span("bench.train_step"):
                self.state, metrics = self._step(self.next_step)
                float(metrics["loss"])
            self.next_step += 1
            n += 1
            ends.append(time.perf_counter())
            if ends[-1] - t0 >= seconds:
                break
        per_token = self.arch.train_per_token(self.dims, self.mix["seq_len"])
        tokens = self.mix["batch"] * self.mix["seq_len"] * n
        return {
            "window_s": ends[-1] - t0, "steps": n, "t0": t0,
            "model_flops": per_token * tokens,
            "step_s": [b - a for a, b in zip([t0] + ends, ends)],
        }

    def close(self) -> None:
        self.streamed.close()
        self.driver.engine.close()
        self.state = None
        self._ckpt_dir.cleanup()

    # -- the check -------------------------------------------------------------
    def reference_readings(self, quant: bool = False, half_batch: bool = False) -> dict:
        """The reference's readings; ``half_batch`` plants a fault (each
        step's loss is the mean over the first half of its rows only)."""
        import jax.numpy as jnp

        w0 = self.arch.reference_weights(self.dims, self.seed, jnp.float32)
        batches = [self.batch(i) for i in range(self.mix["checked_steps"])]
        if half_batch:
            half = self.mix["batch"] // 2
            batches = [(t[:half], y[:half]) for t, y in batches]
        ref = self.arch.Reference(self.dims, quant=quant)
        return reference.train_readings(ref, w0, batches, self.opt)

    def program_readings(self) -> dict:
        return {"loss": self.losses, **self.readings}
