"""A serving cell: the program's ``ServeSession`` under a closed loop.

Set-up builds one session with the benchmark's weights homed where the
placement says, fills every slot from the seeded request stream (their
prefill compiles the prompt width) and runs ``warm_steps`` decode steps (the
decode step compiles).  The window then calls ``ServeSession.step`` back to
back; with ``refill`` a retired slot takes the next queued request at the
end of the same step, so the batch stays full and admissions (with their
prefill) happen inside the window.  Every emitted token is stamped with the
host time its step returned.

The weights and the reference come from the architecture the configuration
names; every key of the placement file goes to ``ServeSession`` under its own
name.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from chipbench import reference, traffic, weights
from chipbench.spans import Phases, span


@contextlib.contextmanager
def _supplied_weights(tree, host_tree):
    """Have ``ServeSession`` take the benchmark's weights instead of drawing
    its own: its constructor reads them from ``repro.train.steps``."""
    from repro.train import steps as st

    saved = st.init_train_state, st.init_weight_streamed_params
    st.init_train_state = lambda key, cfg: (tree, None)
    st.init_weight_streamed_params = lambda key, cfg, plan: plan.init_home(host_tree)
    try:
        yield
    finally:
        st.init_train_state, st.init_weight_streamed_params = saved


class ServeCell:
    kind = "serve"

    def __init__(self, arch, dims, pcfg, mix: dict, placement: dict, seed: int) -> None:
        self.arch = arch
        self.dims, self.pcfg, self.mix, self.placement = dims, pcfg, mix, placement
        self.seed = seed
        self.phase = Phases()
        self.excluded_s = 0.0
        self.width = -(-traffic.max_len(mix) // 128) * 128
        self.times: dict = {}  # rid -> host time of each emitted token

    def setup(self, mesh) -> None:
        import jax
        from repro.launch.serve import ServeSession
        from repro.train import steps as st

        kwargs = traffic.entry_kwargs(ServeSession, self.placement)
        with self.phase("weights"):
            bf16 = jax.numpy.bfloat16
            streamed = kwargs.get("param_kind", "device") != "device"
            if streamed:
                tree, host = None, self.arch.host_tree(self.dims, self.seed, bf16)
            else:
                tree, host = self.arch.device_tree(self.dims, self.seed, bf16), None
            weights.check_layout(host if streamed else tree, st.abstract_params(self.pcfg))
        with self.phase("session"), _supplied_weights(tree, host):
            self.session = ServeSession(
                self.pcfg, mesh,
                slots=self.mix["slots"], max_len=traffic.max_len(self.mix),
                seed=self.seed, **kwargs,
            )
        del tree, host
        self.stream = traffic.RequestStream(self.mix, self.dims.vocab, self.seed)
        self.offered = 0
        for _ in range(self.mix["slots"]):
            self._submit()
        with self.phase("prefill"):
            self._stamp(self.session.admit_pending())
        for i in range(self.mix["warm_steps"]):
            with self.phase(f"decode{i}"):
                self._stamp(self.session.step())

    def _submit(self) -> None:
        req = self.stream.next()
        if self.session.submit(req.prompt, req.gen) is None:
            raise ValueError("the session refused a request of the mix")
        self.offered += 1

    def _stamp(self, emitted: dict) -> None:
        now = time.perf_counter()
        for rid in emitted:
            self.times.setdefault(rid, []).append(now)

    def stats(self) -> dict:
        return {"weights": self.session.param_stats, "kv": self.session.stats}

    def window(self, seconds: float) -> dict:
        s = self.session
        t0 = time.perf_counter()
        n_steps, prefills, end, model_flops = 0, 0, t0, 0.0
        while end - t0 < seconds:
            if self.mix["refill"]:
                # keep a slot's worth of requests queued, so a retired slot
                # is refilled at the end of the very step that retired it
                while len(s.queue) < self.mix["slots"]:
                    self._submit()
            if not s.pending_work():
                break
            before = s.active
            model_flops += sum(
                self.arch.decode_token(self.dims, s.pager.tables[rid].pos + 1)
                for rid in before
            )
            with span("bench.serve_step"):
                emitted = s.step()
            end = time.perf_counter()
            self._stamp(emitted)
            for rid in emitted:
                if rid not in before:  # admitted at the end of this step
                    prefills += 1
                    model_flops += self.arch.prefill(self.dims, len(s.requests[rid].prompt))
            n_steps += 1
        tokens = sum(sum(1 for t in ts if t > t0) for ts in self.times.values())
        gaps = []
        for ts in self.times.values():
            gaps += [b - a for a, b in zip(ts, ts[1:]) if a > t0]
        return {
            "window_s": end - t0, "steps": n_steps, "tokens": tokens,
            "tpot_s": gaps, "prefills": prefills, "t0": t0,
            "model_flops": model_flops,
            "requests": sum(1 for ts in self.times.values() if ts[-1] > t0),
        }

    def close(self) -> None:
        self.served = {
            rid: (np.asarray(r.prompt), np.asarray(r.emitted, np.int32))
            for rid, r in self.session.requests.items() if r.emitted
        }
        self.session.close()
        self.session = None

    # -- the check -------------------------------------------------------------
    def sample(self) -> list:
        """Requests compared: the longest served, then others drawn from the
        seed, up to ``check_requests``."""
        rids = sorted(self.served, key=lambda r: (-len(self.served[r][1]), r))
        rng = np.random.default_rng([int(self.seed) % (1 << 64), 0xC4EC])
        rest = rids[1:]
        rng.shuffle(rest)
        return (rids[:1] + rest)[: self.mix["check_requests"]]

    def gaps(self, quant: bool = False):
        """Per-token gaps of the sampled requests: ``(served, control)``."""
        import jax.numpy as jnp

        w = self.arch.reference_weights(self.dims, self.seed, jnp.bfloat16)
        ref = self.arch.Reference(self.dims)
        ctrl = self.arch.Reference(self.dims, quant=True) if quant else None
        served, control = [], []
        for rid in self.sample():
            prompt, out = self.served[rid]
            g, c = reference.served_gaps(ref, w, prompt, out, self.width, ctrl)
            served.append(g)
            control.append(c)
        return np.concatenate(served), np.concatenate(control)

