"""Paged hierarchical KV-cache serving: continuous batching over memory kinds.

The serving counterpart of the streamed optimizer: each request's KV cache
is split into fixed-size page groups (``repro.core.kvpager``) and only the
hot attention window stays device-resident.  Cold pages live at the kind
named by ``--kv-kind`` (``device`` | ``pinned_host`` | ``disk_host``) and
are fetched ahead of the decode step by the
:class:`~repro.core.engine.TransferEngine` — coalesced (one H2D request per
page group), prefetched under a per-request adaptive window
(``distance="auto"``), written back through the pipelined D2H drain when
they fall out of the hot window.  The decode step consumes the assembled
page view **by reference** — the same executable as the unpaged step, so
where the cache lives never changes what is decoded (bitwise).

:class:`ServeSession` is the engine room: a request queue with continuous
batching — requests are admitted into free batch slots and evicted/retired
between decode steps, each with its own prompt length (pad-free: prefill is
per-request) and its own page table.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \\
      --batch 4 --prompt-len 32 --gen 16 --kv-kind pinned_host --kv-page-len 16
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from collections import deque
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.core import memkind as mk
from repro.core.engine import TransferEngine
from repro.core.hoststream import StreamStats
from repro.core.kvpager import (
    KVPager,
    KVPagerConfig,
    page_template,
    paged_cache_supported,
    shared_prefix_keys,
)
from repro.core.refspec import AUTO
from repro.core.residency import ResidencyCache
from repro.core.spans import span
from repro.core.spillstore import SpillStore
from repro.launch.mesh import make_local_mesh
from repro.parallel import sharding as sh
from repro.train import steps as st

KV_KINDS = ("device", "pinned_host", "disk_host")


def _round_up(n: int, m: int) -> int:
    return (n + m - 1) // m * m


def _schedule_note(plan, *, distance, cache_capacity, budget_mb, kv,
                   route_experts) -> str:
    """Best-effort analyzer occupancy report appended to budget errors, so
    a rejected flag combination names the program points that overrun
    instead of just the closed-form floor."""
    try:
        from repro.core import schedcheck as sc

        report = sc.analyze_serve_schedule(
            plan,
            distance=distance,
            cache_capacity=cache_capacity,
            budget_bytes=(
                int(budget_mb * 1e6) if budget_mb is not None else None
            ),
            kv=kv,
            route_experts=route_experts,
        )
        return "\n" + str(report)
    except Exception:
        return ""


def _prompt_batch(cfg, tokens) -> dict:
    """(B, S) prompt ids -> the model's batch dict (codebook archs replicate
    the ids over codebooks, as the seed serve loop did)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    if cfg.n_codebooks:
        b, s = tokens.shape
        return {
            "codes": jnp.broadcast_to(tokens[:, None], (b, cfg.n_codebooks, s))
        }
    return {"tokens": tokens}


def _step_batch(cfg, tok: np.ndarray) -> dict:
    """Per-slot next tokens — (B,) or (B, n_codebooks) — to a one-token
    decode batch dict."""
    if cfg.n_codebooks:
        return {"codes": jnp.asarray(tok).reshape(-1, cfg.n_codebooks, 1)}
    return {"tokens": jnp.asarray(tok).reshape(-1, 1)}


def _emit(cfg, tok) -> int:
    """The emitted stream token (codebook archs report codebook 0)."""
    return int(tok[0]) if cfg.n_codebooks else int(tok)


@dataclasses.dataclass
class Request:
    """One generation request: prompt in, ``gen`` greedy tokens out."""

    rid: int
    prompt: np.ndarray  # (s,) int32
    gen: int
    #: last sampled token — scalar, or (n_codebooks,) for audio archs —
    #: the next decode step's input
    next_token: Optional[np.ndarray] = None
    emitted: list = dataclasses.field(default_factory=list)

    @property
    def done(self) -> bool:
        return len(self.emitted) >= self.gen


class ServeSession:
    """Continuous-batching decode loop over a paged hierarchical KV cache.

    ``slots`` batch lanes decode in lock-step (one jitted step, per-slot
    positions); requests flow through them: ``submit`` queues work,
    admissions fill free slots between steps (per-request prefill — no
    cross-request prompt padding), finished requests retire and their slot
    is immediately reused.  ``evict``/``readmit`` park a request's pages at
    the host mid-decode and resume it later — decoding continues
    bitwise-identically because pages are reconstructed exactly.
    """

    def __init__(
        self,
        cfg,
        mesh,
        *,
        slots: int,
        max_len: int,
        kv_kind: str = "device",
        page_len: int = 32,
        hot_pages: int = 1,
        distance=AUTO,
        seed: int = 0,
        engine: Optional[TransferEngine] = None,
        spill_dir: Optional[str] = None,
        stats: Optional[StreamStats] = None,
        param_kind: str = "device",
        device_budget_mb: Optional[float] = None,
        param_layers_per_group: Optional[int] = None,
        param_distance=AUTO,
        param_cache_mb: Optional[float] = None,
        expert_stream: bool = False,
        route_experts: bool = True,
        prefix_sharing: bool = True,
    ) -> None:
        self.cfg = cfg
        self.mesh = mesh
        self.slots = slots
        self.stats = stats if stats is not None else StreamStats()
        self.stats.mode = "paged"
        #: transfer accounting of the *weight* stream (separate from the KV
        #: page stream so each tier's request model stays checkable)
        self.param_stats = StreamStats()
        self._kind = mk.as_kind(kv_kind)
        # validate — and do every fallible init — before allocating the
        # engine thread / spill dir: a failed constructor must not leak
        # resources (KVPagerConfig validates its knobs in __post_init__)
        pager_cfg = KVPagerConfig(
            page_len=page_len,
            hot_pages=hot_pages,
            kind=self._kind,
            distance=distance,
        )
        self.max_len = _round_up(max_len, page_len)
        template = st.abstract_caches(cfg, 1, self.max_len)
        if not paged_cache_supported(template):
            raise ValueError(
                f"{cfg.name}: cache tree is not pageable (ring/recurrent "
                "state) — use the unpaged serve path (kv_page_len=0)"
            )
        # streamed weights: plan before any resource allocation (plan
        # construction validates the budget and can raise)
        self._wplan = None
        engine_cfg = None
        if expert_stream and param_kind == "device":
            raise ValueError(
                "--expert-stream streams routed experts from a weight home; "
                "it requires --param-kind pinned_host or disk_host"
            )
        #: expert-group fetch accounting (route-aware MoE streaming) —
        #: separate from ``param_stats`` so the bench can gate routed vs
        #: all-expert link traffic directly
        self.expert_stats: Optional[StreamStats] = None
        #: weight-residency group cache — keeps fetched weight groups
        #: device-resident across prefill/decode steps (serve params are
        #: immutable, so entries are never invalidated, only LRU-evicted)
        self.param_residency: Optional[ResidencyCache] = None
        #: static analyzer report for the streamed-weight + KV page schedule
        #: (:func:`repro.core.schedcheck.analyze_serve_schedule`); ``None``
        #: for device-resident weights
        self.schedule_report = None
        if param_kind != "device":
            from repro.core.engine import EngineConfig
            from repro.core.weightstream import (
                PARAM_KINDS,
                WeightStreamPlan,
                weight_stream_support,
            )

            if param_kind not in PARAM_KINDS:
                raise ValueError(
                    f"unknown param_kind {param_kind!r}; expected one of "
                    f"{PARAM_KINDS}"
                )
            support = weight_stream_support(cfg)
            if not support.serve_supported:
                raise ValueError(
                    f"--param-kind {param_kind}: "
                    f"{support.serve_reason or support.reason}"
                )
            budget = device_budget_mb
            # per-(slot,page) device bytes — the hot-window reservation unit
            # and the analyzer's KV occupancy baseline
            page_nbytes = sum(
                int(np.prod(s.shape)) * s.dtype.itemsize
                for s in jax.tree.leaves(page_template(template, page_len))
            )
            kv_desc = dict(
                slots=slots,
                page_len=page_len,
                hot_pages=hot_pages,
                page_nbytes=page_nbytes,
                max_len=self.max_len,
            )
            if budget is not None:
                # the device budget is shared: the pager's hot window (the
                # current page + hot_pages full pages + the shared zero
                # page, per slot) takes its cut first, weights stream under
                # the remainder
                hot_mb = slots * (hot_pages + 2) * page_nbytes / 1e6
                budget = budget - hot_mb
                if budget <= 0:
                    probe = WeightStreamPlan(
                        cfg,
                        st.abstract_params(cfg),
                        layers_per_group=param_layers_per_group,
                        device_budget_mb=None,
                        expert_stream=expert_stream,
                    )
                    raise ValueError(
                        f"device_budget_mb={device_budget_mb} is consumed by "
                        f"the KV hot window ({hot_mb:.1f} MB); raise the "
                        "budget or shrink hot_pages/page_len"
                        + _schedule_note(
                            probe,
                            distance=1,
                            cache_capacity=0,
                            budget_mb=device_budget_mb,
                            kv=kv_desc,
                            route_experts=route_experts,
                        )
                    )
            self._wplan = WeightStreamPlan(
                cfg,
                st.abstract_params(cfg),
                layers_per_group=param_layers_per_group,
                device_budget_mb=budget,
                expert_stream=expert_stream,
            )
            # weight-residency cache capacity: default = the budget slack
            # above the widest prefetch window (None budget = unbounded);
            # an explicit --param-cache-mb instead RESERVES that many bytes,
            # narrowing the window — which must still fit at distance 1
            if param_cache_mb is None:
                cache_cap = self._wplan.residency_capacity_bytes()
            else:
                cache_cap = int(param_cache_mb * 1e6)
                floor = self._wplan.peak_device_bytes(1, cached_bytes=cache_cap)
                if budget is not None and floor > budget * 1e6:
                    hot_mb = (device_budget_mb or 0) - budget
                    raise ValueError(
                        f"device_budget_mb={device_budget_mb} cannot hold the "
                        f"KV hot window ({hot_mb:.1f} MB) + the distance-1 "
                        f"weight stream floor "
                        f"({self._wplan.peak_device_bytes(1) / 1e6:.1f} MB) + "
                        f"param_cache_mb={param_cache_mb}; raise the budget, "
                        "shrink hot_pages/page_len/param_layers_per_group, or "
                        "lower param_cache_mb"
                        + _schedule_note(
                            self._wplan,
                            distance=1,
                            cache_capacity=cache_cap,
                            budget_mb=device_budget_mb,
                            kv=kv_desc,
                            route_experts=route_experts,
                        )
                    )
            cache_reserved = (
                (cache_cap or 0) if budget is not None else 0
            )
            self.param_residency = ResidencyCache(cache_cap)
            engine_cfg = EngineConfig(
                max_distance=self._wplan.max_distance_for_budget(
                    cached_bytes=cache_reserved
                )
            )
            if engine is not None and (
                budget is not None
                and engine.config.max_distance
                > self._wplan.max_distance_for_budget(
                    cached_bytes=cache_reserved
                )
            ):
                # an external engine must respect the budget's window cap or
                # the adaptive controller can stream past the budget
                raise ValueError(
                    f"external engine's max_distance="
                    f"{engine.config.max_distance} exceeds the device "
                    f"budget's cap "
                    f"{self._wplan.max_distance_for_budget(cached_bytes=cache_reserved)} "
                    "(window + residency cache share the budget); "
                    "pass an engine configured from the plan (or no engine)"
                )
            # static schedule verification: replay the fetch program the
            # session is about to run (prefill walk, router-first decode,
            # KV page demote/readmit) and refuse construction on any
            # occupancy overrun or transfer hazard (core/schedcheck)
            from repro.core.schedcheck import (
                analyze_serve_schedule,
                verify_schedule,
            )

            self.schedule_report = analyze_serve_schedule(
                self._wplan,
                distance=(
                    engine.config.max_distance
                    if engine is not None
                    else engine_cfg.max_distance
                ),
                cache_capacity=cache_cap,
                budget_bytes=(
                    int(device_budget_mb * 1e6)
                    if device_budget_mb is not None
                    else None
                ),
                kv=kv_desc,
                route_experts=route_experts,
            )
            verify_schedule(self.schedule_report)
        self.plan = sh.make_plan(mesh, mode="serve")
        key = jax.random.PRNGKey(seed)
        if self._wplan is not None:
            # group-wise init: the full param tree is never device-resident
            # (the point of streaming arbitrarily large models); homes are
            # built BEFORE the engine thread exists so a failed spill
            # cannot leak a worker
            self.sharder = sh.make_sharder(
                self.plan, st.abstract_params(cfg), slots
            )
            home = st.init_weight_streamed_params(key, cfg, self._wplan)
            self._param_store = None
            if param_kind == "disk_host":
                import tempfile

                pd = (
                    str(Path(spill_dir) / "params")
                    if spill_dir is not None and self._kind == mk.DISK_HOST
                    else tempfile.mkdtemp(prefix="repro-serve-wp-")
                )
                self._param_store = SpillStore(pd, ephemeral=True)
                try:
                    home = self._wplan.spill_home(home, self._param_store)
                except BaseException:
                    # no-leak contract: a failed spill (full disk) must not
                    # orphan the ephemeral chunk directory
                    self._param_store.close()
                    raise
            self.params = home
        else:
            self.params = st.init_train_state(key, cfg)[0]
            self.sharder = sh.make_sharder(self.plan, self.params, slots)
            self._param_store = None

        self._engine = engine or TransferEngine(engine_cfg)
        self._owns_engine = engine is None
        self._store = None
        try:
            if self._kind == mk.DISK_HOST:
                ephemeral = spill_dir is None
                if ephemeral:
                    import tempfile

                    spill_dir = tempfile.mkdtemp(prefix="repro-serve-kv-")
                self._store = SpillStore(spill_dir, ephemeral=ephemeral)

            # cold pages stage at the serve plan's cache specs (derived on
            # the *page* shape so divisibility fallbacks see what actually
            # moves): under --model-parallel a fetched page group costs one
            # coalesced H2D request per device, not one per leaf
            page_specs = sh.cache_specs_tree(
                self.plan, page_template(template, page_len), 1
            )
            self.pager = KVPager(
                template,
                pager_cfg,
                slots=slots,
                engine=self._engine,
                store=self._store,
                device_shardings=sh.named_shardings(mesh, page_specs),
            )
            if self._wplan is not None:
                # stream the homed weights per prefill / decode step; the
                # decode executables consume the groups by reference, so
                # where the weights live never changes the tokens
                p_sh = None
                if mesh.devices.size > 1:
                    p_specs = sh.param_specs(self.plan, st.abstract_params(cfg))
                    p_sh = sh.named_shardings(mesh, p_specs)
                from repro.core.refspec import PrefetchSpec

                w_dist = (
                    param_distance if param_distance == AUTO else int(param_distance)
                )
                param_pf = PrefetchSpec(
                    buffer_size=self._wplan.n_groups + 2, distance=w_dist
                )
                self._prefill = st.make_weight_streamed_prefill_step(
                    cfg, self._wplan, 1, self.max_len, mesh, self.sharder,
                    engine=self._engine, stats=self.param_stats,
                    param_shardings=p_sh, prefetch=param_pf,
                    residency=self.param_residency,
                )
                self._step = st.make_weight_streamed_decode_step(
                    cfg, self._wplan, mesh, self.sharder,
                    engine=self._engine, stats=self.param_stats,
                    param_shardings=p_sh, paged=True, prefetch=param_pf,
                    residency=self.param_residency,
                    route_experts=route_experts,
                )
                self.expert_stats = getattr(self._step, "expert_stats", None)
            else:
                self._prefill = jax.jit(
                    st.make_prefill_step(cfg, 1, self.max_len, mesh, self.sharder)
                )
                self._step = st.make_paged_decode_step(cfg, mesh, self.sharder)
            self._argmax = jax.jit(
                lambda logits: jnp.argmax(logits[..., -1, :], axis=-1).astype(
                    jnp.int32
                )
            )
        except BaseException:
            # the constructor's no-leak contract: anything that fails after
            # the engine thread / spill dirs exist tears them down
            self.close()
            raise

        self.requests: dict[int, Request] = {}
        self.queue: "deque[int]" = deque()
        self._slot_of: dict[int, int] = {}  # rid -> slot
        self._next_rid = 0
        self.n_steps = 0
        #: COW prefix sharing: admit pages under content-digest keys so
        #: requests with a common page-aligned prompt prefix alias one cold
        #: copy (no-op for device-resident caches — nothing is ever cold)
        self._prefix_sharing = prefix_sharing and self._kind != mk.DEVICE
        #: requests rejected at submit (prompt + gen > max_len) — under
        #: open-loop load an oversized request must not kill the session
        self.rejected = 0
        #: readmits that found the batch full, drained (ahead of new
        #: admissions) by the next admission cycle
        self._readmit_queue: "deque[int]" = deque()
        #: per-step compute-blocked transfer wait (steady-state metric)
        self.step_waits: list = []
        #: per-step UNIQUE weight-group fetches (H2D link traffic, not
        #: resident pass-throughs) — the residency gate: with cache slack
        #: this decays to 0 at steady state instead of n_groups every step
        self.param_step_fetches: list = []

    def _tok_shape(self) -> tuple:
        cb = self.cfg.n_codebooks
        return (self.slots, cb) if cb else (self.slots,)

    # -- request lifecycle ---------------------------------------------------
    def submit(self, prompt, gen: int) -> Optional[int]:
        """Queue a request; returns its id.  Admitted at the next step (or
        immediately via :meth:`admit_pending`).

        A request that cannot fit (``prompt + gen > max_len``) is rejected
        gracefully — ``None`` is returned and ``self.rejected`` counts it —
        instead of raising mid-run (under open-loop load one oversized
        prompt must not kill the whole session)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + gen > self.max_len:
            self.rejected += 1
            return None
        rid = self._next_rid
        self._next_rid += 1
        self.requests[rid] = Request(rid=rid, prompt=prompt, gen=gen)
        self.queue.append(rid)
        return rid

    def _bucket_len(self, s: int) -> int:
        """Power-of-two prompt-length bucket (min 8, capped at ``max_len``):
        prefill compiles once per bucket instead of once per distinct
        prompt length.  Bucketing is bitwise-invisible — the pad tail's
        garbage K/V lands beyond the write position (masked by every
        decode step's causal ``pos`` mask until overwritten, or dropped
        with the ``_ZERO`` pages) and the head reads the last *real*
        position via ``last_pos``."""
        b = 8
        while b < s:
            b *= 2
        return min(b, self.max_len)

    def _prefix_keys(self, req: Request) -> Optional[list]:
        if not self._prefix_sharing:
            return None
        return shared_prefix_keys(req.prompt, self.pager.config.page_len)

    def _free_slots(self) -> list:
        return [s for s in range(self.slots) if s not in self.pager._by_slot]

    def admit_pending(self) -> dict:
        """Prefill queued requests into free slots.  Returns ``{rid:
        first_token}`` (the prompt's greedy continuation — emitted at
        admission, before any decode step)."""
        emitted = {}
        for slot in self._free_slots():
            # queued readmits resume first: they were promised a slot
            # before any not-yet-admitted submission existed
            if self._readmit_queue:
                rid = self._readmit_queue.popleft()
                self.pager.readmit(rid, slot)
                self._slot_of[rid] = slot
                continue
            if not self.queue:
                break
            rid = self.queue.popleft()
            req = self.requests[rid]
            with span("serve.admit", rid=rid):
                s = len(req.prompt)
                width = self._bucket_len(s)
                padded = np.zeros((width,), np.int32)
                padded[:s] = req.prompt
                logits, cache = self._prefill(
                    self.params,
                    _prompt_batch(self.cfg, padded[None, :]),
                    jnp.asarray(s - 1, jnp.int32),
                )
                with span("serve.sync.wait"):
                    tok = np.asarray(self._argmax(logits))[0]  # scalar / (n_codebooks,)
                req.next_token = tok
                req.emitted.append(_emit(self.cfg, tok))
                emitted[rid] = req.emitted[-1]
                self._slot_of[rid] = slot
                self.pager.admit(
                    rid, slot, cache, s, prefix_keys=self._prefix_keys(req)
                )
            if req.done:  # gen == 1: nothing left to decode
                self._retire(rid)
        self.pager.flush_demotions(self.stats)
        self.pager.prefetch()
        return emitted

    def _retire(self, rid: int) -> None:
        self._slot_of.pop(rid, None)
        self.pager.retire(rid, self.stats)

    def evict(self, rid: int) -> None:
        """Park a mid-decode request at the host and free its slot."""
        self.pager.evict(rid, self.stats)
        self._slot_of.pop(rid, None)

    def readmit(self, rid: int) -> bool:
        """Resume an evicted request in a free slot (pages stream back in
        cold over the following steps).  When the batch is full the
        readmit is QUEUED for the next admission cycle — ahead of new
        submissions — instead of crashing the session mid-run; returns
        True when a slot was taken now, False when queued."""
        table = self.pager.tables.get(rid)
        if table is None:
            raise KeyError(f"unknown request {rid}")
        if table.slot is not None:
            raise ValueError(f"request {rid} is not evicted")
        if rid in self._readmit_queue:
            return False
        free = self._free_slots()
        if not free:
            self._readmit_queue.append(rid)
            return False
        slot = free[0]
        self.pager.readmit(rid, slot)
        self._slot_of[rid] = slot
        return True

    @property
    def active(self) -> dict:
        """rid -> slot of requests currently decoding."""
        return dict(self._slot_of)

    def pending_work(self) -> bool:
        return bool(self.queue or self._slot_of or self._readmit_queue)

    def prefill_compiles(self) -> Optional[int]:
        """Compiled prefill variant count (bucketed prompt widths); None
        for the streamed-weight prefill (a composite, not one jit)."""
        cache_size = getattr(self._prefill, "_cache_size", None)
        return cache_size() if cache_size is not None else None

    # -- the decode loop -----------------------------------------------------
    def warmup(self) -> None:
        """Compile every per-step executable against a throwaway all-zero
        view (no table/stream state is touched), so the first timed step
        does not pay compile time (cf. ``benchmarks/common.timed``)."""
        view = tuple(
            (self.pager._zero_page,) * self.pager.n_pages for _ in range(self.slots)
        )
        tokens = np.zeros(self._tok_shape(), np.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        logits, nc = self._step(
            self.params, view, _step_batch(self.cfg, tokens), pos
        )
        self._argmax(logits)
        out = self.pager._extract(
            nc, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)
        )
        jax.block_until_ready(out)

    def step(self) -> dict:
        """One decode step over every active slot.  Returns ``{rid: token}``
        for tokens emitted this step (including first tokens of requests
        admitted at the end of the step)."""
        with span("serve.step", step=self.n_steps):
            if not self._slot_of and (self.queue or self._readmit_queue):
                return self.admit_pending()
            wait0 = self.stats.transfer_wait_s
            fetch0 = self.param_stats.unique_group_fetches

            tokens = np.zeros(self._tok_shape(), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            by_slot = {}
            for rid, slot in self._slot_of.items():
                req = self.requests[rid]
                tokens[slot] = req.next_token
                pos[slot] = self.pager.tables[rid].pos
                by_slot[slot] = req

            # pop this step's cold pages (waits only where the window fell
            # short), then speculatively prefetch the same cold set for the
            # next step — those transfers overlap the decode compute below
            view = self.pager.view(self.stats)
            self.pager.prefetch()
            logits, new_cache = self._step(
                self.params, view, _step_batch(self.cfg, tokens), jnp.asarray(pos)
            )
            with span("serve.sync.wait"):
                nxt = np.asarray(self._argmax(logits))  # blocks on the decode compute
            self.pager.update_current(new_cache)

            emitted = {}
            for slot, req in by_slot.items():
                req.next_token = nxt[slot]
                req.emitted.append(_emit(self.cfg, nxt[slot]))
                emitted[req.rid] = req.emitted[-1]
                table = self.pager.tables[req.rid]
                table.pos += 1
                self.pager.advance(table)
            self.pager.flush_demotions(self.stats)
            for req in list(by_slot.values()):
                if req.done:
                    self._retire(req.rid)
            self.n_steps += 1
            self.step_waits.append(self.stats.transfer_wait_s - wait0)
            self.param_step_fetches.append(
                self.param_stats.unique_group_fetches - fetch0
            )
            emitted.update(self.admit_pending())
            return emitted

    def run(self) -> dict:
        """Drive steps until every submitted request has finished.  Returns
        ``{rid: np.ndarray of emitted tokens}``."""
        self.admit_pending()
        while self.pending_work():
            self.step()
        return {
            rid: np.asarray(req.emitted, np.int32)
            for rid, req in self.requests.items()
        }

    def close(self) -> None:
        if self._owns_engine:
            self._engine.close()
        if self._store is not None:
            self._store.close()
        if self._param_store is not None:
            self._param_store.close()
        if self.param_residency is not None:
            self.param_residency.clear()  # release resident device copies

    def __enter__(self) -> "ServeSession":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# ---------------------------------------------------------------------------
# unpaged reference path (per-step whole-cache placement)
# ---------------------------------------------------------------------------


def _serve_unpaged(
    cfg,
    mesh,
    *,
    batch: int,
    prompt_len: int,
    gen: int,
    kv_kind: str,
    seed: int,
    engine: Optional[TransferEngine],
    warmup: bool,
    stats: StreamStats,
    param_kind: str = "device",
    device_budget_mb: Optional[float] = None,
    param_layers_per_group: Optional[int] = None,
    param_distance=AUTO,
    param_cache_mb: Optional[float] = None,
    expert_stream: bool = False,
    route_experts: bool = True,
    spill_dir: Optional[str] = None,
):
    """The pre-pager schedule, kept as the A/B baseline: host-resident
    caches round-trip through host memory synchronously on every decode
    step.  Fixed (debugged) version: placement uses the sharding plan's
    cache specs (a bare ``PartitionSpec()`` silently dropped the plan under
    model parallelism), and the cache is only donated when it is
    device-resident (donating a cache the host branch then re-places trips
    deleted-buffer errors).

    Pageable (full-attention) caches prefill per request and decode with
    per-slot positions — the same executables as the paged session, so the
    two paths are bitwise-comparable.  Ring/recurrent caches (``slot_pos``
    is shared across the batch) keep the seed's lock-step schedule: one
    batched prefill, one scalar position.

    ``param_kind`` homes the weights off-device and streams them through
    the group-program executables (``paged=False`` decode) — the route for
    archs whose KV cache is NOT pageable (SWA rings like mixtral) but whose
    weights should still stream; ``expert_stream`` fetches only the routed
    experts per decode step.
    """
    plan = sh.make_plan(mesh, mode="serve")
    key = jax.random.PRNGKey(seed)
    kind = mk.as_kind(kv_kind)
    if kind == mk.DISK_HOST:
        raise ValueError("the unpaged path has no disk home; use --kv-page-len > 0")
    device_resident = kind.jax_kind == "device"

    max_len = prompt_len + gen
    vector_pos = paged_cache_supported(st.abstract_caches(cfg, 1, max_len))

    wplan = None
    param_stats = StreamStats()
    expert_stats = None
    residency = None
    param_store = None
    own_engine = None
    #: KV round-trip emulation stays tied to the CALLER's engine — an
    #: engine created here for the weight stream must not add synthetic
    #: stalls to the cache path
    kv_engine = engine
    if param_kind != "device":
        from repro.core.engine import EngineConfig
        from repro.core.weightstream import (
            PARAM_KINDS,
            WeightStreamPlan,
            weight_stream_support,
        )

        if param_kind not in PARAM_KINDS:
            raise ValueError(
                f"unknown param_kind {param_kind!r}; expected one of "
                f"{PARAM_KINDS}"
            )
        support = weight_stream_support(cfg)
        if not support.serve_supported:
            raise ValueError(
                f"--param-kind {param_kind}: "
                f"{support.serve_reason or support.reason}"
            )
        wplan = WeightStreamPlan(
            cfg,
            st.abstract_params(cfg),
            layers_per_group=param_layers_per_group,
            device_budget_mb=device_budget_mb,
            expert_stream=expert_stream,
        )
        cache_cap = (
            wplan.residency_capacity_bytes()
            if param_cache_mb is None
            else int(param_cache_mb * 1e6)
        )
        residency = ResidencyCache(cache_cap)
        if engine is None:
            engine = own_engine = TransferEngine(
                EngineConfig(max_distance=wplan.max_distance_for_budget())
            )
        # static schedule verification (same contract as ServeSession):
        # refuse to serve a fetch program that can overrun the budget or
        # re-fetch through a pending writeback
        from repro.core.schedcheck import (
            analyze_serve_schedule,
            verify_schedule,
        )

        verify_schedule(
            analyze_serve_schedule(
                wplan,
                distance=engine.config.max_distance,
                cache_capacity=cache_cap,
                route_experts=route_experts,
                fan_in=(
                    max(1, getattr(cfg, "moe_top_k", 2)) * batch
                    if route_experts
                    else None
                ),
            )
        )
        sharder = sh.make_sharder(plan, st.abstract_params(cfg), batch)
        params = st.init_weight_streamed_params(key, cfg, wplan)
        if param_kind == "disk_host":
            import tempfile

            pd = (
                str(Path(spill_dir) / "params")
                if spill_dir is not None
                else tempfile.mkdtemp(prefix="repro-serve-wp-")
            )
            param_store = SpillStore(pd, ephemeral=True)
            try:
                params = wplan.spill_home(params, param_store)
            except BaseException:
                param_store.close()
                raise
    else:
        if expert_stream:
            raise ValueError(
                "--expert-stream streams routed experts from a weight home; "
                "it requires --param-kind pinned_host or disk_host"
            )
        params = st.init_train_state(key, cfg)[0]
        sharder = sh.make_sharder(plan, params, batch)

    if wplan is not None:
        p_sh = None
        if mesh.devices.size > 1:
            p_specs = sh.param_specs(plan, st.abstract_params(cfg))
            p_sh = sh.named_shardings(mesh, p_specs)
        from repro.core.refspec import PrefetchSpec

        w_dist = (
            param_distance if param_distance == AUTO else int(param_distance)
        )
        param_pf = PrefetchSpec(
            buffer_size=wplan.n_groups + 2, distance=w_dist
        )
        decode_fn = st.make_weight_streamed_decode_step(
            cfg, wplan, mesh, sharder, engine=engine, stats=param_stats,
            param_shardings=p_sh, paged=False, prefetch=param_pf,
            residency=residency, route_experts=route_experts,
        )
        expert_stats = getattr(decode_fn, "expert_stats", None)
    else:
        # donation is only safe when the cache stays on device: the host
        # branch re-reads the pre-step tree to place it (satellite bugfix)
        decode_fn = jax.jit(
            st.make_decode_step(cfg, mesh, sharder),
            donate_argnums=(1,) if device_resident else (),
        )
    argmax_fn = jax.jit(
        lambda logits: jnp.argmax(logits[..., -1, :], axis=-1).astype(jnp.int32)
    )

    key_t = jax.random.PRNGKey(seed + 1)
    prompts = np.asarray(
        jax.random.randint(key_t, (batch, prompt_len), 1, cfg.vocab_size), np.int32
    )

    t0 = time.perf_counter()
    if vector_pos:
        if wplan is not None:
            prefill_fn = st.make_weight_streamed_prefill_step(
                cfg, wplan, 1, max_len, mesh, sharder, engine=engine,
                stats=param_stats, param_shardings=p_sh, prefetch=param_pf,
                residency=residency,
            )
        else:
            prefill_fn = jax.jit(
                st.make_prefill_step(cfg, 1, max_len, mesh, sharder)
            )
        stack_fn = jax.jit(
            lambda slots: jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=xs[0].ndim - 4), *slots
            )
        )
        slot_caches, first = [], []
        for b in range(batch):
            logits, cache = prefill_fn(params, _prompt_batch(cfg, prompts[b][None]))
            first.append(np.asarray(argmax_fn(logits))[0])
            slot_caches.append(cache)
        caches = stack_fn(tuple(slot_caches))
        tokens = np.stack(first)
    else:
        # ring/recurrent decode state: batched lock-step prefill (per-slot
        # positions cannot address a shared ring)
        if wplan is not None:
            prefill_fn = st.make_weight_streamed_prefill_step(
                cfg, wplan, batch, max_len, mesh, sharder, engine=engine,
                stats=param_stats, param_shardings=p_sh, prefetch=param_pf,
                residency=residency,
            )
        else:
            prefill_fn = jax.jit(
                st.make_prefill_step(cfg, batch, max_len, mesh, sharder)
            )
        logits, caches = prefill_fn(params, _prompt_batch(cfg, prompts))
        tokens = np.asarray(argmax_fn(logits))
    jax.block_until_ready(caches)
    t_prefill = time.perf_counter() - t0

    # the sharding plan's cache placement (satellite bugfix: was a bare
    # replicated PartitionSpec that dropped the plan under --model-parallel)
    specs = sh.cache_specs_tree(plan, caches, batch)
    cache_leaves = len(jax.tree.leaves(caches))
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(caches))

    def round_trip(c):
        t0 = time.perf_counter()
        c = mk.place(c, mesh, specs, kind)
        jax.block_until_ready(c)
        if kv_engine is not None:
            kv_engine.emulate_blocking_transfer(cache_leaves, cache_bytes)
        c = mk.place(c, mesh, specs, mk.DEVICE)
        jax.block_until_ready(c)
        if kv_engine is not None:
            kv_engine.emulate_blocking_transfer(cache_leaves, cache_bytes)
        w = time.perf_counter() - t0
        stats.n_transfers += 2
        stats.n_groups += 1
        stats.h2d_requests += cache_leaves
        stats.d2h_requests += cache_leaves
        stats.bytes_h2d += cache_bytes
        stats.bytes_d2h += cache_bytes
        stats.transfer_wait_s += w
        stats.wait_per_group.append(w)
        return c

    def emitted_of(tok_b):
        return tok_b[:, 0] if cfg.n_codebooks else tok_b

    out_tokens = [emitted_of(tokens)]

    def step_pos(i: int):
        if vector_pos:
            return jnp.asarray(np.full((batch,), prompt_len + i, np.int32))
        return jnp.asarray(prompt_len + i, jnp.int32)  # lock-step scalar

    if warmup:
        # compile the decode step against a throwaway copy so t_decode does
        # not include compile time (satellite bugfix; cf. benchmarks.common)
        caches_w = jax.tree.map(jnp.copy, caches)
        jax.block_until_ready(
            decode_fn(params, caches_w, _step_batch(cfg, tokens), step_pos(0))[0]
        )

    step_waits = []
    # decode-loop-only expert traffic (warmup's routed fetches excluded) —
    # what the bench's routed-vs-all-expert gate divides by gen-1 steps
    eb0 = expert_stats.bytes_h2d if expert_stats is not None else 0
    ef0 = expert_stats.unique_group_fetches if expert_stats is not None else 0
    t0 = time.perf_counter()
    try:
        for i in range(gen - 1):
            w0 = stats.transfer_wait_s
            if not device_resident:
                # the paper's Host kind, pre-pager: the ENTIRE cache
                # round-trips through host memory synchronously every step
                caches = round_trip(caches)
            logits, caches = decode_fn(
                params, caches, _step_batch(cfg, tokens), step_pos(i)
            )
            tokens = np.asarray(argmax_fn(logits))
            out_tokens.append(emitted_of(tokens))
            step_waits.append(stats.transfer_wait_s - w0)
        t_decode = time.perf_counter() - t0
    finally:
        if own_engine is not None:
            own_engine.close()
        if param_store is not None:
            param_store.close()
        if residency is not None:
            residency.clear()

    generated = np.stack(out_tokens, axis=1)
    return {
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        # gen-1 decode steps: the first token per slot comes from prefill
        "tokens_per_s": batch * (gen - 1) / t_decode if t_decode else float("inf"),
        "generated": generated,
        "step_waits": step_waits,
        "stats": stats,
        "paged": False,
        "n_steps": gen - 1,
        "param_stats": param_stats,
        "expert_stats": expert_stats,
        "param_plan": wplan,
        "expert_decode_bytes": (
            expert_stats.bytes_h2d - eb0 if expert_stats is not None else 0
        ),
        "expert_decode_fetches": (
            expert_stats.unique_group_fetches - ef0
            if expert_stats is not None
            else 0
        ),
    }


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------


def serve(
    cfg,
    mesh,
    *,
    batch: int,
    prompt_len: int,
    gen: int,
    kv_kind: str = "device",
    kv_page_len: int = 32,
    hot_pages: int = 1,
    distance=AUTO,
    seed: int = 0,
    n_requests: Optional[int] = None,
    engine: Optional[TransferEngine] = None,
    spill_dir: Optional[str] = None,
    warmup: bool = True,
    param_kind: str = "device",
    device_budget_mb: Optional[float] = None,
    param_layers_per_group: Optional[int] = None,
    param_distance=AUTO,
    param_cache_mb: Optional[float] = None,
    expert_stream: bool = False,
    route_experts: bool = True,
    prefix_sharing: bool = True,
    shared_prefix_len: int = 0,
):
    """Serve ``n_requests`` greedy-decode requests (default: one per batch
    slot) of ``prompt_len`` prompt tokens and ``gen`` generated tokens.
    ``shared_prefix_len`` makes the first that many prompt tokens identical
    across requests (the shared-system-prompt traffic shape);
    ``prefix_sharing`` lets the pager alias those pages copy-on-write.

    ``kv_page_len > 0`` routes decode through the paged
    :class:`ServeSession`; ``kv_page_len=0`` runs the unpaged reference
    schedule (synchronous whole-cache placement per step for host kinds).
    ``param_kind`` homes the *weights* off-device and streams them
    group-wise per prefill/decode step (paged and unpaged sessions).
    ``expert_stream`` splits MoE experts into their own fetch groups and
    decodes router-first, fetching only the routed experts per step
    (``route_experts=False`` keeps the split program but fetches all E —
    the bench's all-expert baseline).
    Returns timing, per-request generated tokens (``(n_requests, gen)``),
    the :class:`StreamStats` row, and pager residency accounting.
    """
    stats = StreamStats()
    n_requests = n_requests or batch
    if kv_page_len <= 0:
        if n_requests != batch:
            raise ValueError("the unpaged path serves exactly one request per slot")
        return _serve_unpaged(
            cfg,
            mesh,
            batch=batch,
            prompt_len=prompt_len,
            gen=gen,
            kv_kind=kv_kind,
            seed=seed,
            engine=engine,
            warmup=warmup,
            stats=stats,
            param_kind=param_kind,
            device_budget_mb=device_budget_mb,
            param_layers_per_group=param_layers_per_group,
            param_distance=param_distance,
            param_cache_mb=param_cache_mb,
            expert_stream=expert_stream,
            route_experts=route_experts,
            spill_dir=spill_dir,
        )

    key_t = jax.random.PRNGKey(seed + 1)
    prompts = np.array(
        jax.random.randint(key_t, (n_requests, prompt_len), 1, cfg.vocab_size),
        np.int32,
    )
    if shared_prefix_len:
        shared = min(shared_prefix_len, prompt_len)
        prompts[:, :shared] = prompts[0, :shared]
    with ServeSession(
        cfg,
        mesh,
        slots=batch,
        max_len=prompt_len + gen,
        kv_kind=kv_kind,
        page_len=kv_page_len,
        hot_pages=hot_pages,
        distance=distance,
        seed=seed,
        engine=engine,
        spill_dir=spill_dir,
        stats=stats,
        param_kind=param_kind,
        device_budget_mb=device_budget_mb,
        param_layers_per_group=param_layers_per_group,
        param_distance=param_distance,
        param_cache_mb=param_cache_mb,
        expert_stream=expert_stream,
        route_experts=route_experts,
        prefix_sharing=prefix_sharing,
    ) as session:
        rids = [session.submit(prompts[i], gen) for i in range(n_requests)]
        if warmup:
            session.warmup()
        t0 = time.perf_counter()
        admitted_first = session.admit_pending()
        t_prefill = time.perf_counter() - t0
        t0 = time.perf_counter()
        while session.pending_work():
            session.step()
        t_decode = time.perf_counter() - t0
        generated = np.stack(
            [np.asarray(session.requests[r].emitted, np.int32) for r in rids]
        )
        total_tokens = int(sum(len(session.requests[r].emitted) for r in rids))
        # first tokens of the initial admissions were emitted during the
        # prefill window, not the decode window — don't count them against
        # t_decode
        decode_tokens = total_tokens - len(admitted_first)
        res = {
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "tokens_per_s": decode_tokens / t_decode if t_decode else float("inf"),
            "generated": generated,
            "step_waits": list(session.step_waits),
            "stats": stats,
            "paged": True,
            "n_steps": session.n_steps,
            "stale_drops": session.pager.stream.stale_drops,
            "rejected": session.rejected,
            "prefill_compiles": session.prefill_compiles(),
            "shared_hits": stats.shared_hits,
            "shared_skipped_writebacks": (
                session.pager.shared_skipped_writebacks
            ),
            "demoted_groups": session.pager.demoted_groups,
            "peak_resident_bytes": session.pager.peak_resident_bytes,
            "total_cache_bytes": session.pager.total_cache_bytes(),
            "param_stats": session.param_stats,
            "expert_stats": session.expert_stats,
            "param_plan": session._wplan,
            "param_step_fetches": list(session.param_step_fetches),
            "param_residency": (
                session.param_residency.counters()
                if session.param_residency is not None
                else None
            ),
        }
        return res


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS, default="smollm-360m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests to serve (default: one per slot)")
    ap.add_argument("--kv-kind", default="device", choices=KV_KINDS)
    ap.add_argument("--kv-page-len", type=int, default=32,
                    help="tokens per KV page (0 = unpaged reference path)")
    ap.add_argument("--hot-pages", type=int, default=1,
                    help="full pages kept device-resident behind the write head")
    ap.add_argument("--distance", default="auto",
                    help="page prefetch window: an int or 'auto'")
    ap.add_argument("--spill-dir", default=None,
                    help="disk_host page store directory (default: ephemeral)")
    from repro.core.weightstream import PARAM_KINDS

    ap.add_argument("--param-kind", default="device", choices=PARAM_KINDS,
                    help="home tier of the model weights (host/disk kinds "
                    "stream them layer-group-wise per prefill/decode step)")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="device budget shared by the KV hot window and the "
                    "streamed weight window")
    ap.add_argument("--param-cache-mb", type=float, default=None,
                    help="weight-residency cache capacity (default: the "
                    "budget slack above the prefetch window; unbounded "
                    "without a budget; 0 disables)")
    ap.add_argument("--expert-stream", action="store_true",
                    help="split MoE experts into per-expert fetch groups "
                    "and fetch only the routed top-k per decode step "
                    "(requires a streamed --param-kind and an MoE arch)")
    ap.add_argument("--verify-schedule", action="store_true",
                    help="statically analyze the streamed-weight + KV page "
                    "schedule before serving, print the occupancy report, "
                    "and fail fast on any hazard (requires a streamed "
                    "--param-kind)")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable copy-on-write sharing of page-aligned "
                    "prompt prefixes (the A/B baseline; sharing is "
                    "bitwise-invisible either way)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="make the first N prompt tokens identical across "
                    "requests (the shared-system-prompt traffic shape)")
    # -- open-loop load generator + SLO scheduler ---------------------------
    ap.add_argument("--loadgen", action="store_true",
                    help="serve an open-loop Poisson trace through the SLO "
                    "scheduler instead of the fixed request list")
    ap.add_argument("--lg-seed", type=int, default=0)
    ap.add_argument("--lg-phases", default="4:2,1:8,4:2",
                    help="arrival phases as 'duration_s:rate_rps,...' "
                    "(bursty by default)")
    ap.add_argument("--lg-prompt-lens", default="8,24,48",
                    help="prompt-length mixture support (comma ints)")
    ap.add_argument("--lg-gen-lens", default="4,8,16",
                    help="output-length mixture support (comma ints)")
    ap.add_argument("--lg-shared-frac", type=float, default=1.0,
                    help="fraction of offered requests starting with the "
                    "shared system prompt (--shared-prefix-len)")
    ap.add_argument("--slo-ttft-ms", type=float, default=500.0,
                    help="time-to-first-token SLO target")
    ap.add_argument("--slo-tpot-ms", type=float, default=100.0,
                    help="per-output-token SLO target")
    ap.add_argument("--max-queue", type=int, default=32,
                    help="admission queue bound; arrivals beyond it are "
                    "shed (rejected_overload)")
    ap.add_argument("--virtual-step-ms", type=float, default=10.0,
                    help="virtual clock advance per decode step (0 = wall "
                    "clock)")
    args = ap.parse_args()

    if args.verify_schedule and args.param_kind == "device":
        ap.error("--verify-schedule requires --param-kind pinned_host "
                 "or disk_host (device-resident weights have no schedule)")

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_local_mesh(model=args.model_parallel)
    distance = args.distance if args.distance == AUTO else int(args.distance)
    if args.verify_schedule:
        from repro.core import schedcheck as sc
        from repro.core.weightstream import WeightStreamPlan

        budget = args.device_budget_mb
        kv_desc = None
        if args.kv_page_len > 0:
            max_len = _round_up(
                args.prompt_len + args.gen, args.kv_page_len
            )
            template = st.abstract_caches(cfg, 1, max_len)
            if paged_cache_supported(template):
                page_nbytes = sum(
                    int(np.prod(s.shape)) * s.dtype.itemsize
                    for s in jax.tree.leaves(
                        page_template(template, args.kv_page_len)
                    )
                )
                kv_desc = dict(
                    slots=args.batch,
                    page_len=args.kv_page_len,
                    hot_pages=args.hot_pages,
                    page_nbytes=page_nbytes,
                    max_len=max_len,
                )
                if budget is not None:
                    budget -= (
                        args.batch * (args.hot_pages + 2) * page_nbytes / 1e6
                    )
        wplan = WeightStreamPlan(
            cfg,
            st.abstract_params(cfg),
            device_budget_mb=budget,
            expert_stream=args.expert_stream,
        )
        if args.param_cache_mb is not None:
            cache_cap = int(args.param_cache_mb * 1e6)
        else:
            cache_cap = wplan.residency_capacity_bytes()
        cache_reserved = (cache_cap or 0) if budget is not None else 0
        report = sc.analyze_serve_schedule(
            wplan,
            distance=wplan.max_distance_for_budget(
                cached_bytes=cache_reserved
            ),
            cache_capacity=cache_cap,
            budget_bytes=(
                int(args.device_budget_mb * 1e6)
                if args.device_budget_mb is not None
                else None
            ),
            kv=kv_desc,
        )
        print(report)
        sc.verify_schedule(report)
    if args.loadgen:
        if args.kv_page_len <= 0:
            ap.error("--loadgen drives the paged ServeSession; "
                     "use --kv-page-len > 0")
        from repro.serve import (
            SLO,
            LoadGenConfig,
            Phase,
            SLOScheduler,
            generate,
        )

        phases = tuple(
            Phase(duration_s=float(d), rate_rps=float(r))
            for d, r in (p.split(":") for p in args.lg_phases.split(","))
        )
        prompt_lens = tuple(
            int(x) for x in args.lg_prompt_lens.split(",")
        )
        gen_lens = tuple(int(x) for x in args.lg_gen_lens.split(","))
        lg_cfg = LoadGenConfig(
            seed=args.lg_seed,
            phases=phases,
            prompt_lens=prompt_lens,
            prompt_mix=(1.0,) * len(prompt_lens),
            gen_lens=gen_lens,
            gen_mix=(1.0,) * len(gen_lens),
            shared_prefix_len=args.shared_prefix_len,
            shared_frac=args.lg_shared_frac,
            vocab_size=cfg.vocab_size,
        )
        offered = generate(lg_cfg)
        with ServeSession(
            cfg,
            mesh,
            slots=args.batch,
            max_len=args.prompt_len + args.gen,
            kv_kind=args.kv_kind,
            page_len=args.kv_page_len,
            hot_pages=args.hot_pages,
            distance=distance,
            seed=args.seed,
            spill_dir=args.spill_dir,
            param_kind=args.param_kind,
            device_budget_mb=args.device_budget_mb,
            param_cache_mb=args.param_cache_mb,
            expert_stream=args.expert_stream,
            prefix_sharing=not args.no_prefix_sharing,
        ) as session:
            sched = SLOScheduler(
                session,
                offered,
                slo=SLO(
                    ttft_s=args.slo_ttft_ms / 1e3,
                    tpot_s=args.slo_tpot_ms / 1e3,
                ),
                max_queue=args.max_queue,
                virtual_step_s=(
                    args.virtual_step_ms / 1e3
                    if args.virtual_step_ms > 0
                    else None
                ),
            )
            rep = sched.run()
        print(
            f"loadgen {args.arch}: offered {rep['offered']}, completed "
            f"{rep['completed']} ({rep['rejected_oversize']} oversize, "
            f"{rep['rejected_overload']} overload) over "
            f"{rep['makespan_s']:.2f} s"
        )
        print(
            f"SLO: attainment {rep['slo_attainment']*100:.1f}%, goodput "
            f"{rep['goodput_rps']:.2f} req/s / "
            f"{rep['goodput_tokens_per_s']:.1f} tok/s under SLO, TTFT p50 "
            f"{rep['ttft_s']['p50']*1e3:.1f} ms p99 "
            f"{rep['ttft_s']['p99']*1e3:.1f} ms, TPOT p50 "
            f"{rep['tpot_s']['p50']*1e3:.1f} ms"
        )
        print(
            f"sharing: {rep['shared_hits']} shared-page fetch hits, "
            f"{rep['shared_skipped_writebacks']} skipped writebacks, "
            f"{rep['unique_group_fetches']} unique fetches, "
            f"{rep['disk_requests']} disk req, prefill compiles "
            f"{rep['prefill_compiles']}"
        )
        return 0
    res = serve(
        cfg,
        mesh,
        batch=args.batch,
        prompt_len=args.prompt_len,
        gen=args.gen,
        n_requests=args.requests,
        kv_kind=args.kv_kind,
        kv_page_len=args.kv_page_len,
        hot_pages=args.hot_pages,
        distance=distance,
        seed=args.seed,
        spill_dir=args.spill_dir,
        param_kind=args.param_kind,
        device_budget_mb=args.device_budget_mb,
        param_cache_mb=args.param_cache_mb,
        expert_stream=args.expert_stream,
        prefix_sharing=not args.no_prefix_sharing,
        shared_prefix_len=args.shared_prefix_len,
    )
    stats = res["stats"]
    print(
        f"served {args.arch}: prefill {res['prefill_s']*1e3:.1f} ms, "
        f"decode {res['decode_s']*1e3:.1f} ms total, "
        f"{res['tokens_per_s']:.1f} tok/s "
        f"(kv_kind={args.kv_kind}, page_len={args.kv_page_len}, "
        f"paged={res['paged']})"
    )
    print(
        f"transfers: h2d {stats.h2d_requests} req / {stats.bytes_h2d} B, "
        f"d2h {stats.d2h_requests} req / {stats.bytes_d2h} B, "
        f"disk {stats.disk_requests} req, "
        f"compute wait {stats.transfer_wait_s*1e3:.2f} ms"
    )
    if res["paged"]:
        print(
            f"residency: peak {res['peak_resident_bytes']} B device-resident "
            f"of {res['total_cache_bytes']} B total cache "
            f"({res['demoted_groups']} demotions, "
            f"{res['stale_drops']} stale prefetches)"
        )
    if res.get("param_plan") is not None:
        ps = res["param_stats"]
        plan = res["param_plan"]
        h2d = ps.per_tier()["h2d"]
        print(
            f"weights: {plan.n_groups} groups x {plan.layers_per_group} "
            f"layers, {ps.h2d_requests} H2D req "
            f"({h2d['requests_per_fetched_device_group']:.2f}/"
            f"(device,group) fetched), peak streamed "
            f"{ps.peak_inflight_bytes} B of {plan.total_param_bytes} B "
            f"total params"
        )
        if res.get("expert_stats") is not None:
            es = res["expert_stats"]
            print(
                f"experts: {es.unique_group_fetches} fetched groups / "
                f"{es.cache_hits} resident hits, {es.bytes_h2d} B H2D "
                f"over {res['n_steps']} steps"
            )
        if res.get("param_residency") is not None:
            rc = res["param_residency"]
            print(
                f"weight residency: {rc['hits']} hits / {rc['misses']} "
                f"misses, {rc['resident_bytes']} B resident "
                f"(peak {rc['peak_resident_bytes']} B, "
                f"{rc['evictions']} evictions)"
            )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
