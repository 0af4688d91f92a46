"""Host-driver streaming engine: the paper's host process + channels, in JAX.

The paper's architecture (§4, Fig 2) keeps bulk data on the host; a host-side
service decodes references and feeds per-core channels (32 x 1KB cells) while
device code computes.  This module is the direct analogue at framework level:
model state stays **outside the XLA program** as host arrays; a background
:class:`~repro.core.engine.TransferEngine` (the host service) coalesces,
stages and issues the H2D transfer for layer-group ``i+distance`` while the
jitted apply for group ``i`` runs.  Because transfers and compute are
separate dispatches, this engine runs on *every* backend — including the
CPU container, where it produces the real measurements behind
``benchmarks/offload_modes.py`` and ``benchmarks/engine_compare.py``
(``results/bench/BENCH_engine.json``; the graph engine in ``prefetch.py``
is the production TPU path).

Three transfer schedules, mirroring the paper's evaluation axes:

``eager``      copy *all* groups, then compute (paper's original offload).
``on_demand``  copy group i synchronously right before computing it
               (paper's pass-by-reference without prefetch — the 21-25x
               slowdown case when transfers are small).
``prefetch``   keep ``distance`` groups in flight ahead of compute.
               ``PrefetchSpec(distance="auto")`` lets the engine's
               :class:`~repro.core.engine.AdaptiveDistance` controller size
               the window from observed stalls.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Optional, Sequence

import jax

from repro.core.engine import AdaptiveDistance, EngineConfig, TransferEngine
from repro.core.refspec import Access, PrefetchSpec
from repro.core.spans import span

__all__ = ["StreamStats", "HostStreamExecutor"]

Pytree = Any

#: histogram bucket upper bounds (seconds) for per-group transfer waits
_WAIT_BINS = (10e-6, 100e-6, 1e-3, 10e-3, 100e-3)

#: cap on retained per-group samples (waits, distance trace)
_MAX_SAMPLES = 4096


@dataclasses.dataclass
class StreamStats:
    """Per-run accounting (the paper's Table 2 instrumentation).

    ``n_transfers`` counts *logical* group transfers (one per group per
    direction — the seed's unit, kept for continuity); ``h2d_requests`` /
    ``d2h_requests`` count the *actual* requests issued on the link, which
    is what the paper's on-demand penalty scales with.  With coalescing a
    group is one request regardless of its leaf count.
    """

    mode: str = "prefetch"
    n_transfers: int = 0
    bytes_h2d: int = 0
    bytes_d2h: int = 0
    #: addressable devices groups staged onto (max over groups; 1 for
    #: default placement).  With sharding-aware coalescing a group costs
    #: one request per device, so ``requests_per_group == n_devices``
    n_devices: int = 1
    #: sum over groups of that group's device count — the denominator of
    #: the per-(device, group) request invariant, exact even when one run
    #: mixes sharded and default-placement groups
    n_device_groups: int = 0
    # -- residency accounting (the link-traffic truth) ----------------------
    #: submits that actually crossed a link (>= 1 H2D or disk request).
    #: ``requests_per_group`` is a per-PASS invariant and resets its
    #: denominator with every run, so a step whose forward AND backward each
    #: re-fetch every group still reads a clean 1.0/group — this counter is
    #: what benches gate real per-step traffic on instead
    unique_group_fetches: int = 0
    #: submits whose group was already device-resident end to end (weight
    #: residency-cache hits, and device-kind pass-through): zero link bytes
    cache_hits: int = 0
    #: submits that had to move bytes (always == unique_group_fetches; kept
    #: as its own counter so hit-rate reads don't conflate the two views)
    cache_misses: int = 0
    #: pops satisfied by a same-step fetch of the same *content* key — the
    #: copy-on-write prefix-sharing win: N requests whose prompts share a
    #: page-aligned prefix cost ONE fetch (one ``n_groups`` entry) plus
    #: N-1 shared hits, so ``h2d_requests == n_groups`` stays exact
    shared_hits: int = 0
    #: sum of per-group device counts over *fetched* groups only — the
    #: denominator that keeps the one-request-per-(device, group) coalescing
    #: invariant checkable when resident groups pass through at zero requests
    fetched_device_groups: int = 0
    transfer_wait_s: float = 0.0  # time the *compute* path blocked on data
    compute_s: float = 0.0
    total_s: float = 0.0
    # -- engine-era accounting ----------------------------------------------
    h2d_requests: int = 0
    d2h_requests: int = 0
    n_groups: int = 0
    n_runs: int = 0
    writeback_drain_s: float = 0.0
    #: max H2D payload bytes of groups simultaneously in flight (submitted
    #: but not yet consumed by their apply) — the schedule's device-residency
    #: model for streamed state; what ``--device-budget-mb`` gates against
    peak_inflight_bytes: int = 0
    # -- disk tier (DiskHost groups: stage-1 of the three-level pipeline) ---
    disk_requests: int = 0
    bytes_disk: int = 0
    #: time the *transfer worker* (stage 2) blocked on disk fetches; zero
    #: once the disk read-ahead window hides the disk latency
    disk_wait_s: float = 0.0
    # -- robustness (EngineConfig.max_attempts retry) -----------------------
    #: transient transfer faults absorbed by retry (H2D, D2H, disk stage);
    #: equals the injected fault count in the fault-injection benches
    retries: int = 0
    #: transfers that exhausted ``max_attempts`` (the error surfaced)
    give_ups: int = 0
    #: per-group compute-thread stall (the wait histogram's raw samples);
    #: bounded so a stats object shared across a long training run does not
    #: grow with step count — old samples age out, aggregates stay exact
    wait_per_group: "deque[float]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=_MAX_SAMPLES)
    )
    #: prefetch window size used for each group (adaptive-distance trace)
    distance_trace: "deque[int]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=_MAX_SAMPLES)
    )
    #: per-group stage-2-on-stage-1 (H2D-on-disk) stall samples
    disk_wait_per_group: "deque[float]" = dataclasses.field(
        default_factory=lambda: deque(maxlen=_MAX_SAMPLES)
    )

    @property
    def requests_per_group(self) -> float:
        return self.h2d_requests / self.n_groups if self.n_groups else 0.0

    @property
    def disk_requests_per_group(self) -> float:
        return self.disk_requests / self.n_groups if self.n_groups else 0.0

    def per_tier(self) -> dict[str, dict[str, float]]:
        """Request/byte/wait counters per hierarchy tier (paper Table 2,
        extended down the hierarchy).  The wait of each tier is the stall
        of the consumer one level up: compute stalls on host->device,
        host->device stalls on disk."""
        per_dev_groups = self.n_device_groups or self.n_groups
        return {
            "h2d": {
                "requests": self.h2d_requests,
                "bytes": self.bytes_h2d,
                "wait_s": self.transfer_wait_s,
                "requests_per_group": self.requests_per_group,
                # sharded groups cost one request per (device, group): 1.0
                # here is the coalescing invariant under any mesh
                "requests_per_device_group": (
                    self.h2d_requests / per_dev_groups if per_dev_groups else 0.0
                ),
                # the same invariant restricted to groups that actually
                # fetched — exactly 1.0 under coalescing no matter how many
                # resident groups passed through at zero requests
                "requests_per_fetched_device_group": (
                    self.h2d_requests / self.fetched_device_groups
                    if self.fetched_device_groups
                    else 0.0
                ),
                "unique_group_fetches": self.unique_group_fetches,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "shared_hits": self.shared_hits,
            },
            "d2h": {
                "requests": self.d2h_requests,
                "bytes": self.bytes_d2h,
                "wait_s": self.writeback_drain_s,
            },
            "disk": {
                "requests": self.disk_requests,
                "bytes": self.bytes_disk,
                "wait_s": self.disk_wait_s,
                "requests_per_group": self.disk_requests_per_group,
            },
        }

    def wait_hist(self, bins: Sequence[float] = _WAIT_BINS) -> dict[str, int]:
        """Per-group wait histogram: bucket label -> count."""
        counts = [0] * (len(bins) + 1)
        for w in self.wait_per_group:
            for j, ub in enumerate(bins):
                if w <= ub:
                    counts[j] += 1
                    break
            else:
                counts[-1] += 1
        labels = [f"<={ub:.0e}s" for ub in bins] + [f">{bins[-1]:.0e}s"]
        return dict(zip(labels, counts))

    def reset(self) -> None:
        """Zero all counters (keeps ``mode``) — one benchmark repeat."""
        mode = self.mode
        fresh = StreamStats(mode=mode)
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(fresh, f.name))

    def as_row(self) -> dict[str, Any]:
        row = {
            f.name: getattr(self, f.name)
            for f in dataclasses.fields(self)
            if f.name
            not in ("wait_per_group", "distance_trace", "disk_wait_per_group")
        }
        row["requests_per_group"] = self.requests_per_group
        row["disk_requests_per_group"] = self.disk_requests_per_group
        row["wait_hist"] = self.wait_hist()
        row["per_tier"] = self.per_tier()
        row["final_distance"] = self.distance_trace[-1] if self.distance_trace else None
        return row


def _nbytes(tree: Pytree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


class HostStreamExecutor:
    """Drives ``carry = apply(carry, group_params)`` over host-resident groups.

    Parameters
    ----------
    apply:
        jitted per-group function ``(carry, group) -> carry`` (or
        ``(carry, group) -> (carry, group_out)`` with ``writeback=True`` —
        the paper's ``rw`` access modifier, used e.g. for streamed optimizer
        state which must be copied back to its home kind).
    device_shardings:
        optional pytree of shardings for the staged groups, broadcast over
        every group.  Coalescing composes with explicit placements: each
        group stages through one buffer per addressable device (one H2D
        request per device per group) and the staged leaves are bitwise
        equal to eager sharded placement.  Per-run heterogeneous layouts
        (e.g. optimizer leaf groups) pass ``group_shardings`` to
        :meth:`run` instead.
    engine / engine_config:
        the transfer engine to run on.  By default a private engine with
        ``EngineConfig()`` (coalescing + async writeback) is created;
        pass ``EngineConfig(coalesce=False, async_writeback=False)`` to
        reproduce the seed executor's per-leaf blocking schedule.
    indexed:
        call ``apply(i, carry, group)`` with the group's position in the
        run — for heterogeneous group sequences whose apply dispatches per
        stage (the weight-streaming path: embed / layer groups / head are
        different jitted programs over one streamed sequence).
    """

    def __init__(
        self,
        apply: Callable[..., Any],
        *,
        writeback: bool = False,
        device_shardings: Optional[Pytree] = None,
        engine: Optional[TransferEngine] = None,
        engine_config: Optional[EngineConfig] = None,
        indexed: bool = False,
    ) -> None:
        self._apply = apply
        self._writeback = writeback
        self._indexed = indexed
        self._shardings = device_shardings
        self._engine = engine or TransferEngine(engine_config)
        self._owns_engine = engine is None
        #: adaptive-distance state, persistent across run() calls
        self._controller: Optional[AdaptiveDistance] = None

    @property
    def engine(self) -> TransferEngine:
        return self._engine

    def close(self) -> None:
        if self._owns_engine:
            self._engine.close()

    def __enter__(self) -> "HostStreamExecutor":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    #: sentinel: "no per-group override" (None is a valid override meaning
    #: default placement)
    _UNSET = object()

    # -- transfer primitive (the paper's channel cell write) ----------------
    def _submit(
        self, index: int, group: Pytree, shardings: Any = _UNSET, key=None
    ):
        if shardings is self._UNSET:
            shardings = self._shardings
        return self._engine.submit_group(
            index, group, device_shardings=shardings, key=key
        )

    def run(
        self,
        carry: Pytree,
        groups: Sequence[Pytree],
        *,
        prefetch: Optional[PrefetchSpec] = None,
        mode: str = "prefetch",
        stats: Optional[StreamStats] = None,
        group_shardings: Optional[Sequence[Pytree]] = None,
        group_keys: Optional[Sequence[Optional[str]]] = None,
    ) -> tuple[Pytree, Optional[list]]:
        """Execute all groups under the given schedule.  Returns the final
        carry (+ written-back host groups when ``writeback``).

        ``group_shardings``: optional per-group shardings (one pytree per
        group, aligned with ``groups``) for runs whose groups have
        heterogeneous layouts; overrides the constructor's broadcast
        ``device_shardings``.

        ``group_keys``: optional logical names (one per group, aligned
        with ``groups``) threaded to the engine's hazard sanitizer so
        fetches and writebacks of the same group form a happens-before
        chain across runs; unnamed groups are unchecked.

        A ``groups`` entry may be a zero-arg callable, resolved when its
        transfer is SUBMITTED (not when the run was scheduled): the weight
        streamer's residency-cache substitution must see the cache as it is
        the moment the fetch would be issued — a group that became resident
        mid-pass passes through by reference instead of re-crossing the
        link."""
        if mode not in ("eager", "on_demand", "prefetch"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "prefetch" and prefetch is None:
            prefetch = PrefetchSpec()
        st = stats if stats is not None else StreamStats()
        st.mode = mode
        st.n_runs += 1
        st.n_groups += len(groups)
        cfg = self._engine.config
        if self._writeback and cfg.async_writeback:
            # a failed previous run may have left tickets behind; stale
            # groups must never drain into this run's outputs
            self._engine.discard_writebacks()
        controller: Optional[AdaptiveDistance] = None
        if mode != "prefetch":
            distance = 0
        elif prefetch.is_auto:
            # the controller persists across run() calls: the train loop
            # issues one short run per step, and the learned window must
            # carry over instead of restarting at the minimum every step
            if self._controller is None:
                self._controller = AdaptiveDistance(
                    initial=cfg.min_distance,
                    min_distance=cfg.min_distance,
                    max_distance=cfg.max_distance,
                    wait_eps_s=cfg.wait_eps_s,
                    shrink_after=cfg.shrink_after,
                )
                # external signals (straggler events via engine.widen())
                # reach this window too
                self._engine.register_controller(self._controller)
            controller = self._controller
            distance = controller.distance
        else:
            distance = max(prefetch.distance, 1)
        t_start = time.perf_counter()

        outs: Optional[list] = [] if self._writeback else None
        n = len(groups)

        if group_shardings is not None and len(group_shardings) != n:
            raise ValueError(
                f"group_shardings has {len(group_shardings)} entries for "
                f"{n} groups"
            )
        if group_keys is not None and len(group_keys) != n:
            raise ValueError(
                f"group_keys has {len(group_keys)} entries for {n} groups"
            )

        #: H2D payload bytes of submitted-but-not-yet-consumed groups — the
        #: streamed-state device-residency model (peak gated by the weight
        #: streamer's --device-budget-mb)
        live_bytes = 0

        def submit(i: int):
            nonlocal live_bytes
            group = groups[i]() if callable(groups[i]) else groups[i]
            key = group_keys[i] if group_keys is not None else None
            if group_shardings is None:
                fut = self._submit(i, group, key=key)
            else:  # per-group override, authoritative (None = default)
                fut = self._submit(i, group, group_shardings[i], key=key)
            st.n_transfers += 1
            st.h2d_requests += fut.n_requests
            st.bytes_h2d += fut.nbytes
            st.disk_requests += fut.disk_requests
            st.bytes_disk += fut.disk_nbytes
            st.n_devices = max(st.n_devices, fut.n_devices)
            st.n_device_groups += fut.n_devices
            if fut.is_resident:  # zero link traffic: resident pass-through
                st.cache_hits += 1
            else:
                st.cache_misses += 1
                st.unique_group_fetches += 1
                st.fetched_device_groups += fut.n_devices
            live_bytes += fut.nbytes
            st.peak_inflight_bytes = max(st.peak_inflight_bytes, live_bytes)
            return fut

        #: writeback tickets issued this run (retry accounting at drain)
        wb_tickets: list = []

        def waited(i: int, fut) -> float:
            """fut.wait() plus retry/give-up accounting: absorbed transient
            faults land in ``st.retries``; a surfaced (permanent) fault
            counts one give-up, settles the engine and re-raises to the
            caller."""
            try:
                with span("stream.wait", group=i):
                    w = fut.wait()
            except BaseException:
                st.retries += fut.retries
                st.give_ups += 1
                self._engine.settle()
                raise
            st.retries += fut.retries
            return w

        if mode == "eager":
            # bulk transfer first — the paper's original kernel invocation
            futs = [submit(i) for i in range(n)]
            for i, fut in enumerate(futs):
                w = waited(i, fut)
                st.transfer_wait_s += w
                st.wait_per_group.append(w)
                st.disk_wait_s += fut.disk_wait_s
                st.disk_wait_per_group.append(fut.disk_wait_s)
            t0 = time.perf_counter()
            for i, fut in enumerate(futs):
                with span("stream.unpack", group=i):
                    buf = fut.group()
                carry = self._step(
                    i, carry, buf, outs, st, wb_tickets,
                    wb_key=group_keys[i] if group_keys is not None else None,
                )
                live_bytes -= fut.nbytes
            with span("stream.sync.wait"):
                jax.block_until_ready(carry)
            st.compute_s += time.perf_counter() - t0
        else:
            inflight: "OrderedDict[int, Any]" = OrderedDict()
            issued = 0
            for i in range(n):
                # top up the pipeline to `distance` groups ahead
                while issued <= min(i + distance, n - 1):
                    inflight[issued] = submit(issued)
                    issued += 1
                fut = inflight.pop(i)
                # the paper's blocking fetch: the core stalls until data
                # lands (zero once the window covers the link latency)
                w = waited(i, fut)
                st.transfer_wait_s += w
                st.wait_per_group.append(w)
                st.distance_trace.append(distance)
                st.disk_wait_s += fut.disk_wait_s
                st.disk_wait_per_group.append(fut.disk_wait_s)
                if controller is not None:
                    distance = controller.observe(w)
                t0 = time.perf_counter()
                with span("stream.unpack", group=i):
                    buf = fut.group()
                carry = self._step(
                    i, carry, buf, outs, st, wb_tickets,
                    wb_key=group_keys[i] if group_keys is not None else None,
                )
                live_bytes -= fut.nbytes
                st.compute_s += time.perf_counter() - t0
            t0 = time.perf_counter()
            with span("stream.sync.wait"):
                jax.block_until_ready(carry)
            st.compute_s += time.perf_counter() - t0

        if self._writeback and self._engine.config.async_writeback:
            t0 = time.perf_counter()
            try:
                outs = self._engine.drain_writebacks()
            except BaseException:
                st.retries += sum(t.retries for t in wb_tickets)
                st.give_ups += 1
                raise
            st.retries += sum(t.retries for t in wb_tickets)
            st.writeback_drain_s += time.perf_counter() - t0

        st.total_s = time.perf_counter() - t_start
        return (carry, outs) if self._writeback else (carry, None)

    def _step(
        self,
        index: int,
        carry: Pytree,
        buf: Pytree,
        outs: Optional[list],
        st: StreamStats,
        wb_tickets: Optional[list] = None,
        wb_key: Optional[str] = None,
    ) -> Pytree:
        apply = (
            (lambda c, b: self._apply(index, c, b)) if self._indexed else self._apply
        )
        if self._writeback:
            carry, group_out = apply(carry, buf)
            st.bytes_d2h += _nbytes(group_out)
            st.n_transfers += 1
            if self._engine.config.async_writeback:
                # pipelined writeback: D2H runs on the engine worker while
                # the next group computes; drained in order after the loop
                ticket = self._engine.submit_writeback(
                    len(outs), group_out, key=wb_key
                )
                st.d2h_requests += ticket.n_requests
                if wb_tickets is not None:
                    wb_tickets.append(ticket)
                outs.append(None)  # placeholder — replaced by drain
            else:
                host_out = jax.device_get(group_out)  # blocking (seed path)
                n_leaves = len(jax.tree.leaves(group_out))
                # the blocking copy occupies the same (possibly emulated)
                # link as the worker's transfers — and the compute thread
                self._engine.emulate_blocking_transfer(
                    n_leaves, _nbytes(group_out)
                )
                st.d2h_requests += n_leaves
                outs.append(host_out)
        else:
            carry = apply(carry, buf)
        return carry
