"""Asynchronous coalesced transfer engine — the paper's host service, engineered.

The paper's runtime (§4) is a host process that serves device channel
requests; its performance result (§5.1, Table 2) is that the 21-25x
on-demand penalty comes from *request count*, not per-transfer bandwidth,
and that chunked prefetch with overlap recovers nearly all of it.  The seed
``HostStreamExecutor`` reproduced the *schedule* but not the engineering:
one ``jax.device_put`` per pytree leaf per group, a fresh device allocation
per group, and a blocking ``jax.device_get`` per ``rw`` writeback.  This
module is the engineering:

coalescing
    Each group's host-resident leaves are packed into ONE contiguous
    staging buffer of 32-bit words, so a group costs one H2D request
    instead of one per leaf (paper: "significantly fewer requests").  A
    cached jitted unpack reconstitutes the leaves on device (bitcast +
    reshape — bitwise exact).  Leaves that are already committed
    ``jax.Array``s pass through untouched (true pass-by-reference: data
    already at the fast tier is never re-sent).

sharding-aware coalescing
    Explicit multi-device layouts (``device_shardings``) compose with
    coalescing instead of disabling it: a :class:`ShardedGroupLayout`
    derives each leaf's per-device shard slices from its sharding's
    ``addressable_devices_indices_map``, packs ONE staging buffer per
    (addressable device, group), issues one ``device_put`` per device per
    group, and assembles the committed leaves with
    ``jax.make_array_from_single_device_arrays`` — bitwise identical to
    eager sharded placement, at ``n_devices`` requests per group instead
    of ``n_leaves x n_shards``.  This mirrors the source paper's (and
    ePython's) host service, which feeds *per-core* channels: the host
    process serves one request per device, never one per object per
    device.

buffer reuse
    Staging buffers are preallocated per group layout and recycled
    round-robin (the transfer worker completes a copy before reusing a
    slot).  Device-side, the flat buffer of group ``i`` is *donated* into
    its unpacked leaves, so the ring of ``distance+1`` in-flight flats is
    recycled by the allocator instead of growing per group.

asynchrony
    Transfers run on a dedicated worker thread (the host service).  The
    compute thread submits a group and receives a :class:`TransferFuture`;
    packing, ``device_put`` and (for ``rw`` groups) ``device_get`` all
    happen off the compute path.  ``rw`` writebacks are drained at the end
    of the run, in group order.

adaptive prefetch distance
    :class:`AdaptiveDistance` watches the per-group transfer wait and
    grows/shrinks the in-flight window at run time; it backs
    ``PrefetchSpec(distance="auto")``.

three-level streaming (the ``DiskHost`` tier)
    Groups whose leaves are memory-mapped spill-store views
    (:func:`repro.core.spillstore.is_disk_leaf`) move through a *two-stage*
    pipeline: a dedicated disk worker copies the mapped bytes into pooled
    host staging buffers (the disk read), then the transfer worker packs
    and issues the H2D exactly as for host groups.  Each stage has its own
    staging pool and its own :class:`AdaptiveDistance` controller: the
    executor's controller sizes the submission window from *compute-thread*
    stalls, while the engine's disk controller sizes the disk read-ahead
    window (number of fetched-but-unconsumed buffers) from *transfer-
    worker* stalls — so disk latency hides behind host->device latency
    exactly as host latency hides behind compute.

An optional :class:`LinkModel` emulates a slow interconnect (per-request
service time + serial bandwidth occupancy + overlappable completion
latency) so the paper's phenomenology — request-count collapse, prefetch
hiding latency — is reproducible deterministically on this container,
whose real host->device "link" is main memory.  ``EngineConfig.disk_link``
models the disk tier's (slower) link the same way.
"""
from __future__ import annotations

import dataclasses
import logging
import queue
import threading
import time
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.schedcheck import HazardError, HazardSanitizer, sanitize_enabled
from repro.core.spans import span

log = logging.getLogger("repro.engine")

__all__ = [
    "LinkModel",
    "PAPER_EPIPHANY_LINK",
    "EngineConfig",
    "GroupLayout",
    "ShardedGroupLayout",
    "TransferFuture",
    "AdaptiveDistance",
    "TransferEngine",
    "static_auto_distance",
]

Pytree = Any

#: staging offsets are padded to this many bytes so dtype views stay aligned
_ALIGN = 64
#: bytes per staging word: buffers are uint32 so the device-side unpack of
#: a 4-byte leaf is a same-width bitcast (see :class:`GroupLayout`)
_WORD = 4


#: a strided leaf is packed in tiles of this many elements along the
#: staging's last axis by this many along the leaf's densest axis
_TILE = (64, 1024)


def _align(n: int) -> int:
    return (n + _ALIGN - 1) // _ALIGN * _ALIGN


def _pack_leaf(dst: np.ndarray, src: np.ndarray) -> None:
    """``np.copyto(dst, src)`` into a C-ordered staging view.

    A home the runtime handed back in another axis order (a column-major
    table from ``jax.device_get``) is gathered tile by tile: numpy's copy
    walks the whole leaf in the staging's order, so each element read
    lands on another page, and runs at a fraction of the tiled rate.
    same_kind casting permits the canonicalizing f64->f32 / i64->i32
    cast."""
    last = dst.ndim - 1
    fast = last if src.flags.c_contiguous or last < 1 else int(np.argmin(src.strides))
    if fast == last:
        np.copyto(dst, src, casting="same_kind")
        return
    kd, ks = _TILE
    tile = [slice(None)] * dst.ndim
    for k0 in range(0, dst.shape[last], kd):
        tile[last] = slice(k0, k0 + kd)
        for j0 in range(0, dst.shape[fast], ks):
            tile[fast] = slice(j0, j0 + ks)
            np.copyto(dst[tuple(tile)], src[tuple(tile)], casting="same_kind")


def _sleep_precise(duration_s: float) -> None:
    """Sleep with sub-millisecond accuracy without starving other threads.

    ``time.sleep`` with a nonzero duration overshoots by ~1 ms on this
    container — larger than the paper's 0.104 ms per-request cost the link
    model emulates.  The tail is waited in ``sleep(0)`` yields (a plain spin
    would hold the GIL for up to the 5 ms switch interval and serialize the
    engine worker behind the waiter).
    """
    end = time.perf_counter() + duration_s
    while True:
        remaining = end - time.perf_counter()
        if remaining <= 0:
            return
        if remaining > 1.5e-3:
            time.sleep(remaining - 1e-3)
        else:
            time.sleep(0)  # yield the GIL, keep ~10 us accuracy


# ---------------------------------------------------------------------------
# link emulation (paper §5.1 constants)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """Emulated interconnect for schedule studies.

    ``request_s``
        serial per-request service time (the paper's host-service
        turnaround: ~0.104 ms/request on Epiphany, Table 2).  This is the
        term the coalescer collapses.
    ``bandwidth_Bps``
        serial occupancy: a transfer holds the link for ``nbytes/bw``.
    ``latency_s``
        completion delay *after* the link is released — overlappable by
        prefetch depth, which is what ``distance`` (and the adaptive
        controller) hides.
    """

    request_s: float = 0.104e-3
    bandwidth_Bps: float = 88e6
    latency_s: float = 0.0

    def occupancy_s(self, n_requests: int, nbytes: int) -> float:
        return n_requests * self.request_s + nbytes / self.bandwidth_Bps

    def transfer_s(self, n_requests: int, nbytes: int) -> float:
        return self.occupancy_s(n_requests, nbytes) + self.latency_s


#: the paper's measured Epiphany link (88 MB/s, 0.104 ms/request)
PAPER_EPIPHANY_LINK = LinkModel()


# ---------------------------------------------------------------------------
# engine configuration
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs of the transfer engine.  The defaults are the fast path; the
    seed executor's behaviour is ``EngineConfig(coalesce=False,
    async_writeback=False)`` (kept for A/B benchmarking)."""

    #: pack each group's host leaves into one staging buffer (1 H2D request)
    coalesce: bool = True
    #: drain ``rw`` writebacks at end of run instead of blocking per group
    async_writeback: bool = True
    #: staging buffers preallocated per group layout
    staging_slots: int = 2
    #: donate the flat device buffer into its unpacked leaves
    donate_flat: bool = True
    #: emulated interconnect (None = the container's real link)
    link: Optional[LinkModel] = None
    # -- adaptive distance (PrefetchSpec(distance="auto")) ------------------
    min_distance: int = 1
    max_distance: int = 8
    #: a per-group wait above this counts as a stall -> grow the window
    wait_eps_s: float = 100e-6
    #: consecutive stall-free groups before the window shrinks
    shrink_after: int = 4
    # -- disk tier (DiskHost groups: two-stage disk->host->device) ----------
    #: emulated disk link (None = the container's real page cache / disk)
    disk_link: Optional[LinkModel] = None
    #: initial disk read-ahead window (fetched-but-unconsumed host buffers);
    #: the disk-stage AdaptiveDistance controller grows/shrinks it at run
    #: time from observed transfer-worker stalls
    disk_slots: int = 2
    disk_max_slots: int = 8
    disk_wait_eps_s: float = 100e-6
    disk_shrink_after: int = 4
    # -- robustness (self-healing streamed runtime) -------------------------
    #: attempts per transfer operation (H2D put, D2H get, disk stage);
    #: 1 = fail fast (the historical behaviour).  Retried operations re-read
    #: their intact cold home (host arrays / mapped chunk bytes), so a
    #: schedule that retried is bitwise-equal to one that did not.
    max_attempts: int = 1
    #: base of the exponential backoff between attempts (attempt ``k``
    #: sleeps ``retry_backoff_s * 2**k``); the clean path never sleeps
    retry_backoff_s: float = 1e-3
    #: per-worker join timeout in ``close()`` before the thread counts as
    #: leaked (surfaced on ``TransferEngine.leaked_threads``)
    close_timeout_s: float = 5.0
    #: CRC-verify spill-store chunk bytes in the disk stage before packing
    verify_spill: bool = True
    # -- hazard sanitizer (static analyzer's runtime counterpart) -----------
    #: record a happens-before edge per ticket and raise
    #: :class:`repro.core.schedcheck.HazardError` on writeback-vs-fetch RAW
    #: hazards and staging-pool lifetime violations.  Defaults from the
    #: ``REPRO_SANITIZE`` environment variable so chaos/fault suites can
    #: run sanitized without threading a flag through every constructor.
    sanitize: bool = dataclasses.field(default_factory=sanitize_enabled)


def static_auto_distance(n_chunks: int, cap: int = 4) -> int:
    """Compile-time resolution of ``distance="auto"`` for the graph engine
    (``prefetch.streamed_scan``), which cannot re-shape its ring at run
    time: a small fixed head start, clamped to the chunk count."""
    return max(1, min(cap, n_chunks - 1))


# ---------------------------------------------------------------------------
# adaptive prefetch distance
# ---------------------------------------------------------------------------


class AdaptiveDistance:
    """Grow-on-stall / shrink-when-idle controller for the in-flight window.

    Observes the compute thread's per-group transfer wait.  A wait above
    ``wait_eps_s`` grows the window by one; ``shrink_after`` consecutive
    clean groups shrink it by one.  A stall immediately after a shrink
    raises a sticky floor so the controller converges to the minimal
    sufficient window instead of oscillating.
    """

    def __init__(
        self,
        *,
        initial: int = 1,
        min_distance: int = 1,
        max_distance: int = 8,
        wait_eps_s: float = 100e-6,
        shrink_after: int = 4,
    ) -> None:
        self.min_distance = max(1, min_distance)
        self.max_distance = max(self.min_distance, max_distance)
        self.wait_eps_s = wait_eps_s
        self.shrink_after = max(1, shrink_after)
        self.distance = min(max(initial, self.min_distance), self.max_distance)
        self._floor = self.min_distance
        self._clean = 0
        self._just_shrank = False

    def observe(self, wait_s: float) -> int:
        """Record one group's transfer wait; returns the updated distance."""
        if wait_s > self.wait_eps_s:
            if self._just_shrank:
                # shrinking caused a stall: the previous window was minimal
                self._floor = min(self.distance + 1, self.max_distance)
            self.distance = min(self.distance + 1, self.max_distance)
            self._clean = 0
            self._just_shrank = False
        else:
            self._clean += 1
            self._just_shrank = False
            if self._clean >= self.shrink_after and self.distance > max(
                self.min_distance, self._floor
            ):
                self.distance -= 1
                self._clean = 0
                self._just_shrank = True
        return self.distance

    def boost(self, n: int = 1) -> int:
        """Externally widen the window (straggler feedback): a flagged slow
        step is treated like an observed stall without waiting for one."""
        self.distance = min(self.distance + max(1, n), self.max_distance)
        self._clean = 0
        self._just_shrank = False
        return self.distance


# ---------------------------------------------------------------------------
# group layout: cached pack/unpack plan
# ---------------------------------------------------------------------------


def group_signature(group: Pytree) -> tuple:
    """Hashable identity of a group's structure: treedef + per-leaf
    (shape, dtype, device-resident?)."""
    leaves, treedef = jax.tree.flatten(group)
    return (
        treedef,
        tuple(
            (np.shape(x), str(np.asarray(x).dtype) if not isinstance(x, jax.Array) else str(x.dtype),
             isinstance(x, jax.Array))
            for x in leaves
        ),
    )


def _aliases_host(flat: jax.Array, staging: np.ndarray) -> bool:
    """True if the device array may share the staging memory — in that case
    the buffer must NOT be recycled while the array is alive.

    Only a CPU device can zero-copy a numpy buffer (some CPU backends do),
    so a put that landed anywhere else holds its own copy.  On a CPU device
    the pointers are compared, and one that will not report its pointer is
    assumed to alias."""
    if all(d.platform != "cpu" for d in flat.devices()):
        return False
    try:
        return flat.unsafe_buffer_pointer() == staging.ctypes.data
    except Exception:  # noqa: BLE001 — unknown CPU backend: assume aliasing
        return True


class GroupLayout:
    """Pack/unpack plan for one group structure.

    Host leaves (anything not already a ``jax.Array``) are packed into one
    contiguous buffer of 32-bit words at 64-byte-aligned offsets;
    device-resident leaves pass through by reference.  ``unpack`` is a
    jitted slice+bitcast+reshape, compiled once per layout and
    bitwise-exact.  Staging words rather than bytes keeps the unpack's
    device temp near the staged size: a 4-byte leaf is a same-width
    bitcast, where a byte buffer would need a ``(n, 4)`` uint8 view that
    the TPU pads to a full tile per row (~32x the staged bytes for a
    4-layer f32 smollm-360m group on v5e).
    """

    #: default-placement layouts stage through a single device
    n_devices = 1

    def __init__(self, group: Pytree, *, donate_flat: bool = True) -> None:
        leaves, self.treedef = jax.tree.flatten(group)
        self.n_leaves = len(leaves)
        self.metas: list[tuple[int, int, tuple, np.dtype, int]] = []
        self.passthrough_idx: list[int] = []
        off = 0
        for i, x in enumerate(leaves):
            if isinstance(x, jax.Array):
                self.passthrough_idx.append(i)
                continue
            a = np.asarray(x)
            # pack at JAX's canonical dtype: the per-leaf device_put path
            # canonicalizes float64->float32 etc., and the device-side
            # bitcast target is canonicalized regardless — packing source
            # bytes would unpack into garbage (or a shape error)
            dtype = np.dtype(jax.dtypes.canonicalize_dtype(a.dtype))
            nbytes = a.size * dtype.itemsize
            self.metas.append((i, off, a.shape, dtype, nbytes))
            off = _align(off + nbytes)
        self.staging_bytes = off
        #: actual H2D payload (unpadded), for byte accounting
        self.payload_bytes = sum(m[4] for m in self.metas)
        #: H2D requests this layout costs when coalesced (0 if nothing to move)
        self.n_packed = len(self.metas)

        donate = (0,) if donate_flat else ()
        self._unpack = jax.jit(
            _unpack_fn([m[1:] for m in self.metas]), donate_argnums=donate
        )

    @property
    def has_payload(self) -> bool:
        return bool(self.metas)

    @property
    def n_requests(self) -> int:
        """H2D requests this layout costs per group when coalesced."""
        return 1 if self.metas else 0

    def new_staging(self) -> np.ndarray:
        return np.empty((self.staging_bytes // _WORD,), np.uint32)

    def put_staged(self, staging: np.ndarray):
        """Issue the (single) H2D transfer of the packed staging buffer."""
        return jax.device_put(staging)

    def any_alias(self, flat, staging) -> bool:
        return _aliases_host(flat, staging)

    def pack_into(self, leaves: list, staging: np.ndarray) -> np.ndarray:
        buf = staging.view(np.uint8)
        for i, off, shape, dtype, nbytes in self.metas:
            dst = buf[off : off + nbytes].view(dtype).reshape(shape)
            _pack_leaf(dst, np.asarray(leaves[i]))
        return staging

    def unpack(self, flat: jax.Array, src_leaves: list) -> Pytree:
        """Rebuild the group pytree from the flat device buffer, merging
        passed-through device leaves from the original submission."""
        if self.metas:
            with warnings.catch_warnings():
                # donation is best-effort: backends without aliasing support
                # fall back to a copy — correct, and not worth a warning per
                # layout (scoped here instead of a process-global filter)
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                packed = self._unpack(flat)
        else:
            packed = ()
        out: list = [None] * self.n_leaves
        for (i, *_), leaf in zip(self.metas, packed):
            out[i] = leaf
        for i in self.passthrough_idx:
            out[i] = src_leaves[i]
        return jax.tree.unflatten(self.treedef, out)


def _from_words(seg: jax.Array, dtype: np.dtype, size: int) -> jax.Array:
    """Reinterpret a uint32 word segment as ``size`` elements of ``dtype``.

    4-byte dtypes are a same-width bitcast.  Narrower ones bitcast to
    ``(n, 4 // itemsize)`` and flatten (byte order as packed on the host),
    dropping the tail padding of the last word; 8-byte ones pair words.

    The barrier before a narrowing bitcast keeps XLA from fusing every
    leaf's slice into one fusion with the others: for a 4-layer bf16
    smollm-360m group the v5e compile takes ~35 s fused and ~1.5 s
    with the barrier, at a device temp still below the staged bytes."""
    jdt = jnp.dtype(dtype)
    if jdt == jnp.bool_:
        return _from_words(seg, np.dtype(np.uint8), size) != 0
    if jdt.itemsize == _WORD:
        return lax.bitcast_convert_type(seg, jdt)
    if jdt.itemsize < _WORD:
        seg = lax.optimization_barrier(seg)
        return lax.bitcast_convert_type(seg, jdt).reshape(-1)[:size]
    return lax.bitcast_convert_type(seg.reshape(-1, jdt.itemsize // _WORD), jdt)


def _unpack_fn(metas: list):
    """The jitted body of an unpack: per ``(byte offset, shape, dtype,
    nbytes)``, slice the leaf's words out of the flat uint32 buffer and
    rebuild the leaf."""

    def _unpack(flat: jax.Array) -> tuple:
        outs = []
        for o, shape, dtype, nbytes in metas:
            w0 = o // _WORD
            seg = lax.slice(flat, (w0,), (w0 + -(-nbytes // _WORD),))
            size = int(np.prod(shape, dtype=np.int64))
            outs.append(_from_words(seg, dtype, size).reshape(shape))
        return tuple(outs)

    return _unpack


def _shard_shape(shape: tuple, idx: tuple) -> tuple:
    """Shape of the shard a device holds, from its indices-map entry."""
    if not idx:  # 0-d leaf: every device holds the scalar
        return tuple(shape)
    out = []
    for dim, sl in zip(shape, idx):
        start, stop, step = sl.indices(dim)
        out.append(max(0, -(-(stop - start) // step)))
    return tuple(out)


def flatten_shardings(device_shardings: Any, n_leaves: int) -> list:
    """Flatten a shardings pytree positionally against a group's leaf list.

    ``None`` entries are kept as leaves (they mark default placement for
    that position); a single sharding broadcasts over every leaf.
    """
    flat, _ = jax.tree.flatten(device_shardings, is_leaf=lambda x: x is None)
    if len(flat) == 1 and n_leaves != 1:
        flat = flat * n_leaves
    if len(flat) != n_leaves:
        raise ValueError(
            f"device_shardings has {len(flat)} leaves for a group of "
            f"{n_leaves} leaves"
        )
    return flat


class ShardedGroupLayout:
    """Per-(addressable device, group) pack/unpack plan for explicitly
    sharded groups.

    Each host leaf's per-device shard slices come from its sharding's
    ``addressable_devices_indices_map``; every device gets ONE contiguous
    staging buffer holding its shards at 64-byte-aligned offsets (a
    replicated leaf contributes a full copy per device — exactly the bytes
    eager sharded placement moves).  ``put_staged`` issues one
    ``device_put`` per device per group; ``unpack`` runs a jitted
    slice+bitcast+reshape on each device's flat buffer and assembles the
    committed leaves with ``jax.make_array_from_single_device_arrays`` —
    bitwise identical to ``jax.device_put(leaf, sharding)``.
    """

    def __init__(self, group: Pytree, shardings_flat: list, *, donate_flat: bool = True) -> None:
        leaves, self.treedef = jax.tree.flatten(group)
        self.n_leaves = len(leaves)
        self.passthrough_idx: list[int] = []
        #: per-leaf assembly plan: (leaf idx, global shape, dtype, sharding)
        self.assembly: list[tuple] = []
        entries: dict[Any, list] = {}
        offs: dict[Any, int] = {}
        default_dev = jax.devices()[0]
        #: host leaves that came without a sharding and were placed on the
        #: default device (a mesh run expects none)
        self.n_unplaced = 0
        for i, (x, s) in enumerate(zip(leaves, shardings_flat)):
            if isinstance(x, jax.Array):
                self.passthrough_idx.append(i)
                continue
            a = np.asarray(x)
            # pack at JAX's canonical dtype, same as GroupLayout (and as
            # jax.device_put would canonicalize)
            dtype = np.dtype(jax.dtypes.canonicalize_dtype(a.dtype))
            if s is None:
                # unplaced leaf riding in a sharded group: default device
                s = jax.sharding.SingleDeviceSharding(default_dev)
                self.n_unplaced += 1
            imap = s.addressable_devices_indices_map(a.shape)
            self.assembly.append((i, a.shape, dtype, s))
            for d in sorted(imap, key=lambda d: d.id):
                shard_shape = _shard_shape(a.shape, imap[d])
                nbytes = int(np.prod(shard_shape, dtype=np.int64)) * dtype.itemsize
                off = offs.get(d, 0)
                entries.setdefault(d, []).append(
                    (i, imap[d], off, shard_shape, dtype, nbytes)
                )
                offs[d] = _align(off + nbytes)
        self.devices = sorted(entries, key=lambda d: d.id)
        self.entries = [entries[d] for d in self.devices]
        self.staging_bytes = [offs[d] for d in self.devices]
        #: actual H2D payload (unpadded, summed over devices)
        self.payload_bytes = sum(e[5] for es in self.entries for e in es)
        #: ONE coalesced H2D request per (addressable device, group)
        self.n_requests = len(self.devices)
        self.n_devices = max(1, len(self.devices))
        # one jitted unpack per distinct per-device plan (devices usually
        # share one: identical shard shapes at identical offsets)
        donate = (0,) if donate_flat else ()
        by_plan: dict[tuple, Any] = {}
        self._unpacks = []
        for es in self.entries:
            metas = [(o, shape, dt, nb) for _i, _ix, o, shape, dt, nb in es]
            key = tuple((o, shape, str(dt), nb) for o, shape, dt, nb in metas)
            fn = by_plan.get(key)
            if fn is None:
                fn = by_plan[key] = jax.jit(
                    _unpack_fn(metas), donate_argnums=donate
                )
            self._unpacks.append(fn)

    @property
    def has_payload(self) -> bool:
        return bool(self.devices)

    def new_staging(self) -> list[np.ndarray]:
        return [np.empty((n // _WORD,), np.uint32) for n in self.staging_bytes]

    def pack_into(self, leaves: list, stagings: list[np.ndarray]) -> list[np.ndarray]:
        for staging, es in zip(stagings, self.entries):
            buf = staging.view(np.uint8)
            for i, idx, off, shape, dtype, nbytes in es:
                dst = buf[off : off + nbytes].view(dtype).reshape(shape)
                _pack_leaf(dst, np.asarray(leaves[i])[idx])
        return stagings

    def put_staged(self, stagings: list[np.ndarray]) -> list:
        """One H2D transfer per device: the request-count collapse under a
        mesh is ``n_devices`` per group, not ``n_leaves x n_shards``."""
        return [jax.device_put(buf, d) for buf, d in zip(stagings, self.devices)]

    def any_alias(self, flats: list, stagings: list) -> bool:
        return any(_aliases_host(f, b) for f, b in zip(flats, stagings))

    def unpack(self, flats: list, src_leaves: list) -> Pytree:
        """Rebuild the group: per-device jitted unpack of each flat buffer,
        then per-leaf assembly onto its sharding (committed multi-device
        arrays, bitwise vs eager sharded placement)."""
        shards: dict[int, list] = {}
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            for flat, es, fn in zip(flats or [], self.entries, self._unpacks):
                for (i, *_), piece in zip(es, fn(flat)):
                    shards.setdefault(i, []).append(piece)
        out: list = [None] * self.n_leaves
        for i, shape, dtype, s in self.assembly:
            out[i] = jax.make_array_from_single_device_arrays(shape, s, shards[i])
        for i in self.passthrough_idx:
            out[i] = src_leaves[i]
        return jax.tree.unflatten(self.treedef, out)


# ---------------------------------------------------------------------------
# futures
# ---------------------------------------------------------------------------


def _retryable(e: BaseException) -> bool:
    """Faults the bounded-retry loops may absorb.  Corruption is excluded:
    its recovery path (re-read, durable-home rewrite) already ran inside
    :func:`repro.core.spillstore.verify_disk_leaf`, and retrying would just
    re-consume the same bad bytes.  :class:`HazardError` is excluded too:
    a retried hazard is a hidden hazard."""
    from repro.core.spillstore import SpillCorruptionError

    return not isinstance(
        e, (KeyboardInterrupt, SystemExit, SpillCorruptionError, HazardError)
    )


class TransferFuture:
    """Handle to one in-flight H2D group transfer."""

    __slots__ = (
        "index",
        "layout",
        "src_leaves",
        "n_requests",
        "nbytes",
        "n_devices",
        "disk_requests",
        "disk_nbytes",
        "disk_wait_s",
        "retries",
        "_event",
        "_flat",
        "_device_tree",
        "_error",
        "ready_at",
        "_group",
    )

    def __init__(self, index: int, layout: Optional[GroupLayout], src_leaves, n_requests: int, nbytes: int):
        self.index = index
        self.layout = layout
        self.src_leaves = src_leaves
        self.n_requests = n_requests
        self.nbytes = nbytes
        #: addressable devices this group stages onto (1 for default placement)
        self.n_devices = 1
        #: disk-tier accounting (zero for pure host/device groups)
        self.disk_requests = 0
        self.disk_nbytes = 0
        #: time the *transfer worker* blocked on the disk stage (stage-2-on-
        #: stage-1 stall; zero when the disk read-ahead window covers it)
        self.disk_wait_s = 0.0
        #: transient faults absorbed while staging this group (both stages)
        self.retries = 0
        self._event = threading.Event()
        self._flat = None
        self._device_tree = None
        self._error: Optional[BaseException] = None
        self.ready_at = 0.0
        self._group = None

    # -- worker side --------------------------------------------------------
    def _complete(self, *, flat=None, device_tree=None, ready_at=0.0, error=None):
        self._flat = flat
        self._device_tree = device_tree
        self.ready_at = ready_at
        self._error = error
        self._event.set()

    # -- compute side -------------------------------------------------------
    @property
    def is_resident(self) -> bool:
        """True iff this submit moved NOTHING across any link: every leaf
        was already a committed ``jax.Array`` (device-kind homes, and
        residency-cache hits) and nothing was disk-staged.  The executors'
        cache-hit/unique-fetch accounting keys off this."""
        return self.n_requests == 0 and self.disk_requests == 0

    def wait(self) -> float:
        """Block until the transfer has landed; returns the time the compute
        thread actually spent blocked (the paper's stall time)."""
        t0 = time.perf_counter()
        self._event.wait()
        if self._error is not None:
            raise self._error
        residual = self.ready_at - time.perf_counter()
        if residual > 0:  # emulated link latency tail
            _sleep_precise(residual)
        return time.perf_counter() - t0

    def group(self) -> Pytree:
        """The staged device-side group (unpacks the flat buffer once)."""
        if self._group is None:
            if self._device_tree is not None:
                self._group = self._device_tree
            else:
                self._group = self.layout.unpack(self._flat, self.src_leaves)
            self._flat = None  # donated/consumed — release our reference
            self.src_leaves = None
        return self._group


class _DiskFetchTicket:
    """Handle to one in-flight disk->host-staging fetch (pipeline stage 1).

    The disk worker copies each memory-mapped leaf into a pooled host
    staging buffer (the copy *is* the disk read) and publishes ndarray
    views; the transfer worker substitutes them for the mapped leaves
    before packing, then releases the buffer back to the pool.
    """

    __slots__ = ("sig", "idx", "n_requests", "nbytes", "retries", "_event",
                 "_error", "views", "buf", "ready_at")

    def __init__(self, sig: tuple, idx: list, n_requests: int, nbytes: int):
        self.sig = sig
        #: positions of the disk leaves in the group's flattened leaf list
        self.idx = idx
        self.n_requests = n_requests
        self.nbytes = nbytes
        #: transient disk-stage faults absorbed for this fetch
        self.retries = 0
        self._event = threading.Event()
        self._error: Optional[BaseException] = None
        self.views: Optional[list] = None
        self.buf: Optional[np.ndarray] = None
        self.ready_at = 0.0


class _WritebackTicket:
    __slots__ = ("index", "n_requests", "nbytes", "retries", "_event", "_host",
                 "_error", "ready_at", "key")

    def __init__(self, index: int, n_requests: int, nbytes: int, key=None):
        #: logical group key (sanitizer happens-before tracking); None when
        #: the submitter has no stable name for the group
        self.key = key
        self.index = index
        self.n_requests = n_requests
        self.nbytes = nbytes
        #: transient D2H faults absorbed for this writeback
        self.retries = 0
        self._event = threading.Event()
        self._host = None
        self._error: Optional[BaseException] = None
        self.ready_at = 0.0

    def result(self) -> Pytree:
        self._event.wait()
        if self._error is not None:
            raise self._error
        residual = self.ready_at - time.perf_counter()
        if residual > 0:
            _sleep_precise(residual)
        return self._host


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------


class TransferEngine:
    """Background host service moving groups between host and device.

    One worker thread owns all transfer work (pack, ``device_put``,
    ``device_get``, link emulation); the compute thread only submits work
    and waits on futures.  FIFO processing preserves submission order, so
    writebacks drain in group order by construction.
    """

    def __init__(self, config: Optional[EngineConfig] = None) -> None:
        self.config = config or EngineConfig()
        #: runtime hazard sanitizer (``EngineConfig(sanitize=True)`` /
        #: ``REPRO_SANITIZE=1``); None on the un-instrumented fast path
        self.sanitizer: Optional[HazardSanitizer] = (
            HazardSanitizer() if self.config.sanitize else None
        )
        self._tasks: "queue.SimpleQueue" = queue.SimpleQueue()
        self._worker: Optional[threading.Thread] = None
        self._layouts: dict[tuple, GroupLayout] = {}
        #: per-layout free list of reusable staging buffers (worker-owned)
        self._staging_free: dict[tuple, list[np.ndarray]] = {}
        #: staging buffers allocated, and packs that reused a pooled one:
        #: ``staging_reuses / (staging_reuses + staging_allocs)`` is the
        #: pool's hit share over the coalesced packs
        self.staging_allocs: int = 0
        self.staging_reuses: int = 0
        self._pending_wb: list[_WritebackTicket] = []
        #: the emulated link is one serial resource: every transfer's
        #: occupancy — worker H2D/D2H *and* the executor's blocking D2H
        #: (seed schedule) — holds this lock for its duration
        self._link_lock = threading.Lock()
        # -- disk stage (DiskHost groups) -----------------------------------
        self._disk_tasks: "queue.SimpleQueue" = queue.SimpleQueue()
        self._disk_worker: Optional[threading.Thread] = None
        self._disk_layouts: dict[tuple, GroupLayout] = {}
        self._disk_free: dict[tuple, list[np.ndarray]] = {}
        #: fetched-but-unconsumed disk buffers; bounded by the read-ahead
        #: window so the disk stage cannot run unboundedly ahead of H2D
        self._disk_in_use = 0
        self._disk_cond = threading.Condition()
        self._disk_controller: Optional[AdaptiveDistance] = None
        self._disk_window = max(1, self.config.disk_slots)
        #: the (emulated) disk is its own serial resource
        self._disk_link_lock = threading.Lock()
        #: True when the last close() abandoned a live worker thread
        #: (failed join) — tests assert clean shutdown through this
        self.leaked_threads: bool = False
        #: executor AdaptiveDistance controllers fed by this engine, so
        #: external signals (straggler events) can widen every window
        self._controllers: list[AdaptiveDistance] = []

    # -- external window control --------------------------------------------
    def register_controller(self, ctrl: AdaptiveDistance) -> None:
        """Attach an executor's prefetch controller to this engine (the
        executor registers itself); :meth:`widen` then reaches it."""
        if ctrl not in self._controllers:
            self._controllers.append(ctrl)

    def widen(self, n: int = 1) -> list[int]:
        """Widen every registered prefetch window (and the disk read-ahead
        window) by ``n``.  The driver calls this on straggler events: a slow
        step buys more transfer overlap instead of only a log line.  Returns
        the new window sizes (observability)."""
        out = [c.boost(n) for c in self._controllers]
        with self._disk_cond:
            if self._disk_controller is not None:
                self._disk_window = self._disk_controller.boost(n)
            else:
                self._disk_window = min(
                    self._disk_window + max(1, n), self.config.disk_max_slots
                )
            out.append(self._disk_window)
            self._disk_cond.notify_all()
        return out

    # -- lifecycle -----------------------------------------------------------
    def _ensure_worker(self) -> None:
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_loop, name="transfer-engine", daemon=True
            )
            self._worker.start()

    def _ensure_disk_worker(self) -> None:
        if self._disk_worker is None or not self._disk_worker.is_alive():
            self._disk_worker = threading.Thread(
                target=self._disk_worker_loop, name="transfer-engine-disk",
                daemon=True,
            )
            self._disk_worker.start()

    def close(self) -> None:
        """Stop the worker threads.  Not final: a later submit restarts the
        workers, so close() is "quiesce", matching the driver's restart loop
        (close at shutdown, resurrect transparently if reused).  Pending
        tasks — including in-flight disk fetches — drain before the workers
        exit, so no future is left unset.

        A worker that fails to join within ``close_timeout_s`` is *leaked*,
        not silently forgotten: it is logged loudly, surfaced on
        ``leaked_threads``, and its reference is kept so a later submit
        cannot start a duplicate consumer on the same queue."""
        timeout = self.config.close_timeout_s
        if self._disk_worker is not None and self._disk_worker.is_alive():
            self._disk_tasks.put(None)
        if self._worker is not None and self._worker.is_alive():
            self._tasks.put(None)
            self._worker.join(timeout=timeout)
        if self._disk_worker is not None and self._disk_worker.is_alive():
            self._disk_worker.join(timeout=timeout)
        leaked = [
            t.name
            for t in (self._worker, self._disk_worker)
            if t is not None and t.is_alive()
        ]
        self.leaked_threads = bool(leaked)
        if leaked:
            log.error(
                "TransferEngine.close(): worker thread(s) %s still alive "
                "after a %.1fs join — leaked (wedged transfer?); keeping "
                "their references so a later submit cannot spawn a "
                "duplicate consumer on the same queue",
                leaked,
                timeout,
            )
        if self._worker is not None and not self._worker.is_alive():
            self._worker = None
        if self._disk_worker is not None and not self._disk_worker.is_alive():
            self._disk_worker = None

    def __enter__(self) -> "TransferEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):  # pragma: no cover - defensive
        try:
            self.close()
        except Exception:
            pass

    # -- layout / staging ----------------------------------------------------
    def layout_for(self, group: Pytree) -> GroupLayout:
        return self._layout_for_sig(
            group_signature(group),
            lambda: GroupLayout(group, donate_flat=self.config.donate_flat),
        )

    def _layout_for_sig(self, sig: tuple, factory):
        """Layout cache: one layout + staging pool per signature (shared by
        the default-placement and sharded coalescing paths)."""
        lo = self._layouts.get(sig)
        if lo is None:
            lo = factory()
            self._layouts[sig] = lo
            self._staging_free[sig] = []
        return lo

    def _acquire_staging(self, sig: tuple, layout) -> Any:
        """Check a staging buffer (set) out of the layout's pool (worker
        thread) — one ndarray for default-placement layouts, one ndarray
        per addressable device for sharded layouts.

        Pops a recycled buffer when one is free, else allocates: the pool
        self-sizes to the worker's actual concurrency (1 buffer in the
        steady state, since the worker blocks each ``device_put``).
        """
        free = self._staging_free[sig]
        if free:
            self.staging_reuses += 1
            staging = free.pop()
            if self.sanitizer is not None:
                self.sanitizer.on_staging_acquire(id(staging), from_pool=True)
            return staging
        self.staging_allocs += 1
        staging = layout.new_staging()
        if self.sanitizer is not None:
            self.sanitizer.on_staging_acquire(id(staging), from_pool=False)
        return staging

    def _release_staging(self, sig: tuple, staging: Any) -> None:
        if self.sanitizer is not None:
            self.sanitizer.on_staging_release(id(staging))
        free = self._staging_free[sig]
        if len(free) < max(1, self.config.staging_slots):
            free.append(staging)

    # -- disk stage pool (read-ahead window) --------------------------------
    def _disk_layout_for(self, dsig: tuple, disk_leaves: list) -> GroupLayout:
        lo = self._disk_layouts.get(dsig)
        if lo is None:
            lo = GroupLayout(tuple(disk_leaves), donate_flat=False)
            self._disk_layouts[dsig] = lo
            self._disk_free[dsig] = []
        return lo

    def _acquire_disk_staging(self, dsig: tuple, layout: GroupLayout) -> np.ndarray:
        """Check a host buffer out of the disk pool (disk worker thread).

        Blocks while ``window`` buffers are already fetched-but-unconsumed —
        this is the disk read-ahead throttle the disk-stage controller
        adjusts.  Progress is guaranteed: the transfer worker consumes
        tickets in submission order and releases each buffer after packing.
        """
        with self._disk_cond:
            while self._disk_in_use >= max(1, self._disk_window):
                self._disk_cond.wait(timeout=0.5)
            self._disk_in_use += 1
        try:
            free = self._disk_free[dsig]
            if free:
                return free.pop()
            return layout.new_staging()
        except BaseException:
            # allocation failed (e.g. MemoryError in a RAM-constrained run):
            # give the window slot back or the pipeline wedges permanently
            with self._disk_cond:
                self._disk_in_use -= 1
                self._disk_cond.notify_all()
            raise

    def _release_disk_staging(self, dsig: tuple, buf: np.ndarray) -> None:
        free = self._disk_free.get(dsig)
        if free is not None and len(free) < self.config.disk_max_slots:
            free.append(buf)
        with self._disk_cond:
            self._disk_in_use -= 1
            self._disk_cond.notify_all()

    def _observe_disk_wait(self, wait_s: float) -> None:
        """Feed the disk-stage controller one stage-2-on-stage-1 stall
        sample; widens/narrows the read-ahead window (transfer worker)."""
        if self._disk_controller is None:
            cfg = self.config
            self._disk_controller = AdaptiveDistance(
                initial=cfg.disk_slots,
                min_distance=1,
                max_distance=cfg.disk_max_slots,
                wait_eps_s=cfg.disk_wait_eps_s,
                shrink_after=cfg.disk_shrink_after,
            )
        new = self._disk_controller.observe(wait_s)
        if new != self._disk_window:
            with self._disk_cond:
                self._disk_window = new
                self._disk_cond.notify_all()

    # -- submission (compute thread) ----------------------------------------
    def _submit_disk_stage(self, sig: tuple, leaves: list, fut: TransferFuture):
        """Enqueue the stage-1 disk fetch for a group's disk-tier leaves
        (sharded and unsharded groups feed the disk worker identically);
        returns the ticket, or None when nothing is disk-resident."""
        from repro.core.spillstore import is_disk_leaf

        disk_idx = [i for i, x in enumerate(leaves) if is_disk_leaf(x)]
        if not disk_idx:
            return None
        disk_leaves = [leaves[i] for i in disk_idx]
        # one chunk file = one disk request (the store's coalescing)
        n_files = len({getattr(x, "filename", None) or id(x) for x in disk_leaves})
        # group_signature cannot tell a memmap from an ndarray, so the disk
        # layout must additionally key on *which* leaves are disk-resident
        dsig = ("disk", sig, tuple(disk_idx))
        dlayout = self._disk_layout_for(dsig, disk_leaves)
        ticket = _DiskFetchTicket(dsig, disk_idx, n_files, dlayout.payload_bytes)
        fut.disk_requests = n_files
        fut.disk_nbytes = dlayout.payload_bytes
        self._ensure_disk_worker()
        self._disk_tasks.put((ticket, disk_leaves))
        return ticket

    def submit_group(
        self, index: int, group: Pytree, *, device_shardings=None, key=None
    ) -> TransferFuture:
        """Queue the H2D transfer of one group; returns immediately.

        ``key`` is the group's logical name (plan group key, KV page id):
        the hazard sanitizer refuses a fetch whose key has a D2H writeback
        still in flight.  ``key=None`` transfers are unchecked — exactly
        the transfers the static analyzer cannot name either.

        Coalescing composes with explicit ``device_shardings``
        (multi-device layouts): the group stages through one buffer per
        addressable device — ``n_devices`` requests per group — and the
        committed leaves are assembled bitwise-equal to eager sharded
        placement (see :class:`ShardedGroupLayout`).  Only
        ``EngineConfig(coalesce=False)`` takes the per-leaf path, which
        costs one request per (leaf, addressable shard).

        Groups containing disk-tier leaves (spill-store memmaps, see
        :mod:`repro.core.spillstore`) additionally enqueue a stage-1 fetch
        on the disk worker; the H2D stage blocks on it per group, so the
        two stages pipeline across groups.
        """
        from repro.core.spillstore import is_disk_leaf

        if self.sanitizer is not None:
            self.sanitizer.on_fetch(key)
        leaves = jax.tree.leaves(group)
        sh_flat = None
        if device_shardings is not None:
            sh_flat = flatten_shardings(device_shardings, len(leaves))
        if self.config.coalesce:
            if sh_flat is None:
                sig = group_signature(group)
                layout = self._layout_for_sig(
                    sig,
                    lambda: GroupLayout(group, donate_flat=self.config.donate_flat),
                )
            else:
                sig = ("sharded", group_signature(group), tuple(sh_flat))
                layout = self._layout_for_sig(
                    sig,
                    lambda: ShardedGroupLayout(
                        group, sh_flat, donate_flat=self.config.donate_flat
                    ),
                )
            fut = TransferFuture(
                index, layout, leaves, layout.n_requests, layout.payload_bytes
            )
            fut.n_devices = layout.n_devices
            ticket = self._submit_disk_stage(sig, leaves, fut)
            self._ensure_worker()
            self._tasks.put(("h2d", fut, group, None, True, sig, ticket))
            return fut

        # per-leaf fallback (A/B baseline): one request per (host leaf,
        # addressable shard); disk-tier memmaps are read inline by
        # device_put (no stage-1 pipeline) but their traffic is accounted
        n_host = 0
        nbytes = 0
        n_devices = 1
        disk_files: set = set()
        disk_bytes = 0
        for j, x in enumerate(leaves):
            if isinstance(x, jax.Array):
                continue
            a = np.asarray(x)
            s = sh_flat[j] if sh_flat is not None else None
            if s is None:
                n_shards, shard_bytes = 1, a.size * a.dtype.itemsize
            else:
                imap = s.addressable_devices_indices_map(a.shape)
                n_shards = len(imap)
                n_devices = max(n_devices, n_shards)
                shard_bytes = sum(
                    int(np.prod(_shard_shape(a.shape, idx), dtype=np.int64))
                    * a.dtype.itemsize
                    for idx in imap.values()
                )
            n_host += n_shards
            nbytes += shard_bytes
            if is_disk_leaf(x):
                disk_files.add(getattr(x, "filename", None) or id(x))
                disk_bytes += a.size * a.dtype.itemsize
        fut = TransferFuture(index, None, leaves, n_host, nbytes)
        fut.n_devices = n_devices
        fut.disk_requests = len(disk_files)
        fut.disk_nbytes = disk_bytes
        self._ensure_worker()
        self._tasks.put(("h2d", fut, group, sh_flat, False, None, None))
        return fut

    def submit_writeback(
        self, index: int, group_out: Pytree, *, key=None
    ) -> _WritebackTicket:
        """Queue the D2H copy of an ``rw`` group's output; returns
        immediately.  ``key`` names the group for the hazard sanitizer:
        until the ticket drains, a same-key fetch is a RAW hazard."""
        leaves = jax.tree.leaves(group_out)
        nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
        ticket = _WritebackTicket(index, len(leaves), nbytes, key=key)
        self._pending_wb.append(ticket)
        if self.sanitizer is not None:
            self.sanitizer.on_writeback(key)
        self._ensure_worker()
        self._tasks.put(("d2h", ticket, group_out))
        return ticket

    def drain_writebacks(self) -> list:
        """Wait for every pending writeback; returns host groups in group
        order (FIFO worker + ordered tickets ⇒ paper's per-device ordering)."""
        tickets = sorted(self._pending_wb, key=lambda t: t.index)
        self._pending_wb = []
        with span("stream.drain.wait"):
            out = [t.result() for t in tickets]
        if self.sanitizer is not None:
            # only reached when every result landed: a failed drain keeps
            # its keys pending, so a restart must discard before re-fetching
            self.sanitizer.on_drained([t.key for t in tickets])
        return out

    def discard_writebacks(self) -> int:
        """Drop any pending writeback tickets (a failed run may have left
        some behind; the next run must not drain stale groups).  Returns
        the number discarded."""
        n = len(self._pending_wb)
        if self.sanitizer is not None:
            self.sanitizer.on_drained([t.key for t in self._pending_wb])
        self._pending_wb = []
        return n

    def settle(self) -> None:
        """Block until every transfer submitted so far has completed or
        failed: a barrier queued behind them on the FIFO worker.  A failed
        fetch settles the engine before its error surfaces, so the caller
        sees no transfer of the abandoned window still running (nor a disk
        buffer still checked out of the read-ahead window).  A wedged worker
        is waited on for at most ``close_timeout_s``."""
        if self._worker is None or not self._worker.is_alive():
            return
        done = threading.Event()
        self._tasks.put(("barrier", done))
        done.wait(timeout=self.config.close_timeout_s)

    # -- worker thread -------------------------------------------------------
    def _retry_loop(self, op, counter, what: str):
        """Run ``op()`` with bounded retry + exponential backoff.

        A transient fault re-runs ``op`` from its intact inputs (host
        arrays, disk staging views), incrementing ``counter.retries``;
        attempt exhaustion (or a non-retryable fault) re-raises so the
        waiter sees the permanent error."""
        attempts = max(1, self.config.max_attempts)
        for attempt in range(attempts):
            try:
                return op()
            except BaseException as e:  # noqa: BLE001 — bounded, re-raised
                if attempt + 1 >= attempts or not _retryable(e):
                    raise
                counter.retries += 1
                log.warning(
                    "transient %s fault (attempt %d/%d), backing off: %s",
                    what, attempt + 1, attempts, e,
                )
                _sleep_precise(self.config.retry_backoff_s * (2.0 ** attempt))
        raise AssertionError("unreachable")  # pragma: no cover

    def _worker_loop(self) -> None:
        link = self.config.link
        while True:
            task = self._tasks.get()
            if task is None:
                return
            kind = task[0]
            try:
                if kind == "h2d":
                    _, fut, group, shardings, coalesce, sig, ticket = task
                    if coalesce:
                        src_leaves = fut.src_leaves
                        disk_buf = None
                        if ticket is not None:
                            # stage-2-on-stage-1 wait: zero once the disk
                            # read-ahead window covers the disk latency
                            t0 = time.perf_counter()
                            with span("engine.disk.wait"):
                                ticket._event.wait()
                                if ticket._error is not None:
                                    raise ticket._error
                                residual = ticket.ready_at - time.perf_counter()
                                if residual > 0:
                                    _sleep_precise(residual)
                            fut.disk_wait_s = time.perf_counter() - t0
                            self._observe_disk_wait(fut.disk_wait_s)
                            fut.retries += ticket.retries
                            src_leaves = list(src_leaves)
                            for i, view in zip(ticket.idx, ticket.views):
                                src_leaves[i] = view
                            disk_buf = ticket.buf
                        try:
                            layout = fut.layout
                            if layout.has_payload:
                                # the disk buffer is held across attempts
                                # (src_leaves view into it), so a retried
                                # pack re-reads intact staged bytes
                                flat = self._retry_loop(
                                    lambda: self._put_coalesced(
                                        sig, layout, src_leaves, fut.index
                                    ),
                                    fut, f"H2D (group {fut.index})",
                                )
                            else:  # everything already device-resident
                                flat = None
                        finally:
                            if disk_buf is not None:
                                # packed (or failed): the disk buffer's bytes
                                # are no longer needed either way
                                self._release_disk_staging(ticket.sig, disk_buf)
                        ready_at = self._emulate(link, fut.n_requests, fut.nbytes)
                        fut._complete(flat=flat, ready_at=ready_at)
                    else:
                        with span("engine.put", group=fut.index, bytes=fut.nbytes):
                            tree = self._retry_loop(
                                lambda: self._put_per_leaf(group, shardings),
                                fut, f"H2D per-leaf (group {fut.index})",
                            )
                        ready_at = self._emulate(link, fut.n_requests, fut.nbytes)
                        fut._complete(device_tree=tree, ready_at=ready_at)
                elif kind == "d2h":
                    _, ticket, group_out = task
                    with span("engine.d2h", group=ticket.index, bytes=ticket.nbytes):
                        host = self._retry_loop(
                            lambda: jax.device_get(group_out),
                            ticket, f"D2H (group {ticket.index})",
                        )
                    ready_at = self._emulate(link, ticket.n_requests, ticket.nbytes)
                    ticket.ready_at = ready_at
                    ticket._host = host
                    ticket._event.set()
                elif kind == "barrier":
                    task[1].set()
            except BaseException as e:  # noqa: BLE001 — surface on the waiter
                obj = task[1]
                obj._error = e
                obj._event.set()

    def _put_coalesced(self, sig: tuple, layout, src_leaves, index: int):
        """One attempt of the coalesced H2D: stage, put, block.  On a fault
        the staging buffer is dropped, not recycled — a half-issued put may
        still alias it; the pool reallocates on the next attempt."""
        # a fresh buffer's first write pays a page fault per page
        fresh = not self._staging_free[sig]
        staging = self._acquire_staging(sig, layout)
        nbytes = layout.payload_bytes
        with span("engine.pack", group=index, bytes=nbytes, fresh=fresh):
            layout.pack_into(src_leaves, staging)
        with span("engine.put", group=index, bytes=nbytes):
            flat = layout.put_staged(staging)
            jax.block_until_ready(flat)
        if not layout.any_alias(flat, staging):
            # the device holds its own copy: recycle now
            self._release_staging(sig, staging)
        return flat

    def _put_per_leaf(self, group, shardings):
        """One attempt of the per-leaf fallback H2D."""
        if shardings is not None:
            # per-leaf fallback under explicit placements:
            # one device_put per leaf (None -> default)
            leaves, treedef = jax.tree.flatten(group)
            tree = jax.tree.unflatten(treedef, [
                jax.device_put(x, s) if s is not None
                else (x if isinstance(x, jax.Array) else jax.device_put(x))
                for x, s in zip(leaves, shardings)
            ])
        else:
            tree = jax.device_put(group)
        jax.block_until_ready(tree)
        return tree

    # -- disk worker thread (pipeline stage 1) ------------------------------
    def _disk_worker_loop(self) -> None:
        from repro.core.spillstore import verify_disk_leaf

        link = self.config.disk_link
        while True:
            task = self._disk_tasks.get()
            if task is None:
                return
            ticket, disk_leaves = task
            attempts = max(1, self.config.max_attempts)
            for attempt in range(attempts):
                buf = None
                try:
                    layout = self._disk_layouts[ticket.sig]
                    if self.config.verify_spill:
                        # CRC-check the mapped chunk bytes before consuming
                        # them; a mismatch re-fetches from the durable home
                        # or surfaces a rich SpillCorruptionError — corrupt
                        # bytes never reach the optimizer
                        disk_leaves = [verify_disk_leaf(x) for x in disk_leaves]
                    buf = self._acquire_disk_staging(ticket.sig, layout)
                    # the copy out of the memory-mapped view IS the disk read
                    with span("disk.read", bytes=ticket.nbytes):
                        layout.pack_into(disk_leaves, buf)
                    raw = buf.view(np.uint8)
                    views = [
                        raw[o : o + nb].view(dt).reshape(shape)
                        for _, o, shape, dt, nb in layout.metas
                    ]
                    ticket.ready_at = self._emulate(
                        link, ticket.n_requests, ticket.nbytes,
                        lock=self._disk_link_lock,
                    )
                    ticket.views = views
                    ticket.buf = buf
                    ticket._event.set()
                    break
                except BaseException as e:  # noqa: BLE001 — retry or surface
                    if buf is not None:
                        # give the window slot back between attempts or the
                        # read-ahead throttle counts phantom buffers
                        self._release_disk_staging(ticket.sig, buf)
                    if attempt + 1 >= attempts or not _retryable(e):
                        ticket._error = e
                        ticket._event.set()
                        break
                    ticket.retries += 1
                    log.warning(
                        "transient disk-stage fault (attempt %d/%d), "
                        "backing off: %s",
                        attempt + 1, attempts, e,
                    )
                    _sleep_precise(self.config.retry_backoff_s * (2.0 ** attempt))

    def _emulate(
        self,
        link: Optional[LinkModel],
        n_requests: int,
        nbytes: int,
        *,
        lock: Optional[threading.Lock] = None,
    ) -> float:
        """Hold the emulated link for the transfer's occupancy (sleep under
        the link's serial lock) and return the completion timestamp
        including the overlappable latency tail."""
        if link is None or n_requests == 0:
            return 0.0
        occ = link.occupancy_s(n_requests, nbytes)
        if occ > 0:
            with (lock if lock is not None else self._link_lock):
                _sleep_precise(occ)
        return time.perf_counter() + link.latency_s

    def emulate_blocking_transfer(self, n_requests: int, nbytes: int) -> None:
        """Pay the emulated link for a transfer issued *on the caller's
        thread* (the seed schedule's blocking ``device_get`` write-back).
        No-op without a link model."""
        ready_at = self._emulate(self.config.link, n_requests, nbytes)
        residual = ready_at - time.perf_counter()
        if residual > 0:
            _sleep_precise(residual)
