"""Memory kinds for hierarchical memory placement (paper §3.2, TPU-native).

The paper introduces ``Host`` / ``Shared`` / ``Microcore`` *kind* objects that
declare where in the memory hierarchy a tensor lives; kernels receive
references regardless of kind, and the kind encapsulates transfer mechanics.
Crucially (§3.2), adding a level is *just a new ``Kind`` subclass* — the level
does not have to be addressable by the accelerator, or even by XLA.

The hierarchy modelled here is three levels deep (see
``docs/memory_hierarchy.md``):

  level 0  ``Device``       HBM / device memory — XLA-addressable.
  level 2  ``PinnedHost``   host DRAM, DMA-reachable, not compute-addressable.
  level 3  ``UnpinnedHost`` pageable host DRAM (staging tier).
  level 4  ``DiskHost``     disk/NVMe spill store — *not* a JAX memory at
                            all; data reaches the device via a two-stage
                            disk -> host-staging -> device pipeline run by
                            :class:`repro.core.engine.TransferEngine`, with
                            :class:`repro.core.spillstore.SpillStore` as the
                            home representation (memory-mapped chunk files).

JAX exposes the host tiers as sharding *memory kinds* (``pinned_host`` /
``device``); the VMEM level is managed inside Pallas kernels (see
``repro.kernels``).  Kinds that XLA cannot address (``DiskHost``) resolve to
their *staging kind* for program placement — the compiled program only ever
sees the staging tier, while the runtime streams the data up the extra level.
This module provides:

  * ``MemKind`` subclasses mirroring (and extending) the paper's kinds,
  * ``PlacementPolicy`` — per-state-group kind assignment (params / optimizer
    moments / KV cache / activations), the "one-line change moves your data"
    property of the paper — including ``DISK_OPT`` / ``DISK_PARAMS`` presets
    for the disk tier,
  * a backend capability probe with a CPU-only fallback: the CPU runtime,
    which cannot execute host-placed buffers, maps host kinds onto its one
    memory while keeping the program topology (slice + copy +
    double-buffer) identical; any other backend raises rather than put a
    host kind in device memory.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "MemKind",
    "Device",
    "PinnedHost",
    "UnpinnedHost",
    "DiskHost",
    "PlacementPolicy",
    "ALL_DEVICE",
    "HOST_OPT",
    "HOST_PARAMS",
    "HOST_ALL",
    "DISK_OPT",
    "DISK_PARAMS",
    "all_kinds",
    "backend_memory_kinds",
    "backend_kind_string",
    "default_memory_kind",
    "host_offload_supported",
    "resolve_kind",
    "sharding_for",
    "place",
]


class MemKind:
    """A level of the memory hierarchy.  Subclass to add a level (paper §3.2:
    'To create a kind representing a new level in the memory hierarchy
    requires a new Python class, inheriting from the Kind class')."""

    #: the JAX memory-kind string this level maps to (a logical name for
    #: levels XLA cannot address, see ``jax_addressable``)
    jax_kind: str = "device"
    #: ordering in the hierarchy; higher = further from the compute units
    level: int = 0
    #: can the accelerator's compute units load/store this level directly?
    directly_addressable: bool = True
    #: can XLA place a buffer at this level at all?  ``False`` means the
    #: level exists only to the runtime (disk): program placement uses
    #: ``staging_jax_kind`` and the transfer engine bridges the gap.
    jax_addressable: bool = True
    #: the jax memory kind data from this level is staged through on its way
    #: to the device (only meaningful when ``jax_addressable`` is False)
    staging_jax_kind: str = "pinned_host"

    def __repr__(self) -> str:  # pragma: no cover - trivial
        return f"{type(self).__name__}(jax_kind={self.jax_kind!r}, level={self.level})"

    def __eq__(self, other: Any) -> bool:
        return isinstance(other, MemKind) and self.jax_kind == other.jax_kind

    def __hash__(self) -> int:
        return hash(self.jax_kind)


class Device(MemKind):
    """HBM — the paper's ``Microcore``/``Shared`` analogue (fast, bounded)."""

    jax_kind = "device"
    level = 0
    directly_addressable = True


class PinnedHost(MemKind):
    """Host DRAM, DMA-reachable but not addressable by compute — the paper's
    ``Host`` kind ('allocates the data in the large host memory, not
    accessible directly by the micro-cores')."""

    jax_kind = "pinned_host"
    level = 2
    directly_addressable = False


class UnpinnedHost(MemKind):
    """Pageable host DRAM (slowest RAM tier; staging only)."""

    jax_kind = "unpinned_host"
    level = 3
    directly_addressable = False


class DiskHost(MemKind):
    """Disk/NVMe spill tier — a hierarchy level the accelerator (and XLA)
    cannot address at all, demonstrating the paper's §3.2 claim that a new
    level is just a new ``Kind`` subclass.

    Home representation: memory-mapped chunk files in a
    :class:`repro.core.spillstore.SpillStore`.  The transfer engine streams
    chunks disk -> host staging -> device in a two-stage pipeline, hiding
    disk latency behind host->device latency exactly as host latency is
    hidden behind compute (``PrefetchSpec(distance="auto")`` per stage).
    """

    jax_kind = "disk_host"
    level = 4
    directly_addressable = False
    jax_addressable = False
    staging_jax_kind = "pinned_host"


DEVICE = Device()
PINNED_HOST = PinnedHost()
UNPINNED_HOST = UnpinnedHost()
DISK_HOST = DiskHost()

_KIND_BY_NAME = {
    "device": DEVICE,
    "pinned_host": PINNED_HOST,
    "unpinned_host": UNPINNED_HOST,
    "disk_host": DISK_HOST,
}


def all_kinds() -> tuple[MemKind, ...]:
    """Every registered hierarchy level, nearest-to-compute first (the
    cross-kind conformance matrix iterates this)."""
    return tuple(sorted(_KIND_BY_NAME.values(), key=lambda k: k.level))


def as_kind(kind: "MemKind | str | None") -> MemKind:
    if isinstance(kind, MemKind):
        return kind
    if kind is None:  # backend-default placement reads back as no kind
        return DEVICE
    try:
        return _KIND_BY_NAME[kind]
    except KeyError:
        raise ValueError(
            f"unknown memory kind {kind!r}; expected one of {sorted(_KIND_BY_NAME)}"
        ) from None


@functools.cache
def backend_memory_kinds() -> tuple[str, ...]:
    """Memory kinds the current backend *enumerates*."""
    dev = jax.devices()[0]
    try:
        return tuple(m.kind for m in dev.addressable_memories())
    except Exception:  # pragma: no cover - very old backends
        return ("device",)


@functools.cache
def default_memory_kind() -> Optional[str]:
    """The backend's default memory kind string (None if unqueryable)."""
    try:
        return jax.devices()[0].default_memory().kind
    except Exception:  # pragma: no cover
        return None


_warned_kinds: set = set()


def _on_cpu() -> bool:
    """The CPU backend is the only one allowed to fold host tiers into its
    default memory: it *is* its own host and device tier."""
    return jax.default_backend() == "cpu"


def backend_kind_string(jax_kind: str) -> Optional[str]:
    """Map a logical jax memory-kind string onto one this backend accepts.

    Backends differ in what they enumerate (TPU: ``device`` + ``pinned_host``;
    some CPU builds: only ``unpinned_host``).  On the CPU a kind the backend
    does not enumerate maps to ``None`` — the backend default memory — which
    is the physically correct tier there (it *is* its own host and device
    tier); a host kind warns once.  On any other backend a host kind it
    cannot address raises: placing it in device memory would hide the
    hierarchy on the chip.
    """
    if jax_kind in backend_memory_kinds():
        return jax_kind
    if jax_kind != "device" and not _on_cpu():
        raise RuntimeError(
            f"memory kind {jax_kind!r} is not enumerated by the "
            f"{jax.default_backend()} backend ({backend_memory_kinds()}); "
            "refusing to place it in device memory"
        )
    if jax_kind != "device" and jax_kind not in _warned_kinds:
        _warned_kinds.add(jax_kind)
        import warnings

        warnings.warn(
            f"memory kind {jax_kind!r} is not enumerated by this backend "
            f"({backend_memory_kinds()}); placing at the backend default "
            "memory instead",
            stacklevel=3,
        )
    return None


@functools.cache
def host_offload_supported() -> bool:
    """True iff the backend can *compile and execute* host-placed buffers.

    The CPU runtime enumerates ``pinned_host`` but lacks the
    ``annotate_device_placement`` custom-call implementation, so we probe by
    compiling a tiny host->device copy.  Only the CPU's probe failure means
    "unsupported"; on any other backend the probe's error propagates.
    """
    if "pinned_host" not in backend_memory_kinds():
        return False
    import jax.numpy as jnp

    dev = jax.devices()[0]
    host_s = jax.sharding.SingleDeviceSharding(dev, memory_kind="pinned_host")
    dev_s = jax.sharding.SingleDeviceSharding(dev, memory_kind="device")

    def f(x):
        return jax.device_put(x, dev_s) * 2.0

    try:
        jax.jit(f, in_shardings=(host_s,), out_shardings=dev_s).lower(
            jax.ShapeDtypeStruct((8,), jnp.float32)
        ).compile()
    except Exception:
        if not _on_cpu():
            raise
        return False
    return True


def resolve_kind(kind: "MemKind | str", *, allow_fallback: bool = True) -> MemKind:
    """Map a requested kind to one the backend can execute.

    Kinds XLA cannot address (``DiskHost``) first resolve to their *staging*
    kind — the compiled program only ever sees the staging tier; the extra
    level is the runtime's business (spill store + transfer engine).  On
    the CPU backend, which cannot execute host-placed buffers, host kinds
    then fall back to ``Device`` (identical program topology, both tiers
    physically in the same memory).  On any other backend a host kind it
    cannot execute raises instead of quietly landing in device memory.
    Lowering-only paths (the dry-run) may pass ``allow_fallback=False`` to
    keep the true placement in the StableHLO.
    """
    kind = as_kind(kind)
    if not kind.jax_addressable:
        kind = as_kind(kind.staging_jax_kind)
    if kind.jax_kind == "device":
        return kind
    if not allow_fallback or host_offload_supported():
        return kind
    if not _on_cpu():
        raise RuntimeError(
            f"memory kind {kind.jax_kind!r} cannot execute on the "
            f"{jax.default_backend()} backend; refusing to fall back to "
            "device memory"
        )
    return DEVICE


def sharding_for(
    mesh: Mesh,
    spec: PartitionSpec,
    kind: "MemKind | str" = DEVICE,
    *,
    allow_fallback: bool = True,
) -> NamedSharding:
    """NamedSharding at a given hierarchy level.

    ``allow_fallback=False`` (lowering-only paths, e.g. the dry-run) keeps
    the requested kind string verbatim so the true placement reaches the
    StableHLO — and fails loudly if the backend cannot express it.
    """
    kind = resolve_kind(kind, allow_fallback=allow_fallback)
    mk_str = backend_kind_string(kind.jax_kind) if allow_fallback else kind.jax_kind
    return NamedSharding(mesh, spec, memory_kind=mk_str)


def place(tree: Any, mesh: Mesh, specs: Any, kind: "MemKind | str" = DEVICE) -> Any:
    """``device_put`` a pytree at a hierarchy level.  ``specs`` is a matching
    pytree of PartitionSpec (or a single spec broadcast over leaves)."""
    kind = resolve_kind(kind)
    if isinstance(specs, PartitionSpec):
        specs = jax.tree.map(lambda _: specs, tree)
    shardings = jax.tree.map(
        lambda s: sharding_for(mesh, s, kind),
        specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec),
    )
    return jax.device_put(tree, shardings)


# ---------------------------------------------------------------------------
# Placement policies — the paper's "swap the kind, everything else unchanged"
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PlacementPolicy:
    """Where each state group lives in the hierarchy.

    Mirrors the paper's memory-kind declarations at the granularity that
    matters for a training/serving framework.  ``params_stream`` /
    ``opt_stream`` toggle per-layer streaming (pass-by-reference + prefetch)
    for host-resident groups; non-streamed host groups are bulk-copied at the
    step boundary (the paper's "eager" mode).
    """

    name: str = "all_device"
    params: MemKind = DEVICE
    opt_state: MemKind = DEVICE
    kv_cache: MemKind = DEVICE
    #: prefetch distance (layers ahead) when params are host-resident
    params_prefetch_distance: int = 1
    #: layers fetched per transfer ("elements per pre-fetch" of paper §3.1)
    params_layers_per_fetch: int = 1

    def with_(self, **kw: Any) -> "PlacementPolicy":
        return dataclasses.replace(self, **kw)

    def requires_host(self) -> bool:
        return any(
            k.jax_kind != "device" for k in (self.params, self.opt_state, self.kv_cache)
        )

    def requires_spill(self) -> bool:
        """True if any state group lives at a non-XLA level (disk)."""
        return any(
            not k.jax_addressable
            for k in (self.params, self.opt_state, self.kv_cache)
        )


ALL_DEVICE = PlacementPolicy(name="all_device")
#: Adam moments + f32 master on host — the biggest win for large dense models
HOST_OPT = PlacementPolicy(name="host_opt", opt_state=PINNED_HOST)
#: weights live on host, streamed per layer with prefetch (paper's flagship mode)
HOST_PARAMS = PlacementPolicy(name="host_params", params=PINNED_HOST)
HOST_ALL = PlacementPolicy(
    name="host_all", params=PINNED_HOST, opt_state=PINNED_HOST, kv_cache=PINNED_HOST
)
#: Adam moments + f32 master spill to disk (larger-than-host-RAM training)
DISK_OPT = PlacementPolicy(name="disk_opt", opt_state=DISK_HOST)
#: weights live on disk, streamed disk->host->device (larger-than-RAM models)
DISK_PARAMS = PlacementPolicy(name="disk_params", params=DISK_HOST)

POLICIES = {
    p.name: p
    for p in (ALL_DEVICE, HOST_OPT, HOST_PARAMS, HOST_ALL, DISK_OPT, DISK_PARAMS)
}


def get_policy(name: str) -> PlacementPolicy:
    try:
        return POLICIES[name]
    except KeyError:
        raise ValueError(f"unknown placement policy {name!r}; have {sorted(POLICIES)}") from None
