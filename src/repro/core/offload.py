"""The ``@offload`` decorator — paper Listings 1-3, TPU-native.

Paper semantics: decorating a function with ``@offload`` makes calls execute
on the accelerator; arguments are passed **by reference** and the runtime
moves data according to each argument's memory kind and optional prefetch
annotation.

Here, "the accelerator" is the TPU mesh: ``@offload`` compiles the function
with per-argument shardings + memory kinds derived from ``OffloadRef``
annotations, and materializes arguments at their declared hierarchy level on
first use.  Host-kind arguments annotated with a ``PrefetchSpec`` are streamed
block-wise through the graph engine instead of bulk-copied.

Example (paper Listing 3 analogue)::

    from repro.core import offload, OffloadRef, PrefetchSpec, memkind as mk

    @offload(refs=dict(
        a=OffloadRef(kind=mk.PINNED_HOST,
                     prefetch=PrefetchSpec(buffer_size=10, elements_per_fetch=2,
                                           distance=4)),
        b=OffloadRef(kind=mk.PINNED_HOST,
                     prefetch=PrefetchSpec(buffer_size=10, elements_per_fetch=2,
                                           distance=4)),
    ))
    def mykernel(a, b):
        return a + b
"""
from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, Mapping, Optional

import jax
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec

from repro.core import memkind as mk
from repro.core import prefetch as pf
from repro.core.refspec import OffloadRef

__all__ = ["offload"]


def _default_mesh() -> Mesh:
    dev = jax.devices()
    return jax.make_mesh((len(dev),), ("data",), axis_types=(AxisType.Auto,))


class OffloadedFunction:
    """Callable produced by ``@offload``.  Keeps the paper's behaviours:

    * ``__call__`` — execute on the mesh, honouring each ref's kind+prefetch.
    * ``.eager``   — force the paper's original bulk-copy invocation.
    * ``.place(name, value)`` — the paper's ``define_on_device`` /
      ``copy_to_device``: materialize an argument at its declared kind ahead
      of the call so repeated invocations skip the transfer.
    """

    def __init__(
        self,
        fn: Callable[..., Any],
        refs: Mapping[str, OffloadRef],
        mesh: Optional[Mesh],
        out_specs: Any,
        donate: tuple[str, ...] = (),
    ) -> None:
        functools.update_wrapper(self, fn)
        self._fn = fn
        self._refs = dict(refs)
        self._mesh = mesh
        self._out_specs = out_specs
        self._donate = donate
        self._signature = inspect.signature(fn)
        self._params = list(self._signature.parameters)
        unknown = set(refs) - set(self._params)
        if unknown:
            raise ValueError(f"refs for unknown arguments: {sorted(unknown)}")
        self._compiled: dict[Any, Callable] = {}
        #: host-stream executors, keyed on (streamed-arg set, per-arg memory
        #: kinds, engine identity) — see stream_host.  Keying on the arg set
        #: alone reused a stale executor (wrong engine / wrong tier) when the
        #: caller switched PlacementPolicy between calls.
        self._stream_host_cache: dict[tuple, "HostStreamExecutor"] = {}
        #: lazily-created spill store for DiskHost-kind streamed args
        self._spill_store: Any = None

    # -- placement helpers ---------------------------------------------------
    def mesh(self) -> Mesh:
        if self._mesh is None:
            self._mesh = _default_mesh()
        return self._mesh

    def _ref(self, name: str) -> OffloadRef:
        return self._refs.get(name, OffloadRef())

    def _home_sharding(self, name: str):
        r = self._ref(name)
        return mk.sharding_for(self.mesh(), r.spec, r.kind)

    def _device_sharding(self, name: str):
        r = self._ref(name)
        return mk.sharding_for(self.mesh(), r.spec, mk.DEVICE)

    def place(self, name: str, value: Any) -> jax.Array:
        """Materialize ``value`` at the argument's declared hierarchy level."""
        if name not in self._params:
            raise ValueError(f"{name!r} is not an argument of {self._fn.__name__}")
        return jax.device_put(value, self._home_sharding(name))

    # -- invocation ----------------------------------------------------------
    def _build(self, streamed: bool):
        names = self._params
        in_shardings = tuple(self._home_sharding(n) for n in names)
        donate_argnums = tuple(i for i, n in enumerate(names) if n in self._donate)

        stream_names = [
            n for n in names if self._ref(n).streamed and streamed
        ]

        if not stream_names:
            fn = self._fn
        else:
            # Streamed refs are processed block-wise over their stream axis
            # (all streamed args must agree on leading-axis length); the rest
            # are closed over.  fn must be a per-element map for this path —
            # the framework's layer streaming uses prefetch.streamed_scan
            # directly instead (see repro/train/steps.py).
            refs = {n: self._ref(n) for n in stream_names}
            spec = next(iter(refs.values())).prefetch
            base = self._fn

            def fn(*args):
                bound = dict(zip(names, args))
                streamed_args = tuple(bound[n] for n in stream_names)
                dev_sh = tuple(
                    jax.tree.map(lambda _: self._device_sharding(n), bound[n])
                    for n in stream_names
                )

                def block_fn(*blocks):
                    full = dict(bound)
                    full.update(dict(zip(stream_names, blocks)))
                    return base(**full)

                return pf.stream_blocks(
                    block_fn, streamed_args, prefetch=spec, dev_shardings=dev_sh
                )

        out_shardings = (
            None
            if self._out_specs is None
            else jax.tree.map(
                lambda s: mk.sharding_for(self.mesh(), s),
                self._out_specs,
                is_leaf=lambda s: isinstance(s, PartitionSpec),
            )
        )
        return jax.jit(
            fn,
            in_shardings=in_shardings,
            out_shardings=out_shardings,
            donate_argnums=donate_argnums,
        )

    def _call(self, streamed: bool, *args: Any, **kwargs: Any) -> Any:
        bound = self._signature.bind(*args, **kwargs)
        bound.apply_defaults()
        key = streamed
        if key not in self._compiled:
            self._compiled[key] = self._build(streamed)
        ordered = tuple(bound.arguments[n] for n in self._params)
        # materialize at home kinds (pass-by-reference: host args stay host)
        placed = tuple(
            v if isinstance(v, jax.Array) else self.place(n, v)
            for n, v in zip(self._params, ordered)
        )
        return self._compiled[key](*placed)

    def __call__(self, *args: Any, **kwargs: Any) -> Any:
        return self._call(True, *args, **kwargs)

    def eager(self, *args: Any, **kwargs: Any) -> Any:
        """Paper's original eager-copy invocation (bulk transfer, then run)."""
        return self._call(False, *args, **kwargs)

    def stream_host(
        self,
        *args: Any,
        mode: str = "prefetch",
        engine: Any = None,
        stats: Any = None,
        policy: Any = None,
        spill_dir: Any = None,
        **kwargs: Any,
    ) -> Any:
        """Run with streamed refs served by the *host-side* transfer engine.

        Where ``__call__`` streams inside the compiled program (the graph
        engine — static ring, fixed distance), this path is the paper's §4
        runtime architecture: streamed arguments stay host-resident numpy,
        a background :class:`~repro.core.engine.TransferEngine` coalesces
        and prefetches blocks ahead of the jitted per-block apply, and the
        block outputs write back to the host kind (``rw``).  It honours
        ``PrefetchSpec(distance="auto")`` (runtime-adaptive window) and is
        numerically identical to ``__call__``/``eager``.

        Under a multi-device mesh the streamed blocks are staged at each
        ref's *device* sharding through the engine's sharding-aware
        coalescing: one H2D request per (addressable device, block group)
        — the per-leaf fallback that re-introduced the request storm under
        ``--model-parallel`` is gone — and staged blocks are bitwise equal
        to eager sharded placement.

        ``policy`` (a :class:`~repro.core.memkind.PlacementPolicy`)
        overrides the home tier of the streamed arguments at call time —
        its ``params`` kind applies to every streamed ref.  A non-XLA kind
        (``DiskHost``) spills each block to a chunk-granular
        :class:`~repro.core.spillstore.SpillStore` (under ``spill_dir``, or
        a private temp dir) and streams it through the engine's two-stage
        disk->host->device pipeline — same values, one more hierarchy
        level.

        The executor (jitted per-block apply + engine worker) is cached per
        (streamed-arg set, per-arg memory kind, engine identity); switching
        ``policy`` or ``engine`` between calls therefore builds a fresh
        executor instead of silently reusing a stale one.  Call
        :meth:`close` to release the workers.
        """
        from repro.core.hoststream import HostStreamExecutor

        bound = self._signature.bind(*args, **kwargs)
        bound.apply_defaults()
        stream_names = [n for n in self._params if self._ref(n).streamed]
        if not stream_names:
            return self(*args, **kwargs)
        spec = self._ref(stream_names[0]).prefetch
        g = spec.elements_per_fetch
        kinds = tuple(
            (policy.params if policy is not None else self._ref(n).kind)
            for n in stream_names
        )
        fixed = {
            n: v if isinstance(v, jax.Array) else self.place(n, v)
            for n, v in bound.arguments.items()
            if n not in stream_names
        }
        streamed_vals = {n: bound.arguments[n] for n in stream_names}
        n_rows = jax.tree.leaves(streamed_vals[stream_names[0]])[0].shape[0]
        if n_rows % g != 0:
            raise ValueError(
                f"leading axis {n_rows} not divisible by elements_per_fetch={g}"
            )

        # the executor (and its jitted per-block apply + engine worker) is
        # built once per (streamed-arg set, kinds, engine, mesh, streamed
        # tree structure) and reused across calls; the fixed arguments
        # travel in the carry, so new values don't retrace.  The structure
        # is part of the key because the executor's broadcast
        # device_shardings are derived from it — a different pytree shape
        # for the same arg name needs a fresh executor
        key = (
            tuple(stream_names),
            tuple(k.jax_kind for k in kinds),
            id(engine) if engine is not None else None,
            self.mesh(),
            tuple(jax.tree.structure(streamed_vals[n]) for n in stream_names),
        )
        ex = self._stream_host_cache.get(key)
        if ex is None:
            base = self._fn

            @jax.jit
            def apply(carry, block):
                return carry, base(**carry, **dict(zip(stream_names, block)))

            # stage each block at its ref's device sharding: under a mesh
            # the engine packs one buffer per (device, group) instead of
            # falling back to per-leaf placement
            dev_sh = tuple(
                jax.tree.map(lambda _: self._device_sharding(n), streamed_vals[n])
                for n in stream_names
            )
            ex = HostStreamExecutor(
                apply, writeback=True, engine=engine, device_shardings=dev_sh
            )
            self._stream_host_cache[key] = ex

        groups = [
            tuple(
                jax.tree.map(lambda a: a[i : i + g], streamed_vals[n])
                for n in stream_names
            )
            for i in range(0, n_rows, g)
        ]
        if any(not k.jax_addressable for k in kinds):
            groups = [
                self._spill(f"g{i:04d}", grp, spill_dir)
                for i, grp in enumerate(groups)
            ]
        _, outs = ex.run(fixed, groups, mode=mode, prefetch=spec, stats=stats)
        return jax.tree.map(lambda *xs: np.concatenate(xs, axis=0), *outs)

    def _spill(self, key: str, group: Any, spill_dir: Any) -> Any:
        """Move one block to the DiskHost tier: persist it in the spill
        store and return the memory-mapped view tree.  A privately created
        temp store is ephemeral (deleted on close); a caller-supplied
        ``spill_dir`` is durable and never deleted."""
        import pathlib

        from repro.core.spillstore import SpillStore

        if self._spill_store is not None and spill_dir is not None:
            if pathlib.Path(spill_dir) != self._spill_store.dir:
                raise ValueError(
                    f"stream_host already bound a spill store at "
                    f"{str(self._spill_store.dir)!r}; close() before "
                    f"switching to spill_dir={str(spill_dir)!r}"
                )
        if self._spill_store is None:
            ephemeral = spill_dir is None
            if ephemeral:
                import tempfile

                spill_dir = tempfile.mkdtemp(
                    prefix=f"repro-spill-{self._fn.__name__}-"
                )
            self._spill_store = SpillStore(spill_dir, ephemeral=ephemeral)
        self._spill_store.put(key, group)
        return self._spill_store.get(key)

    def close(self) -> None:
        """Shut down any host-stream executors (and their engine workers),
        and drop the spill store (deleting it if privately created)."""
        for ex in self._stream_host_cache.values():
            ex.close()
        self._stream_host_cache.clear()
        if self._spill_store is not None:
            self._spill_store.close()  # deletes iff the store is ephemeral
            self._spill_store = None

    def lower(self, *args: Any, streamed: bool = True):
        """Lower without executing (dry-run path; keeps true memory kinds)."""
        if streamed not in self._compiled:
            self._compiled[streamed] = self._build(streamed)
        return self._compiled[streamed].lower(*args)


def offload(
    fn: Optional[Callable[..., Any]] = None,
    *,
    refs: Optional[Mapping[str, OffloadRef]] = None,
    mesh: Optional[Mesh] = None,
    out_specs: Any = None,
    donate: tuple[str, ...] = (),
) -> Any:
    """Decorate a function for accelerator offload (see module docstring)."""

    def wrap(f: Callable[..., Any]) -> OffloadedFunction:
        return OffloadedFunction(f, refs or {}, mesh, out_specs, donate)

    if fn is not None:
        return wrap(fn)
    return wrap
