"""Weights drawn from a run's seed, owned by the benchmark.

Each architecture (``chipbench/archs/<arch>.py``) makes its own weights from
the seed, in the program's parameter tree (``device_tree``, ``host_tree``)
and in its reference's layout (``reference_weights``).  What is common to all
of them lives here: the key a seed gives (:func:`prng_key`) and the check
that a tree has the program's own layout (:func:`check_layout`), which the
harness makes before it hands a tree over.

``shapes``, ``make``, ``to_program`` and ``from_program`` are the dense
architecture's, kept under the names they had here.
"""
from __future__ import annotations

import jax

_DENSE = ("shapes", "make", "to_program", "from_program")


def __getattr__(name: str):
    if name in _DENSE:
        from chipbench.archs import dense

        return getattr(dense, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def prng_key(seed: int) -> jax.Array:
    """A key for any whole ``seed`` below 2**64 (seeds may exceed 32 bits)."""
    return jax.random.PRNGKey(int(seed) % (1 << 64))


def check_layout(program_tree, abstract_tree) -> None:
    """Raise unless ``program_tree`` has the program's structure and shapes."""
    got = jax.tree.structure(program_tree)
    want = jax.tree.structure(abstract_tree)
    if got != want:
        raise ValueError(f"weight tree {got} is not the program's {want}")
    for a, b in zip(jax.tree.leaves(program_tree), jax.tree.leaves(abstract_tree)):
        if tuple(a.shape) != tuple(b.shape):
            raise ValueError(f"weight shape {a.shape} is not the program's {b.shape}")
