"""Weights drawn from a run's seed, owned by the benchmark.

One jitted call makes every leaf on the device.  The reference reads them in
its own layout (:func:`make`); :func:`to_program` renames them into the
program's parameter tree, which the harness checks leaf for leaf against the
program's own abstract tree before handing it over.

Matrices are normal with standard deviation ``fan_in ** -0.5`` and the table
``0.02``, as usual for a fresh model; norm scales are ``1 + 0.1 * normal`` so
that a path which drops a scale does not pass by accident.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.model import Dims


def prng_key(seed: int) -> jax.Array:
    """A key for any whole ``seed`` below 2**64 (seeds may exceed 32 bits)."""
    return jax.random.PRNGKey(int(seed) % (1 << 64))


def shapes(dims: Dims) -> dict:
    """Reference-layout leaf shapes: ``{name: (shape, fan_in or None)}``."""
    L, d, f = dims.layers, dims.d, dims.f
    n, k, h = dims.n_heads, dims.n_kv, dims.head_dim
    out = {
        "embed": ((dims.vocab, d), None),
        "wq": ((L, d, n, h), d),
        "wk": ((L, d, k, h), d),
        "wv": ((L, d, k, h), d),
        "wo": ((L, n, h, d), n * h),
        "w_gate": ((L, d, f), d),
        "w_up": ((L, d, f), d),
        "w_down": ((L, f, d), f),
    }
    if dims.norm == "rmsnorm":
        out.update(attn_norm=((L, d), 0), mlp_norm=((L, d), 0), final_norm=((d,), 0))
    return out


def make(dims: Dims, seed: int, dtype=jnp.float32) -> dict:
    """Every weight of the model in reference layout, made on the device."""
    spec = shapes(dims)

    @jax.jit
    def build(key):
        out = {}
        for i, (name, (shape, fan_in)) in enumerate(sorted(spec.items())):
            z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            if fan_in is None:
                w = 0.02 * z
            elif fan_in == 0:  # a norm scale
                w = 1.0 + 0.1 * z
            else:
                w = z * fan_in ** -0.5
            out[name] = w.astype(dtype)
        return out

    return build(prng_key(seed))


def to_program(w: dict, dims: Dims) -> dict:
    """Reference layout -> the program's parameter tree (same arrays)."""
    norm = (lambda s: {"scale": s}) if dims.norm == "rmsnorm" else (lambda s: {})
    return {
        "embed": {"tok": w["embed"]},
        "ln_f": norm(w.get("final_norm")),
        "blocks": {
            "ln1": norm(w.get("attn_norm")),
            "ln2": norm(w.get("mlp_norm")),
            "attn": {"wq": w["wq"], "wk": w["wk"], "wv": w["wv"], "wo": w["wo"]},
            # the program's SwiGLU is silu(x @ wi) * (x @ wg) @ wo
            "mlp": {"wi": w["w_gate"], "wg": w["w_up"], "wo": w["w_down"]},
        },
    }


def from_program(tree: dict, dims: Dims) -> dict:
    """The program's parameter tree -> reference layout (inverse of
    :func:`to_program`), for reading the program's state back."""
    b = tree["blocks"]
    out = {
        "embed": tree["embed"]["tok"],
        "wq": b["attn"]["wq"], "wk": b["attn"]["wk"],
        "wv": b["attn"]["wv"], "wo": b["attn"]["wo"],
        "w_gate": b["mlp"]["wi"], "w_up": b["mlp"]["wg"], "w_down": b["mlp"]["wo"],
    }
    if dims.norm == "rmsnorm":
        out.update(
            attn_norm=b["ln1"]["scale"], mlp_norm=b["ln2"]["scale"],
            final_norm=tree["ln_f"]["scale"],
        )
    return out


def check_layout(program_tree, abstract_tree) -> None:
    """Raise unless ``program_tree`` has the program's structure and shapes."""
    got = jax.tree.structure(program_tree)
    want = jax.tree.structure(abstract_tree)
    if got != want:
        raise ValueError(f"weight tree {got} is not the program's {want}")
    for a, b in zip(jax.tree.leaves(program_tree), jax.tree.leaves(abstract_tree)):
        if tuple(a.shape) != tuple(b.shape):
            raise ValueError(f"weight shape {a.shape} is not the program's {b.shape}")
