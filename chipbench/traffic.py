"""The one generator every traffic file is read by.

A traffic file under ``chipbench/traffic/`` names its entry (``train`` or
``serve``), the placement it runs under and the parameters of its mix.
Everything drawn here comes from the run's ``--seed``, so the same seed gives
the same batches and requests.

Training rows follow the program's own synthetic task (a token period that
repeats, with a share of tokens replaced by noise), copied here so that a
change to the program cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import inspect
import json
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent


def load(name: str, traffic_dir: Path = ROOT / "traffic") -> dict:
    return json.loads((Path(traffic_dir) / f"{name}.json").read_text())


def load_placement(name: str, placements_dir: Path = ROOT / "placements") -> dict:
    return json.loads((Path(placements_dir) / f"{name}.json").read_text())


def entry_kwargs(entry, placement: dict) -> dict:
    """The placement's keys as keyword arguments of ``entry`` (the program's
    ``ServeSession`` or ``build_trainer``), each under its own name; a key
    that ``entry`` does not take is an error, raised before anything is built."""
    params = inspect.signature(entry).parameters
    unknown = sorted(k for k in placement if k not in params)
    if unknown:
        raise ValueError(
            f"placement keys {unknown} are not parameters of {entry.__qualname__}"
        )
    return dict(placement)


# ---------------------------------------------------------------------------
# training rows
# ---------------------------------------------------------------------------


def train_batch_fn(mix: dict, vocab: int, seed: int):
    """A jitted ``step -> (tokens, targets)``; every step's rows differ."""
    import jax
    import jax.numpy as jnp

    from chipbench.weights import prng_key

    b, s, period = mix["batch"], mix["seq_len"], mix["induction_period"]
    base_key = jax.random.fold_in(prng_key(seed), 0x7A11)

    @jax.jit
    def batch(step):
        k1, k2, k3 = jax.random.split(jax.random.fold_in(base_key, step), 3)
        base = jax.random.randint(k1, (b, period), 1, vocab)
        reps = (s + 2 * period - 1) // period
        seq = jnp.tile(base, (1, reps))[:, : s + 1]
        noise = jax.random.bernoulli(k2, mix["noise"], seq.shape)
        seq = jnp.where(noise, jax.random.randint(k3, seq.shape, 1, vocab), seq)
        return seq[:, :-1].astype(jnp.int32), seq[:, 1:].astype(jnp.int32)

    return batch


# ---------------------------------------------------------------------------
# serving requests
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    prompt: np.ndarray  # (p,) int32
    gen: int


def _stratified(lo: int, hi: int, n: int) -> np.ndarray:
    """``n`` lengths spread evenly over ``[lo, hi]`` (both ends included)."""
    return np.rint(np.linspace(lo, hi, n)).astype(np.int64)


class RequestStream:
    """Requests drawn one after another from the seed.

    Lengths come in blocks of ``slots`` requests: each block holds the same
    ``slots`` prompt and output lengths, spread evenly over the mix's
    inclusive ranges, in an order the seed shuffles.  So every seed offers
    the same work in another order.  Token ids are uniform over the
    vocabulary (id 0 left out)."""

    def __init__(self, mix: dict, vocab: int, seed: int) -> None:
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed) % (1 << 64), 0x5E7E])
        self._block: list = []

    def next(self) -> Request:
        if not self._block:
            n = self.mix["slots"]
            p = self.rng.permutation(_stratified(*self.mix["prompt_len"], n))
            g = self.rng.permutation(_stratified(*self.mix["gen_len"], n))
            self._block = list(zip(p.tolist(), g.tolist()))
        p, g = self._block.pop()
        prompt = self.rng.integers(1, self.vocab, size=p, dtype=np.int32)
        return Request(prompt=prompt, gen=g)


def max_len(mix: dict) -> int:
    """Longest context a request of the mix can reach."""
    return mix["prompt_len"][1] + mix["gen_len"][1]
