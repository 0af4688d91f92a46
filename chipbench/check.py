"""The numbers that decide ``correct``, and their limits.

Training compares, against the reference's own three steps from the same
weights and rows:

``loss_gap``
    the largest relative gap of a step's loss;
``grad_gap``
    the first step's gradient as the optimizer applies it (the program's
    first moment over ``1 - b1``): the worst leaf's gap between the
    program's norm and the reference's, over the larger of that leaf's
    reference norm and the median leaf's;
``delta_gap``
    the weights' change after the checked steps (the program's f32 masters
    less the initial weights), measured as ``grad_gap`` is, over the leaves
    whose reference gradient is at least a thousandth of the median leaf's
    (a leaf below that moves under Adam by round-off alone);
``grad_err``
    the first step's gradient again, by the worst leaf's norm of the
    difference from the reference's over the same denominator.  The norms
    above average rounding away; this one is what separates a step computed
    in a lower precision.

Serving compares ``token_gap``: the widest gap by which a served token's
reference logit lies below the reference's best logit at its position.

Each limit lives in ``chipbench/limits/<workload>.json`` beside the readings
it was set from.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
#: a leaf whose reference gradient is below this share of the median leaf's
#: is left out of ``delta_gap``
DEAD_LEAF = 1e-3


def _leaf_gap(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in ref]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def _leaf_err(prog: dict, ref: dict, ref_norms: dict) -> float:
    """Worst leaf's ``|prog - ref|`` over ``max(|ref|, median leaf |ref|)``."""
    import jax
    import jax.numpy as jnp

    dist = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    med = float(np.median(list(ref_norms.values())))
    return max(
        float(dist(jnp.asarray(prog[k]), ref[k])) / max(ref_norms[k], med) for k in ref
    )


def train_numbers(prog: dict, ref: dict) -> dict:
    loss = max(abs(p - r) / abs(r) for p, r in zip(prog["loss"], ref["loss"]))
    grads = ref["grad_norm"]
    med = float(np.median(list(grads.values())))
    live = [k for k, g in grads.items() if g >= DEAD_LEAF * med]
    return {
        "loss_gap": float(loss),
        "grad_gap": _leaf_gap(prog["grad_norm"], grads, grads),
        "delta_gap": _leaf_gap(prog["delta_norm"], ref["delta_norm"], live),
        "grad_err": _leaf_err(prog["grad_norm.leaves"], ref["grad_norm.leaves"], grads),
    }


def serve_numbers(gaps) -> dict:
    return {"token_gap": float(np.max(gaps)) if len(gaps) else math.nan}


def limits(workload: str, limits_dir: Path = ROOT / "limits") -> dict:
    raw = json.loads((Path(limits_dir) / f"{workload}.json").read_text())
    return {k: float(v["limit"]) for k, v in raw.items()}


def judge(numbers: dict, lim: dict) -> tuple[bool, dict]:
    """``(correct, {name: {"value", "limit"}})`` over the numbers that have a
    limit; one that is missing or not finite fails."""
    checks = {
        k: {"value": numbers.get(k, math.nan), "limit": v} for k, v in lim.items()
    }
    ok = all(
        math.isfinite(c["value"]) and c["value"] <= c["limit"] for c in checks.values()
    )
    return bool(ok and checks), checks
