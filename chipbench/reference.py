"""What the plain references of every architecture share.

Each architecture (``chipbench/archs/<arch>.py``) brings its own
``Reference(dims, quant=False)`` with ``hidden``, ``logits`` and ``loss``, in
float32 with every matrix product at ``Precision.HIGHEST`` (a TPU otherwise
multiplies float32 in bfloat16), importing nothing of the program.  This
module drives them: AdamW as the traffic file states it
(:func:`train_readings`), and the gap of each served token below the
reference's best logit (:func:`served_gaps`).  A reference whose weights do
not fit the device at once sets ``blocked = True``: its ``hidden`` and
``logits`` then run eagerly, each jitting its own blocks (a layer at a time),
instead of under one jit over the whole tree.

``quant=True`` is the control: every operand of every matrix product is
rounded to float8 (:func:`fp8`: e4m3, one scale per tensor) on the way in,
the step below the bfloat16 the configurations are served and trained in.

``Reference`` is the dense architecture's, kept under the name it had here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def __getattr__(name: str):
    if name == "Reference":
        from chipbench.archs.dense import Reference

        return Reference
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one per-tensor scale; the gradient
    passes straight through."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


# ---------------------------------------------------------------------------
# training: AdamW as the traffic file states it
# ---------------------------------------------------------------------------


def lr_at(opt: dict, step):
    """Linear warm-up, then cosine decay to ``min_lr_ratio`` (1-based step)."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm, total = opt["peak_lr"], opt["warmup_steps"], opt["total_steps"]
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    r = opt["min_lr_ratio"]
    cos = peak * (r + (1 - r) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()}


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _train_step(ref, opt_items: tuple, state, tokens, targets):
    opt = dict(opt_items)
    w, m, v, step = state["w"], state["m"], state["v"], state["step"] + 1
    loss, g = jax.value_and_grad(ref.loss)(w, tokens, targets)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in g.values()))
    clip = opt["grad_clip"]
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
    lr = lr_at(opt, step)
    b1, b2 = opt["b1"], opt["b2"]
    bc1, bc2 = 1.0 - b1 ** step.astype(jnp.float32), 1.0 - b2 ** step.astype(jnp.float32)
    new = {"w": {}, "m": {}, "v": {}, "step": step}
    applied = {}
    for k in w:
        gk = g[k] * scale
        applied[k] = gk
        mk = b1 * m[k] + (1 - b1) * gk
        vk = b2 * v[k] + (1 - b2) * gk * gk
        upd = (mk / bc1) / (jnp.sqrt(vk / bc2) + opt["eps"])
        new["w"][k] = w[k] * (1.0 - lr * opt["weight_decay"]) - lr * upd
        new["m"][k], new["v"][k] = mk, vk
    return new, loss, applied


def train_readings(ref, w0: dict, batches: list, opt: dict) -> dict:
    """Run ``len(batches)`` AdamW steps from ``w0``.

    Returns each step's loss, the first step's gradient as the optimizer
    applies it (after clipping: its leaves and their norms), and the per-leaf
    norms of the weights' change after the last step."""
    state = {
        "w": {k: jnp.array(x, jnp.float32) for k, x in w0.items()},
        "m": {k: jnp.zeros(x.shape, jnp.float32) for k, x in w0.items()},
        "v": {k: jnp.zeros(x.shape, jnp.float32) for k, x in w0.items()},
        "step": jnp.zeros((), jnp.int32),
    }
    losses, first = [], None
    items = tuple(sorted(opt.items()))
    with jax.default_matmul_precision("highest"):
        for tokens, targets in batches:
            state, loss, applied = _train_step(ref, items, state, tokens, targets)
            losses.append(float(loss))
            if first is None:
                first = applied
        delta = jax.jit(
            lambda a, b: leaf_norms({k: a[k] - b[k].astype(jnp.float32) for k in a})
        )(state["w"], w0)
    return {
        "loss": losses,
        "grad_norm": {k: float(x) for k, x in jax.jit(leaf_norms)(first).items()},
        "grad_norm.leaves": first,
        "delta_norm": {k: float(x) for k, x in delta.items()},
    }


# ---------------------------------------------------------------------------
# serving: the gap of each served token below the reference's best
# ---------------------------------------------------------------------------


def _row_gaps(ref, ctrl, w, hr, nx, hq):
    """Gaps of one block of rows: ``hr`` (C, D) hidden states, ``nx`` (C,)
    the tokens that follow, ``hq`` the control's hidden states or None."""
    lg = ref.logits(w, hr[None])[0]
    best = jnp.max(lg, -1)
    gap = best - jnp.take_along_axis(lg, nx[:, None], -1)[:, 0]
    if hq is None:
        return gap, jnp.zeros_like(gap)
    top = jnp.argmax(ctrl.logits(w, hq[None])[0], -1)
    return gap, best - jnp.take_along_axis(lg, top[:, None], -1)[:, 0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(ref, ctrl, w, tokens):
    """For every position t of ``tokens`` (1, T): the reference's best logit
    minus its logit of ``tokens[t + 1]``, and (with a control) minus its logit
    of the token the control puts first."""
    h = ref.hidden(w, tokens)
    hc = ctrl.hidden(w, tokens) if ctrl is not None else None
    t = tokens.shape[1]
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    c = 128 if t % 128 == 0 else t
    hs = h[0].reshape(t // c, c, -1)
    hq = hc[0].reshape(t // c, c, -1) if hc is not None else None
    gap, cgap = jax.lax.map(
        lambda a: _row_gaps(ref, ctrl, w, *a), (hs, nxt.reshape(t // c, c), hq)
    )
    return gap.reshape(t), cgap.reshape(t)


def _gaps_blocked(ref, ctrl, w, tokens):
    """:func:`_gaps` of a ``blocked`` reference, run eagerly."""
    h = ref.hidden(w, tokens)
    hc = ctrl.hidden(w, tokens) if ctrl is not None else None
    t = tokens.shape[1]
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    c = 128 if t % 128 == 0 else t
    parts = [
        _row_gaps(ref, ctrl, w, h[0, i : i + c], nxt[i : i + c],
                  None if hc is None else hc[0, i : i + c])
        for i in range(0, t, c)
    ]
    return jnp.concatenate([g for g, _ in parts]), jnp.concatenate([q for _, q in parts])


def served_gaps(ref, w, prompt, served, width: int, ctrl=None):
    """Gaps of ``served`` (the tokens a program emitted after ``prompt``),
    position by position; ``width`` is the padded length compiled for."""
    import numpy as np

    p, n = len(prompt), len(served)
    seq = np.zeros((width,), np.int32)
    full = np.concatenate([prompt, served]).astype(np.int32)
    if len(full) > width:
        raise ValueError(f"request of {len(full)} tokens exceeds width {width}")
    seq[: len(full)] = full
    with jax.default_matmul_precision("highest"):
        gaps = _gaps_blocked if getattr(ref, "blocked", False) else _gaps
        gap, cgap = gaps(ref, ctrl, w, jax.numpy.asarray(seq[None]))
    sl = slice(p - 1, p - 1 + n)
    return np.asarray(gap)[sl], np.asarray(cgap)[sl]
