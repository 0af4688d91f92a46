"""Plain reference of the dense decoders the benchmark runs.

Straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16), with
no kernel, cache or batching of the program's.  It follows the published
Llama / OLMo block: pre-norm (RMSNorm with a scale, or OLMo's LayerNorm
without scale or bias), rotary positions in the rotate-half layout, grouped
query attention, SwiGLU, a final norm and the tied embedding as the output
head.  It imports nothing of the program.

``quant=True`` is the control: every operand of every matrix product is
rounded to float8 (e4m3, one scale per tensor) on the way in, the step below
the bfloat16 the configurations are served and trained in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.model import Dims

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def fp8(x: jax.Array) -> jax.Array:
    """``x`` rounded to float8 e4m3 under one per-tensor scale; the gradient
    passes straight through."""
    scale = F8_MAX / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


class Reference:
    def __init__(self, dims: Dims, *, quant: bool = False) -> None:
        self.dims = dims
        self.quant = quant

    def mm(self, spec: str, a, b):
        if self.quant:
            a, b = fp8(a), fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def norm(self, x, scale):
        eps = self.dims.eps
        if self.dims.norm == "rmsnorm":
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)

    def rope(self, x, pos):
        """x (B, S, N, H), pos (S,) -> rotated x."""
        h = self.dims.head_dim
        inv = 1.0 / (self.dims.rope_theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
        ang = pos.astype(jnp.float32)[:, None] * inv  # (S, H/2)
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., : h // 2], x[..., h // 2 :]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def layer(self, x, p):
        dims = self.dims
        b, s, _ = x.shape
        pos = jnp.arange(s)
        h = self.norm(x, p.get("attn_norm"))
        q = self.rope(self.mm("bsd,dnh->bsnh", h, p["wq"]), pos)
        k = self.rope(self.mm("bsd,dkh->bskh", h, p["wk"]), pos)
        v = self.mm("bsd,dkh->bskh", h, p["wv"])
        g = dims.n_heads // dims.n_kv
        qg = q.reshape(b, s, dims.n_kv, g, dims.head_dim)
        scores = self.mm("bskgh,btkh->bkgst", qg, k) * dims.head_dim ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(causal, scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        att = self.mm("bkgst,btkh->bskgh", probs, v).reshape(b, s, dims.n_heads, dims.head_dim)
        x = x + self.mm("bsnh,nhd->bsd", att, p["wo"])
        h = self.norm(x, p.get("mlp_norm"))
        a = jax.nn.silu(self.mm("bsd,df->bsf", h, p["w_gate"]))
        u = self.mm("bsd,df->bsf", h, p["w_up"])
        return x + self.mm("bsf,fd->bsd", a * u, p["w_down"])

    def hidden(self, w, tokens):
        """tokens (B, S) -> final-normed hidden states (B, S, D), one layer
        at a time (each layer recomputed in the backward pass)."""
        layer_keys = [k for k in w if k not in ("embed", "final_norm")]
        stacked = {k: w[k] for k in layer_keys}
        x = w["embed"][tokens]

        @jax.checkpoint
        def body(x, p):
            return self.layer(x, p), None

        x, _ = jax.lax.scan(body, x, stacked)
        return self.norm(x, w.get("final_norm"))

    def logits(self, w, h):
        return self.mm("bsd,vd->bsv", h, w["embed"])

    def loss(self, w, tokens, targets, chunk: int = 256):
        """Mean next-token cross-entropy over every position."""
        h = self.hidden(w, tokens)
        b, s, d = h.shape
        c = min(chunk, s)
        hs = jnp.moveaxis(h.reshape(b, s // c, c, d), 1, 0)
        ts = jnp.moveaxis(targets.reshape(b, s // c, c), 1, 0)

        @jax.checkpoint
        def nll(hc, tc):
            lg = self.logits(w, hc)
            gold = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0]
            return jnp.sum(jax.nn.logsumexp(lg, -1) - gold)

        tot, _ = jax.lax.scan(lambda t, a: (t + nll(*a), None), jnp.zeros(()), (hs, ts))
        return tot / (b * s)


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
def _train_step(ref: Reference, opt_items: tuple, state, tokens, targets):
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


def train_readings(ref: Reference, w0: dict, batches: list, opt: dict) -> dict:
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


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(ref: Reference, ctrl, w, tokens):
    """For every position t of ``tokens`` (1, T): the reference's best logit
    minus its logit of ``tokens[t + 1]``, and (with a control) minus its logit
    of the token the control puts first."""
    h = ref.hidden(w, tokens)
    hc = ctrl.hidden(w, tokens) if ctrl is not None else None
    t = tokens.shape[1]
    nxt = jnp.concatenate([tokens[0, 1:], tokens[0, :1]])
    c = 128 if t % 128 == 0 else t

    def rows(args):
        hr, nx, hq = args
        lg = ref.logits(w, hr[None])[0]
        best = jnp.max(lg, -1)
        gap = best - jnp.take_along_axis(lg, nx[:, None], -1)[:, 0]
        if hq is None:
            return gap, jnp.zeros_like(gap)
        top = jnp.argmax(ctrl.logits(w, hq[None])[0], -1)
        return gap, best - jnp.take_along_axis(lg, top[:, None], -1)[:, 0]

    hs = h[0].reshape(t // c, c, -1)
    hq = hc[0].reshape(t // c, c, -1) if hc is not None else None
    gap, cgap = jax.lax.map(rows, (hs, nxt.reshape(t // c, c), hq))
    return gap.reshape(t), cgap.reshape(t)


def served_gaps(ref: Reference, w: dict, prompt, served, width: int, ctrl=None):
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
        gap, cgap = _gaps(ref, ctrl, w, jax.numpy.asarray(seq[None]))
    sl = slice(p - 1, p - 1 + n)
    return np.asarray(gap)[sl], np.asarray(cgap)[sl]
