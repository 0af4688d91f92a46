"""A fixture architecture for the tests: the decoder the program's
``minitron-4b`` runs, which no benchmark configuration uses.

Pre-norm LayerNorm with a scale and a bias, rotary positions over the whole
head in the rotate-half layout, grouped-query attention, a squared-ReLU MLP
(``relu(x @ wi) ** 2 @ wo``), a final LayerNorm and an output head of its own
(untied).  A test copies this file to ``<root>/archs/`` to show that a new
architecture needs new files only.

It builds its host tree one layer at a time, so set-up never holds more than
one layer's leaves on the device, and hands its reference the weights a layer
at a time (``Reference.blocked``).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import HIGHEST, fp8
from chipbench.weights import prng_key

SCALE, BIAS, TABLE = 0, -1, None  # kinds of leaf besides a matrix's fan-in


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    d: int
    f: int
    n_heads: int
    n_kv: int
    head_dim: int
    layers: int
    vocab: int
    eps: float
    rope_theta: float

    @property
    def layer_params(self) -> int:
        d, n, k, h = self.d, self.n_heads, self.n_kv, self.head_dim
        return d * (n + 2 * k) * h + n * h * d + 2 * d * self.f + 4 * d


def dims(raw: dict, name: str) -> Dims:
    if raw["tie_word_embeddings"] or raw["hidden_act"] != "relu2":
        raise ValueError(f"{name}: this architecture is untied with a relu2 MLP")
    return Dims(
        name=name, d=raw["hidden_size"], f=raw["intermediate_size"],
        n_heads=raw["num_attention_heads"], n_kv=raw["num_key_value_heads"],
        head_dim=raw["head_dim"], layers=raw["num_hidden_layers"],
        vocab=raw["vocab_size"], eps=float(raw["norm_eps"]),
        rope_theta=float(raw["rope_theta"]),
    )


def check_program(dims: Dims, pcfg) -> None:
    want = {
        "d_model": dims.d, "d_ff": dims.f, "n_heads": dims.n_heads,
        "n_kv_heads": dims.n_kv, "head_dim": dims.head_dim, "n_layers": dims.layers,
        "vocab_size": dims.vocab, "rope_theta": dims.rope_theta,
        "norm_type": "layernorm", "mlp_type": "relu2", "pos_type": "rope",
        "tie_embeddings": False, "family": "dense", "attn_type": "full",
        "qkv_bias": False, "qk_norm": False, "dtype": "bfloat16",
    }
    bad = {k: (getattr(pcfg, k), v) for k, v in want.items() if getattr(pcfg, k) != v}
    if bad:
        raise ValueError(f"program config {pcfg.name} differs from {dims.name}: {bad}")


def smoke(dims: Dims, pcfg) -> Dims:
    return dataclasses.replace(
        dims, d=pcfg.d_model, f=pcfg.d_ff, n_heads=pcfg.n_heads, n_kv=pcfg.n_kv_heads,
        head_dim=pcfg.head_dim, layers=pcfg.n_layers, vocab=pcfg.vocab_size,
    )


# ---------------------------------------------------------------------------
# weights, in the program's layout: (path, shape, kind) per leaf
# ---------------------------------------------------------------------------


def _outer(dims: Dims) -> tuple:
    d, v = dims.d, dims.vocab
    return (
        (("embed", "tok"), (v, d), TABLE), (("head", "out"), (d, v), d),
        (("ln_f", "scale"), (d,), SCALE), (("ln_f", "bias"), (d,), BIAS),
    )


def _layer(dims: Dims) -> tuple:
    d, f, n, k, h = dims.d, dims.f, dims.n_heads, dims.n_kv, dims.head_dim
    return (
        (("ln1", "scale"), (d,), SCALE), (("ln1", "bias"), (d,), BIAS),
        (("ln2", "scale"), (d,), SCALE), (("ln2", "bias"), (d,), BIAS),
        (("attn", "wq"), (d, n, h), d), (("attn", "wk"), (d, k, h), d),
        (("attn", "wv"), (d, k, h), d), (("attn", "wo"), (n, h, d), n * h),
        (("mlp", "wi"), (d, f), d), (("mlp", "wo"), (f, d), f),
    )


@functools.partial(jax.jit, static_argnums=(1, 2))
def _draw(key, spec: tuple, dtype) -> dict:
    """One block of leaves (the outer leaves, or one layer), on the device."""
    out = {}
    for j, (path, shape, kind) in enumerate(spec):
        z = jax.random.normal(jax.random.fold_in(key, j), shape, jnp.float32)
        if kind is TABLE:
            w = 0.02 * z
        elif kind == SCALE:
            w = 1.0 + 0.1 * z
        elif kind == BIAS:
            w = 0.1 * z
        else:
            w = z * kind ** -0.5
        out[path] = w.astype(dtype)
    return out


def _key(seed: int, layer: int | None) -> jax.Array:
    key = prng_key(seed)
    if layer is None:
        return jax.random.fold_in(key, 0)
    return jax.random.fold_in(jax.random.fold_in(key, 1), layer)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for (a, b), leaf in flat.items():
        tree.setdefault(a, {})[b] = leaf
    return tree


def _tree(outer: dict, layers: list, stack) -> dict:
    blocks = {path: stack([lay[path] for lay in layers]) for path in layers[0]}
    return {**_nest(outer), "blocks": _nest(blocks)}


def device_tree(dims: Dims, seed: int, dtype) -> dict:
    outer = _draw(_key(seed, None), _outer(dims), dtype)
    layers = [_draw(_key(seed, i), _layer(dims), dtype) for i in range(dims.layers)]
    return _tree(outer, layers, jnp.stack)


def host_tree(dims: Dims, seed: int, dtype) -> dict:
    """:func:`device_tree` on the host, drawn a block at a time."""
    outer = jax.device_get(_draw(_key(seed, None), _outer(dims), dtype))
    layers = [
        jax.device_get(_draw(_key(seed, i), _layer(dims), dtype)) for i in range(dims.layers)
    ]
    return _tree(outer, layers, np.stack)


def from_program(tree: dict, dims: Dims) -> dict:
    """The program's tree -> ``{"<group>.<leaf>": leaf}``, layers stacked."""
    out = {f"{a}.{b}": tree[a][b] for (a, b), _, _ in _outer(dims)}
    out.update({f"{a}.{b}": tree["blocks"][a][b] for (a, b), _, _ in _layer(dims)})
    return out


class LayerWeights:
    """The reference's weights, as the program holds them in ``dtype``, in
    float32: the outer leaves on the device, each layer drawn when asked for."""

    def __init__(self, dims: Dims, seed: int, dtype) -> None:
        self.seed, self.dtype, self.spec = seed, dtype, _layer(dims)
        drawn = _draw(_key(seed, None), _outer(dims), dtype)
        self.outer = {p: v.astype(jnp.float32) for p, v in drawn.items()}

    def layer(self, i: int) -> dict:
        drawn = _draw(_key(self.seed, i), self.spec, self.dtype)
        return {p: v.astype(jnp.float32) for p, v in drawn.items()}


def reference_weights(dims: Dims, seed: int, dtype=jnp.float32) -> LayerWeights:
    return LayerWeights(dims, seed, dtype)


# ---------------------------------------------------------------------------
# the plain reference, a layer at a time
# ---------------------------------------------------------------------------


class Reference:
    blocked = True

    def __init__(self, dims: Dims, *, quant: bool = False) -> None:
        self.dims = dims
        self.quant = quant

    def mm(self, spec: str, a, b):
        if self.quant:
            a, b = fp8(a), fp8(b)
        return jnp.einsum(spec, a, b, precision=HIGHEST)

    def norm(self, x, scale, bias):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + self.dims.eps) * scale + bias

    def rope(self, x, pos):
        h = self.dims.head_dim
        inv = 1.0 / (self.dims.rope_theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
        ang = pos.astype(jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = x[..., : h // 2], x[..., h // 2 :]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    @functools.partial(jax.jit, static_argnums=0)
    def layer(self, x, p):
        dims = self.dims
        b, s, _ = x.shape
        pos = jnp.arange(s)
        h = self.norm(x, p["ln1", "scale"], p["ln1", "bias"])
        q = self.rope(self.mm("bsd,dnh->bsnh", h, p["attn", "wq"]), pos)
        k = self.rope(self.mm("bsd,dkh->bskh", h, p["attn", "wk"]), pos)
        v = self.mm("bsd,dkh->bskh", h, p["attn", "wv"])
        g = dims.n_heads // dims.n_kv
        qg = q.reshape(b, s, dims.n_kv, g, dims.head_dim)
        scores = self.mm("bskgh,btkh->bkgst", qg, k) * dims.head_dim ** -0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        att = self.mm("bkgst,btkh->bskgh", probs, v).reshape(b, s, dims.n_heads, dims.head_dim)
        x = x + self.mm("bsnh,nhd->bsd", att, p["attn", "wo"])
        h = self.norm(x, p["ln2", "scale"], p["ln2", "bias"])
        a = jnp.square(jax.nn.relu(self.mm("bsd,df->bsf", h, p["mlp", "wi"])))
        return x + self.mm("bsf,fd->bsd", a, p["mlp", "wo"])

    def hidden(self, w: LayerWeights, tokens):
        x = w.outer["embed", "tok"][tokens]
        for i in range(self.dims.layers):
            x = self.layer(x, w.layer(i))
        return self.norm(x, w.outer["ln_f", "scale"], w.outer["ln_f", "bias"])

    def logits(self, w: LayerWeights, h):
        return self.mm("bsd,dv->bsv", h, w.outer["head", "out"])

    def loss(self, w: LayerWeights, tokens, targets):
        lg = self.logits(w, self.hidden(w, tokens))
        gold = jnp.take_along_axis(lg, targets[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(lg, -1) - gold)


# ---------------------------------------------------------------------------
# model FLOPs: layers, final norm and the untied head; PaLM's attention term
# ---------------------------------------------------------------------------


def matmul_params(dims: Dims) -> int:
    return dims.layers * dims.layer_params + 2 * dims.d + dims.d * dims.vocab


def _attn(dims: Dims) -> int:
    return dims.layers * dims.n_heads * dims.head_dim


def train_per_token(dims: Dims, seq_len: int) -> float:
    return 6.0 * matmul_params(dims) + 6.0 * _attn(dims) * seq_len


def decode_token(dims: Dims, context: int) -> float:
    return 2.0 * matmul_params(dims) + 4.0 * _attn(dims) * context


def prefill(dims: Dims, prompt_len: int) -> float:
    p = prompt_len
    return 2.0 * matmul_params(dims) * p + 2.0 * _attn(dims) * p * (p + 1)
