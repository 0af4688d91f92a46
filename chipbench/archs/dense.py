"""The dense decoder: Llama / OLMo blocks with a tied head and SwiGLU.

An architecture module of the benchmark (``chipbench/archs/<arch>.py``, named
by a configuration file's ``"arch"`` key; a file without the key is
``dense``).  It gives the harness everything that depends on the block:

- the sizes read from the configuration file (:func:`dims`), checked against
  the program's ``ModelConfig`` (:func:`check_program`) or cut to its smoke
  sizes (:func:`smoke`);
- the weights drawn from a run's seed, in the program's parameter tree on the
  device (:func:`device_tree`) or on the host (:func:`host_tree`), read back
  into the reference's layout (:func:`from_program`), and in the reference's
  own layout (:func:`reference_weights`);
- the plain reference (:class:`Reference`);
- model FLOPs (:func:`train_per_token`, :func:`decode_token`,
  :func:`prefill`).

Weights: one jitted call makes every leaf on the device (:func:`make`).
Matrices are normal with standard deviation ``fan_in ** -0.5`` and the table
``0.02``, as usual for a fresh model; norm scales are ``1 + 0.1 * normal`` so
that a path which drops a scale does not pass by accident.

The reference is straight ``jax.numpy`` in float32, every matrix product at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16), with
no kernel, cache or batching of the program's.  It follows the published
Llama / OLMo block: pre-norm (RMSNorm with a scale, or OLMo's LayerNorm
without scale or bias), rotary positions in the rotate-half layout, grouped
query attention, SwiGLU, a final norm and the tied embedding as the output
head.  It imports nothing of the program.  ``quant=True`` is the control:
every operand of every matrix product is rounded to float8 (e4m3, one scale
per tensor) on the way in, the step below the bfloat16 the configurations are
served and trained in.

FLOPs are of the mathematics the model requires, from its sizes alone: two
FLOPs per multiply-add, recomputation not counted.  ``N`` is the parameters
every token multiplies by: the layers (norm scales included), the final norm
and the output head (the tied table, used once as the head; the embedding
lookup is no multiply).  Attention scores follow PaLM (arXiv:2204.02311,
App. B): a causal token at context ``c`` costs ``4 * layers * heads *
head_dim * c`` in the forward pass (QK^T and AV), so a training token of a
length-``s`` row costs ``6 * N + 6 * L * H * Q * s``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from chipbench.reference import HIGHEST, fp8
from chipbench.weights import prng_key

# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a dense decoder, as the configuration file states them."""

    name: str
    d: int  # hidden size
    f: int  # feed-forward width
    n_heads: int
    n_kv: int
    head_dim: int
    layers: int
    vocab: int
    norm: str  # "rmsnorm" | "layernorm_nonparam"
    eps: float
    rope_theta: float
    program_arch: str

    @property
    def layer_params(self) -> int:
        """Parameters of one decoder layer (norm scales included)."""
        d, n, k, h = self.d, self.n_heads, self.n_kv, self.head_dim
        attn = d * (n + 2 * k) * h + n * h * d
        mlp = 3 * d * self.f
        norms = 2 * d if self.norm == "rmsnorm" else 0
        return attn + mlp + norms

    @property
    def non_embedding_params(self) -> int:
        """Layer parameters plus the final norm (the tied table is left out)."""
        return self.layers * self.layer_params + (self.d if self.norm == "rmsnorm" else 0)


def dims(raw: dict, name: str) -> Dims:
    """The sizes in the configuration file ``raw`` (called ``name``)."""
    if not raw.get("tie_word_embeddings", False):
        raise ValueError(f"{name}: the dense reference models tied embeddings only")
    if raw.get("hidden_act") != "silu":
        raise ValueError(f"{name}: the dense reference models SwiGLU (silu) only")
    norm = raw["norm_type"]
    eps = raw["rms_norm_eps"] if norm == "rmsnorm" else raw["layer_norm_eps"]
    return Dims(
        name=name,
        d=raw["hidden_size"],
        f=raw["intermediate_size"],
        n_heads=raw["num_attention_heads"],
        n_kv=raw["num_key_value_heads"],
        head_dim=raw.get("head_dim", raw["hidden_size"] // raw["num_attention_heads"]),
        layers=raw["num_hidden_layers"],
        vocab=raw["vocab_size"],
        norm=norm,
        eps=float(eps),
        rope_theta=float(raw["rope_theta"]),
        program_arch=raw["program_arch"],
    )


def check_program(dims: Dims, pcfg) -> None:
    """Raise unless the program's ``ModelConfig`` runs exactly these sizes."""
    want = {
        "d_model": dims.d, "d_ff": dims.f, "n_heads": dims.n_heads,
        "n_kv_heads": dims.n_kv, "head_dim": dims.head_dim,
        "n_layers": dims.layers, "vocab_size": dims.vocab,
        "norm_type": dims.norm, "rope_theta": dims.rope_theta,
        "mlp_type": "swiglu", "pos_type": "rope", "tie_embeddings": True,
        "family": "dense", "qkv_bias": False, "qk_norm": False,
        "attn_type": "full", "logit_softcap": 0.0, "scale_embeddings": False,
        "dtype": "bfloat16",
    }
    got = {k: getattr(pcfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise ValueError(f"program config {pcfg.name} differs from {dims.name}: {bad}")


def smoke(dims: Dims, pcfg) -> Dims:
    """``dims`` at the program's ``smoke()`` sizes (CPU tests only)."""
    return dataclasses.replace(
        dims, d=pcfg.d_model, f=pcfg.d_ff, n_heads=pcfg.n_heads,
        n_kv=pcfg.n_kv_heads, head_dim=pcfg.head_dim, layers=pcfg.n_layers,
        vocab=pcfg.vocab_size,
    )


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------


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


def device_tree(dims: Dims, seed: int, dtype) -> dict:
    """The program's parameter tree in ``dtype``, on the device."""
    return to_program(make(dims, seed, dtype=dtype), dims)


def host_tree(dims: Dims, seed: int, dtype) -> dict:
    """:func:`device_tree` read back into host arrays."""
    return jax.device_get(device_tree(dims, seed, dtype))


def reference_weights(dims: Dims, seed: int, dtype=jnp.float32) -> dict:
    """The weights as the program holds them in ``dtype``, in reference
    layout, as float32 on the device."""
    return {k: v.astype(jnp.float32) for k, v in make(dims, seed, dtype=dtype).items()}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


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
# model FLOPs
# ---------------------------------------------------------------------------


def matmul_params(dims: Dims) -> int:
    return dims.non_embedding_params + dims.d * dims.vocab


def _attn(dims: Dims) -> int:
    return dims.layers * dims.n_heads * dims.head_dim


def train_per_token(dims: Dims, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a causal row of ``seq_len``."""
    return 6.0 * matmul_params(dims) + 6.0 * _attn(dims) * seq_len


def decode_token(dims: Dims, context: int) -> float:
    """Forward FLOPs of one token that attends to ``context`` positions
    (itself included)."""
    return 2.0 * matmul_params(dims) + 4.0 * _attn(dims) * context


def prefill(dims: Dims, prompt_len: int) -> float:
    """Forward FLOPs of a causal prompt of ``prompt_len`` tokens."""
    p = prompt_len
    return 2.0 * matmul_params(dims) * p + 2.0 * _attn(dims) * p * (p + 1)
