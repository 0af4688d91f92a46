"""A configuration file read into the sizes the benchmark and its reference use.

The file under ``chipbench/configs/`` is the source of truth for what a cell
runs.  The program under test has its own registry of configurations; the
harness checks that the program's entry agrees with the file on every size
before it builds anything (:func:`check_program_config`).
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent


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


def load_config(name: str, configs_dir: Path = ROOT / "configs") -> tuple[Dims, dict]:
    """``(dims, raw file)`` of the configuration called ``name``."""
    path = Path(configs_dir) / f"{name}.json"
    raw = json.loads(path.read_text())
    if not raw.get("tie_word_embeddings", False):
        raise ValueError(f"{path}: the reference models tied embeddings only")
    if raw.get("hidden_act") != "silu":
        raise ValueError(f"{path}: the reference models SwiGLU (silu) only")
    norm = raw["norm_type"]
    eps = raw["rms_norm_eps"] if norm == "rmsnorm" else raw["layer_norm_eps"]
    dims = Dims(
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
    return dims, raw


def check_program_config(dims: Dims, pcfg) -> None:
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


def smoke_dims(dims: Dims, pcfg) -> Dims:
    """``dims`` at the program's ``smoke()`` sizes (CPU tests only)."""
    return dataclasses.replace(
        dims, d=pcfg.d_model, f=pcfg.d_ff, n_heads=pcfg.n_heads,
        n_kv=pcfg.n_kv_heads, head_dim=pcfg.head_dim, layers=pcfg.n_layers,
        vocab=pcfg.vocab_size,
    )
