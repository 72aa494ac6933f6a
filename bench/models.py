"""Served-model sizes read from a configuration file.

A configuration file (``bench/configs/<name>.json``) holds the accurate
model at its top level, under the key names of its published config, and
the fast model as the nested group ``fast_model``.  ``Dims`` is the one
normalized form the weights, the reference and the FLOP counts read.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path


@dataclasses.dataclass(frozen=True)
class Dims:
    """Shapes of one served LM (``kind`` is ``transformer`` or ``ssd``)."""

    kind: str
    name: str
    layers: int
    d: int
    vocab: int
    # transformer
    heads: int = 0
    kv_heads: int = 0
    head_dim: int = 0
    ff: int = 0
    act: str = "silu"  # silu: gated SwiGLU MLP; gelu: plain GELU MLP
    rope_theta: float = 10_000.0
    # ssd (Mamba-2)
    d_state: int = 0
    headdim: int = 0
    expand: int = 0
    ngroups: int = 1
    d_conv: int = 0
    chunk: int = 0
    eps: float = 1e-6

    @property
    def gated(self) -> bool:
        return self.act == "silu"

    @property
    def d_inner(self) -> int:
        return self.expand * self.d

    @property
    def ssd_heads(self) -> int:
        return self.d_inner // self.headdim

    @property
    def d_xbc(self) -> int:
        return self.d_inner + 2 * self.ngroups * self.d_state


def _transformer(c: dict) -> Dims:
    return Dims(
        kind="transformer", name=c["model_name"], layers=c["num_hidden_layers"],
        d=c["hidden_size"], vocab=c["vocab_size"], heads=c["num_attention_heads"],
        kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        ff=c["intermediate_size"], act=c["hidden_act"], rope_theta=float(c["rope_theta"]),
        eps=float(c["rms_norm_eps"]),
    )


def _ssd(c: dict) -> Dims:
    return Dims(
        kind="ssd", name=c["model_name"], layers=c["n_layer"], d=c["d_model"],
        vocab=c["vocab_size"], d_state=c["d_state"], headdim=c["headdim"],
        expand=c["expand"], ngroups=c["ngroups"], d_conv=c["d_conv"],
        chunk=c["chunk_size"], eps=float(c["rms_norm_eps"]),
    )


def dims_of(group: dict) -> Dims:
    """``Dims`` of one model group of a configuration file."""
    return _ssd(group) if group["model_type"] == "mamba2" else _transformer(group)


def load_config(path: Path) -> dict:
    """The configuration file with ``roles``: {"fast": Dims, "accurate": Dims}."""
    cfg = json.loads(Path(path).read_text())
    cfg["roles"] = {"fast": dims_of(cfg["fast_model"]), "accurate": dims_of(cfg)}
    return cfg


def to_model_config(dims: Dims):
    """The program's ``ModelConfig`` for these sizes (bf16, as served)."""
    from repro.configs.base import ModelConfig

    if dims.kind == "ssd":
        return ModelConfig(
            name=dims.name, family="ssm", num_layers=dims.layers, d_model=dims.d,
            vocab_size=dims.vocab, pattern=("ssd:none",), ssd_state=dims.d_state,
            ssd_headdim=dims.headdim, ssd_expand=dims.expand, ssd_ngroups=dims.ngroups,
            ssd_chunk=dims.chunk, conv_width=dims.d_conv, tie_embeddings=True,
        )
    return ModelConfig(
        name=dims.name, family="dense", num_layers=dims.layers, d_model=dims.d,
        vocab_size=dims.vocab, num_heads=dims.heads, num_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim, d_ff=dims.ff,
        activation="swiglu" if dims.gated else "gelu", pattern=("attn:mlp",),
        rope_theta=dims.rope_theta, tie_embeddings=True,
    )


def param_layout(dims: Dims) -> dict:
    """Nested {name: (shape, law, std)} in the program's parameter layout.

    Laws: ``normal`` (std given), ``scale`` (a norm's (1 + scale) term, drawn
    small around 0), ``A_log``, ``dt_bias`` (Mamba-2's initialisation
    ranges), ``near_one`` (the skip weight D).
    """
    L, d = dims.layers, dims.d
    if dims.kind == "ssd":
        din, h, g, n, dx = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state, dims.d_xbc
        block = {
            "pre_norm": {"scale": ((L, d), "scale", 0.1)},
            "ssd": {
                "in_proj": ((L, d, 2 * din + 2 * g * n + h), "normal", d ** -0.5),
                "conv_w": ((L, dims.d_conv, dx), "normal", dims.d_conv ** -0.5),
                "conv_b": ((L, dx), "normal", 0.1),
                "A_log": ((L, h), "A_log", 0.0),
                "D": ((L, h), "near_one", 0.1),
                "dt_bias": ((L, h), "dt_bias", 0.0),
                "norm_scale": ((L, din), "scale", 0.1),
                "out_proj": ((L, din, d), "normal", din ** -0.5),
            },
        }
    else:
        H, Hkv, Dh, F = dims.heads, dims.kv_heads, dims.head_dim, dims.ff
        mlp = {
            "w_up": ((L, d, F), "normal", d ** -0.5),
            "w_down": ((L, F, d), "normal", F ** -0.5),
        }
        if dims.gated:
            mlp["w_gate"] = ((L, d, F), "normal", d ** -0.5)
        block = {
            "pre_norm": {"scale": ((L, d), "scale", 0.1)},
            "attn": {
                "wq": ((L, d, H, Dh), "normal", d ** -0.5),
                "wk": ((L, d, Hkv, Dh), "normal", d ** -0.5),
                "wv": ((L, d, Hkv, Dh), "normal", d ** -0.5),
                "wo": ((L, H, Dh, d), "normal", (H * Dh) ** -0.5),
            },
            "mlp_norm": {"scale": ((L, d), "scale", 0.1)},
            "mlp": mlp,
        }
    return {
        "embed": {"embedding": ((dims.vocab, d), "normal", 0.02)},
        "blocks": [block],
        "tail": [],
        "final_norm": {"scale": ((d,), "scale", 0.1)},
    }


def param_count(dims: Dims) -> int:
    """Parameters of the served model, from its shapes."""
    L, d, V = dims.layers, dims.d, dims.vocab
    if dims.kind == "ssd":
        din, h, g, n, dx = dims.d_inner, dims.ssd_heads, dims.ngroups, dims.d_state, dims.d_xbc
        layer = d + d * (2 * din + 2 * g * n + h) + dims.d_conv * dx + dx + 3 * h + din + din * d
    else:
        H, Hkv, Dh, F = dims.heads, dims.kv_heads, dims.head_dim, dims.ff
        layer = 2 * d + d * H * Dh + 2 * d * Hkv * Dh + H * Dh * d + (3 if dims.gated else 2) * d * F
    return V * d + d + L * layer
