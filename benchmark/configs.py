"""A configuration file -> the program's `ModelConfig`, registered at run
time through the public constructor and `register_model`; nothing in
`models/config.py` is edited for a configuration the benchmark adds.

The file holds the published `config.json` keys under their own names, with
the cut applied (`reduced` names each changed key), what the builder had to
set itself under `assumed`, and how it is served (`chips`, `serve_args`).
This module is the one mapping from those keys to `ModelConfig` fields, for
the dense decoder family the program serves (RMSNorm, RoPE, grouped
attention, gated MLP)."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        raw = json.load(f)
    raw["name"] = name
    return raw


def model_kwargs(raw: dict) -> dict:
    """`ModelConfig(**model_kwargs(raw))`: one field per published key."""
    heads = int(raw["num_attention_heads"])
    window = raw.get("sliding_window")
    if raw.get("use_sliding_window") is False:
        window = None
    return dict(
        name=raw["name"],
        vocab_size=int(raw["vocab_size"]),
        dim=int(raw["hidden_size"]),
        n_layers=int(raw["num_hidden_layers"]),
        n_heads=heads,
        n_kv_heads=int(raw["num_key_value_heads"]),
        ffn_dim=int(raw["intermediate_size"]),
        head_dim=int(raw.get("head_dim") or raw["hidden_size"] // heads),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        activation=raw["hidden_act"],
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        sliding_window=None if window is None else int(window),
        attn_bias=bool(raw.get("attention_bias", False)),
        context_window=int(raw["max_position_embeddings"]),
        output_limit=int(raw.get("serving", {}).get("output_limit", 4096)),
        eos_token_id=int(raw["eos_token_id"]),
        bos_token_id=int(raw["bos_token_id"]),
    )


def register(raw: dict):
    """Register the configuration with the program; returns its spec."""
    from quoracle_tpu.models.config import ModelConfig, register_model
    register_model(ModelConfig(**model_kwargs(raw)))
    return f"xla:{raw['name']}"


DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def kv_bytes_per_token(raw: dict) -> int:
    """Bytes of K and V that one resident token holds over all layers, at
    the type the configuration states (`torch_dtype`)."""
    k = model_kwargs(raw)
    return (2 * k["n_layers"] * k["n_kv_heads"] * k["head_dim"]
            * DTYPE_BYTES[raw["torch_dtype"]])


def weight_bytes_per_step(raw: dict, bytes_per_weight: int = 2) -> int:
    """Bytes of weights one decode step has to read, from the shapes alone:
    every layer's projections and MLP, the norms, and the output head (the
    embedding matrix where it is tied). The embedding lookup reads rows,
    not the table, and is left out: a lower bound."""
    k = model_kwargs(raw)
    d, f, hd = k["dim"], k["ffn_dim"], k["head_dim"]
    q, kv = k["n_heads"] * hd, k["n_kv_heads"] * hd
    layer = d * q + 2 * d * kv + q * d + 3 * d * f + 2 * d
    if k["attn_bias"]:
        layer += q + 2 * kv
    total = k["n_layers"] * layer + d + k["vocab_size"] * d
    return total * bytes_per_weight
