"""The model dimensions the yardstick needs, read from a configuration file
of ``bench/configs`` by its published (Hugging Face ``config.json``) keys.

Nothing here reads the program's own ``ModelConfig``: the reference, the
weight generator and the cost arithmetic all start from the file, and the
harness checks that the program was built to the same numbers.
"""

from __future__ import annotations

import dataclasses
import json
import os


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool          # SwiGLU (gate, up, down) or a plain GELU MLP
    qkv_bias: bool
    tied: bool           # LM head is the embedding table
    rope_theta: float
    norm_eps: float
    window: int          # sliding window in tokens, 0 = full attention
    dtype: str           # weights, KV and activations as served
    itemsize: int        # bytes per element of that type


_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def dims(cfg: dict) -> Dims:
    """:class:`Dims` of one configuration file's contents."""
    act = cfg["hidden_act"]
    if act not in ("silu", "gelu_pytorch_tanh"):
        raise ValueError(f"no reference for hidden_act {act!r}")
    window = 0
    if cfg.get("use_sliding_window", True) and cfg.get("sliding_window"):
        window = int(cfg["sliding_window"])
    n_heads = int(cfg["num_attention_heads"])
    return Dims(
        n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]),
        n_heads=n_heads,
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim")
                     or cfg["hidden_size"] // n_heads),
        d_ff=int(cfg["intermediate_size"]),
        vocab=int(cfg["vocab_size"]),
        gated=act == "silu",
        qkv_bias=bool(cfg.get("attention_bias", cfg.get("use_bias", False))),
        tied=bool(cfg["tie_word_embeddings"]),
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg.get("rms_norm_eps", cfg.get("norm_epsilon"))),
        window=window,
        dtype=cfg["torch_dtype"],
        itemsize=_ITEMSIZE[cfg["torch_dtype"]],
    )


def load(root: str, path: str) -> dict:
    with open(os.path.join(root, path)) as f:
        return json.load(f)
