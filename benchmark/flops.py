"""Operations the algorithm needs, from a configuration's shapes.

Kept with the benchmark so that no PR that claims a gain can change the
count. Recomputed operations (remat) do not count: the figure is what the
forward and backward passes require, not what the program chooses to run.
"""
from __future__ import annotations

import json
import os


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication for every token:
    the decoder layers' projections and ``lm_head``. The embedding table is
    a gather, not a matmul, and the norms' gains are elementwise."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    kv = (cfg["num_key_value_heads"]
          * (h // cfg["num_attention_heads"]))
    per_layer = h * h + 2 * h * kv + h * h + 3 * h * i   # wq, wk+wv, wo, mlp
    return cfg["num_hidden_layers"] * per_layer + h * cfg["vocab_size"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    """Forward + backward floating-point operations per trained token at
    sequence length ``seq``: 6 per matmul parameter (2 forward, 4 backward),
    plus causal attention's two ``S x S x d`` products per head — forward
    ``2 * 2 * S * H`` per token and layer over the full square, times 3 for
    forward + backward, halved by the causal mask: ``6 * S * H * L``."""
    return (6.0 * matmul_params(cfg)
            + 6.0 * seq * cfg["hidden_size"] * cfg["num_hidden_layers"])


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error,
    never a default."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmark/peaks.json (it has {sorted(table)})")
    return table[device_kind]
