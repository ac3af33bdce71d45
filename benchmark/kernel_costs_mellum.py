"""Bytes and operations that the attention kernel and the experts of a
Mellum 2 configuration (``"model": "mellum"``) need for one engine step,
from shapes and counts: the numerators of ``rpa_roofline_pct.mellum`` and
``moe_experts_roofline_pct.mellum``. Kept with the benchmark, like
``kernel_costs_cohere2_moe.py``, so that no PR that claims a gain can change
the count. Standard library only; every width and the layers of each kind
are read from the configuration file (``layer_types`` as given).

Both are the algorithm's least: a window layer's K/V of the keys inside some
query's window and a full layer's of the row's every token, each read once
a layer, and the fed tokens' own query and output rows (not the zero-padded
rows the kernel is given); experts that got a row and no others, each read
once a step however many row tiles visit it, and the pairs routed; so
neither share can pass 100 whatever implements the scope.
"""
from __future__ import annotations

from benchmark.kernel_costs import ITEMSIZE


def _item(config: dict) -> int:
    return ITEMSIZE[config.get("dtype", "bfloat16")]


def _layers(config: dict) -> tuple:
    """``(window layers, full layers)`` among the layers kept."""
    kept = config["layer_types"][:config["num_hidden_layers"]]
    full = sum(t == "full_attention" for t in kept)
    return len(kept) - full, full


def attn_step(config: dict, step: dict) -> dict:
    """What the attention kernel of one engine step needs, all layers:
    ``step`` holds the step's ``fed_tokens``, ``kv_tokens`` and ``qk_pairs``
    (ONE full layer's: every token of a fed row, the rectangle ``q_len x
    seq_len``) and the model's ``window_kv_tokens`` and ``window_qk_pairs``
    (ONE window layer's), arguments of its ``serve/engine_step`` span.

    bytes: K and V of those tokens once a layer (``2 x kv heads x head_dim``
    elements a token), plus the query read and the output written for every
    fed token, head and layer (``head_dim`` elements each way). flops: a
    query head and key pair costs ``2 x head_dim`` for its score and as much
    for its share of the output."""
    windowed, full = _layers(config)
    heads, d = config["num_attention_heads"], config["head_dim"]
    token = 2 * config["num_key_value_heads"] * d
    kv = token * (windowed * step["window_kv_tokens"]
                  + full * step["kv_tokens"])
    qo = (windowed + full) * step["fed_tokens"] * heads * 2 * d
    pairs = windowed * step["window_qk_pairs"] + full * step["qk_pairs"]
    return {"bytes": (kv + qo) * _item(config),
            "flops": pairs * heads * 4 * d}


def moe_step(config: dict, step: dict) -> dict:
    """What the experts need for one engine step, all layers: ``step``
    holds the step's ``experts_hit`` (experts with at least one row, summed
    over the layers) and ``held_rows`` (the (token, expert) pairs routed,
    summed over the layers): the device counts both, arguments of the
    ``serve/engine_step`` span.

    bytes: the three matrices of every hit expert once (``3 x hidden x
    moe_intermediate`` elements), a row of the hidden size in and one out
    for every pair. flops: ``6 x hidden x moe_intermediate`` a pair (three
    products of 2 each)."""
    width = config["hidden_size"] * config["moe_intermediate_size"]
    rows = 2 * step["held_rows"] * config["hidden_size"]
    return {"bytes": (3 * step["experts_hit"] * width + rows) * _item(config),
            "flops": 6 * step["held_rows"] * width}
