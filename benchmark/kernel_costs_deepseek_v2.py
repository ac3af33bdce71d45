"""Bytes and operations that the two distinctive layers of a DeepSeek-V2
configuration (``"model": "deepseek_v2"``) need for one engine step, from
shapes and counts: the numerators of ``moe_experts_roofline_pct`` and
``mla_attn_roofline_pct``. Kept with the benchmark, like ``kernel_costs.py``,
so that no PR that claims a gain can change the count. Standard library only.

Both are the algorithm's least: experts that got a row and no others, the
latent's ``kv_lora_rank + qk_rope_head_dim`` elements and not the 128-lane
tiles they are stored in; so neither share can pass 100.
"""
from __future__ import annotations

from benchmark.kernel_costs import ITEMSIZE


def _item(config: dict) -> int:
    return ITEMSIZE[config.get("dtype", "bfloat16")]


def moe_step(config: dict, step: dict) -> dict:
    """What the routed experts of one engine step need, all expert layers:
    ``step`` holds the step's ``experts_hit`` (experts with at least one
    row, summed over the expert layers: the device counts it) and
    ``moe_pairs`` (``fed_tokens x num_experts_per_tok x`` expert layers),
    arguments of its ``serve/engine_step`` span.

    bytes: the three matrices of every hit expert once (``3 x hidden x
    moe_intermediate`` elements), a row of the hidden size in and one out
    for every pair. flops: ``6 x hidden x moe_intermediate`` a pair (three
    products of 2 each)."""
    width = config["hidden_size"] * config["moe_intermediate_size"]
    rows = 2 * step["moe_pairs"] * config["hidden_size"]
    return {"bytes": (3 * step["experts_hit"] * width + rows) * _item(config),
            "flops": 6 * step["moe_pairs"] * width}


def mla_step(config: dict, step: dict) -> dict:
    """What the latent attention of one engine step needs, all layers:
    ``step`` holds ``fed_tokens`` and the model's ``latent_kv_tokens`` (the
    cached vectors a layer reads: a fed row's, to its length) and
    ``latent_qk_pairs`` (sum of ``q_len x seq_len``).

    bytes: a cached token's ``kv_lora_rank + qk_rope_head_dim`` elements
    once a layer (key and value both: they are one vector), plus for every
    fed token and head the absorbed query (as wide) in and the latent output
    (``kv_lora_rank``) out. flops: a pair and head costs ``2 x (rank +
    rope)`` for its score and ``2 x rank`` for its share of the output,
    over the rectangle ``q_len x seq_len`` as ``kernel_costs.rpa_step``."""
    layers, heads = config["num_hidden_layers"], config["num_attention_heads"]
    rank = config["kv_lora_rank"]
    key = rank + config["qk_rope_head_dim"]
    moved = key * step["latent_kv_tokens"] \
        + step["fed_tokens"] * heads * (key + rank)
    return {"bytes": layers * moved * _item(config),
            "flops": layers * step["latent_qk_pairs"] * heads
            * (2 * key + 2 * rank)}
