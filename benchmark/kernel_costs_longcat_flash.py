"""Bytes and operations that the two distinctive layers of a LongCat-Flash
configuration (``"model": "longcat_flash"``) need for one engine step, from
shapes and counts: the numerators of ``moe_experts_roofline_pct.scmoe`` and
``mla_attn_roofline_pct.scmoe``. Kept with the benchmark, like
``kernel_costs_deepseek_v2.py``, so that no PR that claims a gain can change
the count. Standard library only.

Both are the algorithm's least: held experts that got a row and no others,
each read once a step however many row tiles visit it, and only the pairs
that landed on a held expert (``held_rows``: not the zero-compute experts'
nor the absent ones'); the latent's ``kv_lora_rank + qk_rope_head_dim``
elements once a sublayer and not the 128-lane tiles they are stored in; so
neither share can pass 100 whatever implements the scope.
"""
from __future__ import annotations

from benchmark.kernel_costs import ITEMSIZE


def _item(config: dict) -> int:
    return ITEMSIZE[config.get("dtype", "bfloat16")]


def moe_step(config: dict, step: dict) -> dict:
    """What the routed experts held here need for one engine step, all
    layers: ``step`` holds the step's ``experts_hit`` (held experts with at
    least one row, summed over the layers) and ``held_rows`` (the (token,
    expert) pairs that landed on a held expert, summed over the layers): the
    device counts both, arguments of the ``serve/engine_step`` span.

    bytes: the three matrices of every hit expert once (``3 x hidden x
    expert_ffn_hidden`` elements), a row of the hidden size in and one out
    for every held pair. flops: ``6 x hidden x expert_ffn_hidden`` a held
    pair (three products of 2 each)."""
    width = config["hidden_size"] * config["expert_ffn_hidden_size"]
    rows = 2 * step["held_rows"] * config["hidden_size"]
    return {"bytes": (3 * step["experts_hit"] * width + rows) * _item(config),
            "flops": 6 * step["held_rows"] * width}


def mla_step(config: dict, step: dict) -> dict:
    """What the latent attention of one engine step needs, all ``2 x
    num_layers`` sublayers: ``step`` holds ``fed_tokens`` and the model's
    ``latent_kv_tokens`` (the cached vectors ONE sublayer reads: a fed row's,
    to its length) and ``latent_qk_pairs`` (sum of ``q_len x seq_len``, one
    sublayer's).

    bytes: a cached token's ``kv_lora_rank + qk_rope_head_dim`` elements once
    a sublayer (key and value both: they are one vector), plus for every fed
    token and head the absorbed query (as wide) in and the latent output
    (``kv_lora_rank``) out. flops: a pair and head costs ``2 x (rank + rope)``
    for its score and ``2 x rank`` for its share of the output, over the
    rectangle ``q_len x seq_len`` as ``kernel_costs.rpa_step``."""
    sublayers, heads = 2 * config["num_layers"], config["num_attention_heads"]
    rank = config["kv_lora_rank"]
    key = rank + config["qk_rope_head_dim"]
    moved = key * step["latent_kv_tokens"] \
        + step["fed_tokens"] * heads * (key + rank)
    return {"bytes": sublayers * moved * _item(config),
            "flops": sublayers * step["latent_qk_pairs"] * heads
            * (2 * key + 2 * rank)}
