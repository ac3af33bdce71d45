"""Bytes and operations that ragged paged attention needs for one engine
step of a Phi-4-mini-flash configuration (``"model": "phi4flash"``), from
shapes and counts: the numerator of ``rpa_roofline_pct.hybrid``. Kept with
the benchmark, like ``kernel_costs.py``, so that no PR that claims a gain can
change the count. Standard library only.

The layers that attend (``models/phi4flash.py``): ``L/4`` window layers, each
on its own K/V of the last ``sliding_window`` tokens, and ``L/4`` readers of
ONE pool that holds every token (the full layer ``L/2 + 1`` and the ``L/4 -
1`` cross layers after it).
"""
from __future__ import annotations

from benchmark.kernel_costs import ITEMSIZE, kv_itemsize


def rpa_step(config: dict, traffic: dict, step: dict) -> dict:
    """What the attention of one engine step needs, all layers: ``step``
    holds the step's ``fed_tokens`` (sum of ``q_len``), ``kv_tokens`` (sum of
    ``seq_len``), ``qk_pairs`` (sum of ``q_len x seq_len``) and the model's own
    ``window_kv_tokens`` (over the fed rows ``min(seq_len, W + q_len - 1)``)
    and ``window_qk_pairs`` (over the fed tokens ``min(p + 1, W)``),
    arguments of its ``serve/engine_step`` span.

    bytes: K and V of the LIVE tokens, once a layer that reads them: a
    window layer the tokens inside some query's window, a reader of the
    shared pool every token of the row (``2 x kv heads x d`` elements a
    token each); plus q read and o written for the fed tokens in every
    attending layer (``heads x d`` elements each, in the model's dtype:
    the algorithm's rows, not the kernel's zero-padded ones). flops: a
    query head and key pair costs ``2 d`` for its score and ``2 x 2d`` for
    its row of the pair-head's V, twice as wide as the head."""
    quarter = config["num_hidden_layers"] // 4      # window layers; readers
    heads, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    token = 2 * nkv * d * kv_itemsize(config, traffic)
    kv = quarter * token * (step["window_kv_tokens"] + step["kv_tokens"])
    qo = 2 * quarter * 2 * step["fed_tokens"] * heads * d \
        * ITEMSIZE[config.get("dtype", "bfloat16")]
    pairs = quarter * (step["window_qk_pairs"] + step["qk_pairs"])
    return {"bytes": kv + qo, "flops": pairs * heads * (2 * d + 2 * 2 * d)}
