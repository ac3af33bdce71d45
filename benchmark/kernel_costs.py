"""Bytes and operations that a kernel's algorithm needs, from shapes and
counts: the numerator of a kernel's share of its roofline. Kept with the
benchmark, like ``flops.py``, so that no PR that claims a gain can change
the count. Standard library only.
"""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}
# function names of the ragged-paged-attention kernel (int8 pages: _quant)
RPA_KERNELS = ("_rpa_kernel", "_rpa_kernel_quant")


def kv_itemsize(config: dict, traffic: dict) -> int:
    """Bytes of one element of the engine's page pools: its ``kv_dtype``
    argument where the traffic file gives one, else the model's dtype."""
    dtype = traffic.get("engine", {}).get("kv_dtype") \
        or config.get("dtype", "bfloat16")
    if dtype not in ITEMSIZE:
        raise KeyError(f"no item size for the dtype {dtype!r} (known: "
                       f"{sorted(ITEMSIZE)})")
    return ITEMSIZE[dtype]


def pool_shapes(config: dict, traffic: dict) -> list:
    """The shapes the engine's K or V page pool takes in its step: stacked
    over the layers ``[L, nkv, P, page, d]``, one layer's as the scan over
    the layers slices it out of the stack ``[1, nkv, P, page, d]`` and as
    the layer uses it ``[nkv, P, page, d]``, and that pool flat over its
    tokens ``[nkv, P * page, d]`` (how ``forward_paged`` scatters new
    tokens into it). From the configuration and the traffic file's
    ``engine`` arguments; ``page_size`` defaults to the engine's 128."""
    eng = traffic["engine"]
    nkv = config["num_key_value_heads"]
    d = config["hidden_size"] // config["num_attention_heads"]
    pages, page = eng["num_pages"], eng.get("page_size", 128)
    return [(config["num_hidden_layers"], nkv, pages, page, d),
            (1, nkv, pages, page, d), (nkv, pages, page, d),
            (nkv, pages * page, d)]


def rpa_step(config: dict, traffic: dict, step: dict) -> dict:
    """What ragged paged attention needs for one engine step, all layers:
    ``step`` holds the step's ``fed_tokens`` (sum of ``q_len``),
    ``kv_tokens`` (sum of ``seq_len``) and ``qk_pairs`` (sum of ``q_len x
    seq_len``), the arguments of its ``serve/engine_step`` span.

    bytes: K and V of the LIVE tokens (``kv_tokens x nkv x d`` each, not
    whole pages and not the pages of idle slots), plus q read and o written
    for the fed tokens (``fed_tokens x heads x d`` each, in the model's
    dtype). flops: ``4 x qk_pairs x heads x d`` (QK^T and PV, 2 each per
    pair, head and element), over the rectangle ``q_len x seq_len``: the
    causal mask inside a chunk spares at most ``(q_len - 1) / (2 x
    seq_len)`` of it."""
    layers = config["num_hidden_layers"]
    heads, nkv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["hidden_size"] // heads
    kv = 2 * step["kv_tokens"] * nkv * d * kv_itemsize(config, traffic)
    qo = 2 * step["fed_tokens"] * heads * d \
        * ITEMSIZE[config.get("dtype", "bfloat16")]
    return {"bytes": layers * (kv + qo),
            "flops": layers * 4 * step["qk_pairs"] * heads * d}


def least_time_s(cost: dict, peaks: dict) -> dict:
    """The least time the chip could take for ``cost`` and the bound that
    sets it: the larger of bytes over the HBM peak and operations over the
    bf16 peak."""
    by = {"memory": cost["bytes"] / peaks["hbm_bytes_per_s"],
          "compute": cost["flops"] / peaks["bf16_flops_per_s"]}
    bound = max(by, key=by.get)
    return {"seconds": by[bound], "bound": bound, **by}
