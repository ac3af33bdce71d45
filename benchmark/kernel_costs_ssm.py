"""Bytes and operations that the selective scan of a state-space (Mamba)
layer needs for one engine step, from shapes and counts: the numerator of
``ssm_scan_roofline_pct``. Kept with the benchmark, like ``kernel_costs.py``,
so that no PR that claims a gain can change the count. Standard library only.
"""
from __future__ import annotations

from benchmark.kernel_costs import ITEMSIZE

STATE_ITEMSIZE = 4      # the recurrent state S is float32 (config: assumed)
OPS_PER_UPDATE = 6      # dt*A, exp, *S, dx*B, +, and the multiply-add of S*C


def mamba_layers(config: dict) -> int:
    """Layers that are Mamba mixers: all but those where ``i %
    attn_layer_period == attn_layer_offset``."""
    return sum(i % config["attn_layer_period"] != config["attn_layer_offset"]
               for i in range(config["num_hidden_layers"]))


def scan_step(config: dict, step: dict) -> dict:
    """What the selective scan needs for one engine step, all Mamba layers:
    ``step`` holds the step's ``state_rows`` (rows with ``q_len > 0``) and
    ``fed_tokens`` (sum of ``q_len``), arguments of its ``serve/engine_step``
    span.

    bytes, a layer: the state ``S [E, N]`` float32 of every row that is fed,
    read once and written once whatever the chunk's length (``state_rows x 2
    x E x N x 4``), plus for every fed token its inputs dt and x and its
    output y (``3 E`` elements) and B and C (``2 N``), in the model's dtype.
    The state of idle rows, the convolution's carried inputs (they move
    under the scope ``ssm_conv``, not ``ssm_scan``) and any intermediate a
    program keeps in HBM are not needed and not counted. flops, a layer:
    ``fed_tokens x E x N x 6``."""
    E = config["mamba_expand"] * config["hidden_size"]
    N = config["mamba_d_state"]
    act = ITEMSIZE[config.get("dtype", "bfloat16")]
    state = step["state_rows"] * 2 * E * N * STATE_ITEMSIZE
    io = step["fed_tokens"] * (3 * E + 2 * N) * act
    layers = mamba_layers(config)
    return {"bytes": layers * (state + io),
            "flops": layers * step["fed_tokens"] * E * N * OPS_PER_UPDATE}
