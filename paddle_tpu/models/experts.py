"""An expert layer that drops no token: route, sort the (token, expert) pairs
by expert, one grouped SwiGLU over the sorted rows, un-sort, weighted sum.

With ``E`` experts of which this device HOLDS ``held`` (all of them, or a
chip's share: ``model-configs`` guide section 4), ``K`` experts a token and
``x [T, D]``::

    p       = softmax(x W_r)                    float32, over all E
    idx, w  = the K largest p (ties to the lower index), as they are
    y[t]    = sum over k with idx[t, k] held of  w[t, k] expert_idx[t,k](x[t])
    expert_e(x) = W_d[e] (silu(W_g[e] x) * W_u[e] x)

The router always scores all ``E`` experts; a pair whose expert is not held
adds nothing HERE (its chip adds it; on one chip nothing stands in for the
absent ones), so the parts that the shares of a layer give add up to the
whole layer.  There is no capacity: an expert takes every row routed to it,
``ops.pallas_ops.grouped_experts`` reads its weights once however many, and
an expert with no row costs nothing.  ``models/llama.py``'s ``_moe_mlp`` (a
GShard dispatch with a capacity, ROADMAP D6) is the older layer this one is
to replace.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

__all__ = ["route_top_k", "routed_experts"]


def route_top_k(x, w_router, k: int):
    """``(weights [T, k] float32, experts [T, k] int32)``: the ``k`` largest
    of ``softmax(x W_r)``, the softmax in float32 over every expert from the
    float32-accumulated logits, the probabilities as they are (not
    renormalised over the chosen), equal ones to the lower index."""
    logits = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
    weights, experts = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    return weights, experts.astype(jnp.int32)


def routed_experts(x, weights, experts, w_gate, w_up, w_down, *,
                   num_experts: int, held: Optional[Sequence[int]] = None,
                   layer=0, live=None):
    """The routed part of the layer for the pairs ``weights, experts [T, K]``
    of the tokens ``x [T, D]``.

    ``w_gate, w_up [.., Eh, D, F]`` and ``w_down [.., Eh, F, D]`` are the
    stacked matrices of the ``Eh`` experts held here (``held``: their ids
    among the ``num_experts`` the router scores, in the stacks' order; None
    for all of them), one layer's or every layer's with ``layer`` choosing.
    ``live [T]`` marks the tokens that are tokens: the others (a step's
    padding) are routed nowhere.  Returns ``(y [T, D] float32, rows [Eh]
    int32)``: the weighted sum over a token's held experts, and how many
    rows each held expert got."""
    from ..ops.pallas_ops import grouped_experts
    T, K = experts.shape
    Eh = w_gate.shape[-3]
    if held is not None:
        if len(held) != Eh:
            raise ValueError(f"{len(held)} experts are held and the stacks "
                             f"have {Eh}")
        local = np.full((num_experts,), Eh, np.int32)
        local[np.asarray(held)] = np.arange(Eh, dtype=np.int32)
        experts = jnp.asarray(local)[experts]
    if live is not None:
        experts = jnp.where(live[:, None], experts, Eh)
    flat = experts.reshape(-1)                               # [T x K]
    M = flat.shape[0]
    order = jnp.argsort(flat, stable=True)       # the pairs, by expert
    sorted_e = flat[order]
    rows = jnp.sum(flat[:, None] == jnp.arange(Eh, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)
    xs = x[order // K]
    with jax.named_scope("moe_experts"):
        ys = grouped_experts(xs, rows, w_gate, w_up, w_down, layer=layer)
    # a pair that no held expert computed is a row of no group: undefined
    ys = jnp.where((sorted_e < Eh)[:, None], ys, 0.0)
    back = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    # elementwise, not a matmul: float32 products whatever the platform's
    # default matmul precision
    y = jnp.sum(weights.astype(jnp.float32)[:, :, None]
                * ys[back].reshape(T, K, -1), axis=1)
    return y, rows
