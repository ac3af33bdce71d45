"""An expert layer that drops no token: route, sort the (token, expert) pairs
by expert, one grouped SwiGLU over the sorted rows, un-sort, weighted sum.

With ``E`` experts of which this device HOLDS ``held`` (all of them, or a
chip's share: ``model-configs`` guide section 4), ``K`` experts a token and
``x [T, D]``::

    p       = softmax(x W_r)                    float32, over all E
    idx, w  = the K largest p (ties to the lower index), as they are
    y[t]    = sum over k with idx[t, k] held of  w[t, k] expert_idx[t,k](x[t])
    expert_e(x) = W_d[e] (silu(W_g[e] x) * W_u[e] x)

A router may choose by ``p + b`` (a bias an expert, which balances the load
and is no part of the weight: ``route_top_k(bias=)``), and may score ``Z``
ZERO-COMPUTE experts after the ``E`` routed ones (ids ``E .. E + Z - 1``,
``routed_experts(zero_experts=Z)``): such an expert is the identity, has no
weights, is held by every chip, costs no row of the grouped product and adds
``w x`` to the token, so the experts a token computes number ``K`` less a
draw.

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


def route_top_k(x, w_router, k: int, bias=None):
    """``(weights [T, k] float32, experts [T, k] int32)``: the ``k`` largest
    of ``softmax(x W_r)``, the softmax in float32 over every expert from the
    float32-accumulated logits, the probabilities as they are (not
    renormalised over the chosen), equal ones to the lower index.  With
    ``bias [E]`` the choice is by ``p + bias`` and the weight is still
    ``p``: the bias steers the load and never scales an expert's output."""
    logits = jnp.dot(x, w_router, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if bias is None:
        weights, experts = lax.top_k(p, k)
    else:
        _, experts = lax.top_k(p + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(p, experts, axis=-1)
    return weights, experts.astype(jnp.int32)


def routed_experts(x, weights, experts, w_gate, w_up, w_down, *,
                   num_experts: int, held: Optional[Sequence[int]] = None,
                   layer=0, live=None, zero_experts: int = 0):
    """The routed part of the layer for the pairs ``weights, experts [T, K]``
    of the tokens ``x [T, D]``.

    ``w_gate, w_up [.., Eh, D, F]`` and ``w_down [.., Eh, F, D]`` are the
    stacked matrices of the ``Eh`` experts held here (``held``: their ids
    among the ``num_experts`` the router scores, in the stacks' order; None
    for all of them), one layer's or every layer's with ``layer`` choosing.
    ``live [T]`` marks the tokens that are tokens: the others (a step's
    padding) are routed nowhere.  ``zero_experts = Z``: the ids
    ``num_experts .. num_experts + Z - 1`` are zero-compute experts
    (identity): a pair that chose one adds ``w x`` (scope ``moe_zero``) and is
    no row of the grouped product, on whichever chip the token lives.
    Returns ``(y [T, D] float32, rows [Eh] int32)``: the weighted sum over a
    token's held and zero-compute experts, and how many rows each held
    expert got.

    Where the device holds a share (``held``), most pairs belong to no held
    expert.  The sort puts the held experts' pairs first, so only the first
    ``cap`` pairs are gathered, computed and summed back (``_held_rows_cap``:
    four times the share a uniform router would send here), and the step
    falls back to all ``T x K`` pairs when more than ``cap`` landed here: no
    pair is dropped either way.  At 16 of 512 + 256 held and 256 tokens the
    layer's work outside the kernel is 0.07 ms this way and 0.56 ms over
    all 3,072 pairs (PERF.md section 6, PR 37)."""
    T, K = experts.shape
    Eh = w_gate.shape[-3]
    if held is not None and len(held) != Eh:
        raise ValueError(f"{len(held)} experts are held and the stacks "
                         f"have {Eh}")
    zero = None
    if zero_experts:
        with jax.named_scope("moe_zero"):
            chose = experts >= num_experts
            if live is not None:
                chose &= live[:, None]
            share = jnp.sum(jnp.where(chose, weights.astype(jnp.float32),
                                      0.0), axis=1)
            zero = share[:, None] * x.astype(jnp.float32)
    if held is not None or zero_experts:
        local = np.full((num_experts + zero_experts,), Eh, np.int32)
        local[np.arange(num_experts) if held is None
              else np.asarray(held, np.int64)] = np.arange(Eh, dtype=np.int32)
        experts = jnp.asarray(local)[experts]
    if live is not None:
        experts = jnp.where(live[:, None], experts, Eh)
    flat = experts.reshape(-1)                               # [T x K]
    M = flat.shape[0]
    order = jnp.argsort(flat, stable=True)       # the pairs, by expert
    rows = jnp.sum(flat[:, None] == jnp.arange(Eh, dtype=jnp.int32)[None, :],
                   axis=0, dtype=jnp.int32)
    stacks = (w_gate, w_up, w_down)
    cap = None if held is None else _held_rows_cap(
        M, Eh, num_experts + zero_experts)
    if cap is None or cap >= M:
        y = _every_pair(x, weights, flat, order, rows, stacks, layer)
    else:
        y = lax.cond(
            jnp.sum(rows) <= cap,
            lambda: _first_pairs(x, weights, flat, order[:cap], rows, stacks,
                                 layer),
            lambda: _every_pair(x, weights, flat, order, rows, stacks, layer))
    return (y if zero is None else y + zero), rows


def _held_rows_cap(pairs: int, held: int, scored: int) -> int:
    """How many (token, expert) pairs of a step the short path takes: four
    times what a uniform router would send to ``held`` of ``scored`` experts,
    in whole 128-row tiles."""
    return -(-4 * pairs * held // scored // 128) * 128


def _every_pair(x, weights, flat, order, rows, stacks, layer):
    """All ``T x K`` pairs through the grouped product, un-sorted and summed
    by token: what a device that holds every expert does."""
    from ..ops.pallas_ops import grouped_experts
    T, K = weights.shape
    Eh, M = rows.shape[0], flat.shape[0]
    xs = x[order // K]
    with jax.named_scope("moe_experts"):
        ys = grouped_experts(xs, rows, *stacks, layer=layer)
    # a pair that no held expert computed is a row of no group: undefined
    ys = jnp.where((flat[order] < Eh)[:, None], ys, 0.0)
    back = jnp.zeros((M,), jnp.int32).at[order].set(
        jnp.arange(M, dtype=jnp.int32))
    # elementwise, not a matmul: float32 products whatever the platform's
    # default matmul precision
    return jnp.sum(weights.astype(jnp.float32)[:, :, None]
                   * ys[back].reshape(T, K, -1), axis=1)


def _first_pairs(x, weights, flat, first, rows, stacks, layer):
    """The pairs ``first [C]`` (the head of the sorted order, which holds
    every pair of a held expert) through the grouped product, and each row
    added to its token by one product with the ``[T, C]`` matrix that holds
    pair c's weight at its token's row (float32 at the highest precision:
    the sum the un-sort makes, without gathering ``T x K`` rows for it)."""
    from ..ops.pallas_ops import grouped_experts
    T, K = weights.shape
    Eh = rows.shape[0]
    token = first // K
    xs = x[token]
    with jax.named_scope("moe_experts"):
        ys = grouped_experts(xs, rows, *stacks, layer=layer)
    ours = flat[first] < Eh
    ys = jnp.where(ours[:, None], ys, 0.0)
    w = jnp.where(ours, weights.astype(jnp.float32).reshape(-1)[first], 0.0)
    place = jnp.where(
        token[None, :] == jnp.arange(T, dtype=token.dtype)[:, None],
        w[None, :], 0.0)
    return jnp.dot(place, ys, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
