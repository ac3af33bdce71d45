"""Dense attention on the engine's page pools, for every model whose
attention keeps K and V heads (the latent counterpart is ``mla.absorbed``):
the step's K/V written into a layer's pages (``paged_kv_write``, under the
scope ``kv_write``) and its queries walked over them
(``ragged_paged_attention``).  What this module hides is the kernels'
format: the stacked pools ``[L, nkv, P, page, d]`` (window rings too) and the
query rows ``[R, nkv, Tc x rep, d]``, row ``t x rep + j`` query head ``g x
rep + j`` of token t (query head h reads K/V head ``h // rep``).
"""
from __future__ import annotations

import contextlib

import jax

__all__ = ["write", "queries", "walk", "outputs", "attend"]


def write(pools, k, v, table, seq_lens, q_lens, layer):
    """``pools`` with ``k, v [R, Tc, nkv, d]`` written into ``layer``."""
    from ..ops.pallas_ops import paged_kv_write
    with jax.named_scope("kv_write"):
        return paged_kv_write(*pools, k, v, table, seq_lens, q_lens,
                              layer=layer)


def queries(q, kv_heads: int):
    """``q [R, Tc, ..., d]`` (a token's query heads in order, over any axes)
    as the kernel's rows ``[R, nkv, Tc x rep, d]``."""
    R, Tc, d = q.shape[0], q.shape[1], q.shape[-1]
    q = q.reshape(R, Tc, kv_heads, -1, d)
    return q.transpose(0, 2, 1, 3, 4).reshape(R, kv_heads, -1, d)


def walk(qk, pools, table, seq_lens, q_lens, *, rep: int, layer,
         window=None):
    """The rows ``qk`` attended over ``layer`` of ``pools`` (then scales)."""
    from ..ops.pallas_ops import ragged_paged_attention
    return ragged_paged_attention(
        qk, pools[0], pools[1], table, seq_lens, q_lens, rep=rep,
        layer=layer, window=window,
        **dict(zip(("k_scales", "v_scales"), pools[2:])))


def outputs(out, Tc: int, heads=None):
    """The kernel's rows ``out [R, nkv, Tc x rep, d]`` back as ``[R, Tc, nkv
    x rep x d]``, ``rep`` read as the axes ``heads`` (default ``(rep,)``)."""
    R, kv_heads, _, d = out.shape
    heads = tuple(heads or (out.shape[2] // Tc,))
    out = out.reshape((R, kv_heads, Tc) + heads + (d,))
    return out.transpose((0, 2, 1) + tuple(range(3, out.ndim))).reshape(
        R, Tc, -1)


def attend(q, pools, table, seq_lens, q_lens, *, layer, window=None,
           scope=None):
    """``q [R, Tc, nh, d]`` attended over layer ``layer`` of ``pools``
    (``walk``): ``[R, Tc, nh x d]``.  ``scope`` names the kernel's call
    alone in a profile, where a reader times it."""
    Tc = q.shape[1]
    qk = queries(q, pools[0].shape[1])
    with jax.named_scope(scope) if scope else contextlib.nullcontext():
        out = walk(qk, pools, table, seq_lens, q_lens,
                   rep=qk.shape[2] // Tc, layer=layer, window=window)
    return outputs(out, Tc)

