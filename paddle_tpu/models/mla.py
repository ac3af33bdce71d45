"""Multi-head latent attention (MLA), the part two models share
(``models/deepseek_v2.py``, ``models/longcat_flash.py``): the projections, the
materialised form for whole sequences and the absorbed form on the engine's
paged latents.

With ``nh`` heads, latent rank r, head sizes ``dn`` (no position), ``dr``
(rotary), ``dv`` (value) and x the normed input of a token at position p::

    q                = x W_q                                  without a query latent
    q                = RMSNorm_q(x W_qa) W_qb  * s_q          with one (``q_lora_rank``)
    [c_raw r | k_pe dr] = x W_kva
    c                = RMSNorm_kv(c_raw)  * s_kv
    q_pe, k_pe       rotated by p (one k_pe for all heads)
    [k_nope_i dn | v_i dv] = c W_kvb                          per head i
    s_i              = (q_nope_i . k_nope_i + q_pe_i . k_pe) scale   causal
    o_i              = softmax_float32(s_i) v_i               (W_o is the caller's)

``s_q = sqrt(hidden / q_lora_rank)`` where the configuration says
``mla_scale_q_lora`` and ``s_kv = sqrt(hidden / kv_lora_rank)`` where it says
``mla_scale_kv_lora``; both 1 otherwise (DeepSeek-V2 has neither key).  A
configuration is any object with the fields named here (``num_attention_heads
kv_lora_rank q_lora_rank qk_nope_head_dim qk_rope_head_dim v_head_dim
hidden_size rms_norm_eps`` and the properties ``latent_lanes
softmax_scale``); a layer's leaves are ``wkva kv_norm wkvb`` and ``wq``, or
``wqa q_norm wqb`` under a query latent.

**Absorbed** (``absorbed``): with ``W_kvb = [W_UK_i | W_UV_i]``, ``q_abs_i =
s_kv q_nope_i W_UK_i^T`` (r wide), ``s_i = (q_abs_i . cn + q_pe_i . k_pe)
scale``, ``o_lat_i = sum p cn``, ``o_i = s_kv o_lat_i W_UV_i`` where ``cn =
RMSNorm_kv(c_raw)`` is what the cache holds, WITHOUT ``s_kv``: the factor
rides in float32 on ``q_abs`` and on the output, so that the cached vector
``[cn | rotated k_pe | 0]`` and the weights are what they are without it and
nothing is rounded twice.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["rms_norm", "swiglu", "rotate", "latent_lanes", "q_scale",
           "kv_scale", "project", "up_projections", "materialised",
           "absorbed"]

_LANES = 128


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    return (xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def rotate(x, sin, cos):
    """``x [T, heads, dr]`` turned by its token's angle (``sin, cos [T, dr /
    2]`` float32): pair j is lanes ``(j, j + dr/2)``."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    sin, cos = sin[:, None, :], cos[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def latent_lanes(cfg) -> int:
    """Lanes of a cached token's vector: ``r + dr`` in whole tiles."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // _LANES) * _LANES


def q_scale(cfg) -> float:
    if cfg.q_lora_rank and getattr(cfg, "mla_scale_q_lora", False):
        return math.sqrt(cfg.hidden_size / cfg.q_lora_rank)
    return 1.0


def kv_scale(cfg) -> float:
    if getattr(cfg, "mla_scale_kv_lora", False):
        return math.sqrt(cfg.hidden_size / cfg.kv_lora_rank)
    return 1.0


def _scaled(x, factor, dtype):
    """``x * factor`` in float32, as ``dtype``; ``x`` as it is for 1."""
    if factor == 1.0:
        return x.astype(dtype)
    return (x.astype(jnp.float32) * factor).astype(dtype)


def _einsum_scaled(spec, a, b, factor):
    """``einsum(spec, a, b) * factor``, the factor applied to the float32
    accumulation before the one rounding to ``a``'s dtype; the plain product
    for a factor of 1 (DeepSeek-V2's program is the one it was)."""
    if factor == 1.0:
        return jnp.einsum(spec, a, b)
    return _scaled(jnp.einsum(spec, a, b,
                              preferred_element_type=jnp.float32),
                   factor, a.dtype)


def project(cfg, lp, xn, sin, cos):
    """The projections of one layer for the normed tokens ``xn [T, D]``:
    ``q_nope [T, nh, dn]`` and rotated ``q_pe [T, nh, dr]`` (with ``s_q``),
    the normed latent ``cn [T, r]`` WITHOUT ``s_kv`` (what the cache holds)
    and the rotated ``k_pe [T, dr]``."""
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    T = xn.shape[0]
    if cfg.q_lora_rank:
        qn = rms_norm(xn @ lp["wqa"], lp["q_norm"], cfg.rms_norm_eps)
        q = _einsum_scaled("tc,cd->td", qn, lp["wqb"], q_scale(cfg))
    else:
        q = xn @ lp["wq"]
    q = q.reshape(T, nh, dn + dr)
    kv = xn @ lp["wkva"]
    cn = rms_norm(kv[:, :r], lp["kv_norm"], cfg.rms_norm_eps)
    return (q[..., :dn], rotate(q[..., dn:], sin, cos), cn,
            rotate(kv[:, None, r:], sin, cos)[:, 0])


def up_projections(cfg, lp):
    """``W_UK [r, nh, dn]`` and ``W_UV [r, nh, dv]`` out of ``W_kvb``."""
    w = lp["wkvb"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def materialised(cfg, lp, xn, sin, cos, B, S):
    """The attention of ``B`` whole sequences of ``S`` tokens (``xn [B S,
    D]``, sequence after sequence), every head's K and V expanded from the
    latent and a plain causal softmax: ``[B S, nh dv]``, before ``W_o``."""
    nh, dv = cfg.num_attention_heads, cfg.v_head_dim
    q_nope, q_pe, cn, k_pe = project(cfg, lp, xn, sin, cos)
    c = _scaled(cn, kv_scale(cfg), cn.dtype)
    w_uk, w_uv = up_projections(cfg, lp)
    k_nope = jnp.einsum("tc,chn->thn", c, w_uk)
    v = jnp.einsum("tc,chv->thv", c, w_uv)
    rows = lambda t: t.reshape((B, S) + t.shape[1:])       # noqa: E731
    s = (jnp.einsum("bqhn,bkhn->bhqk", rows(q_nope), rows(k_nope),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bqhr,bkr->bhqk", rows(q_pe), rows(k_pe),
                      preferred_element_type=jnp.float32))
    causal = jnp.tril(jnp.ones((S, S), bool))
    p = jax.nn.softmax(jnp.where(causal, s * cfg.softmax_scale, -jnp.inf),
                       axis=-1)
    o = jnp.einsum("bhqk,bkhv->bqhv", p.astype(v.dtype), rows(v))
    return o.reshape(B * S, nh * dv)


def absorbed(cfg, lp, xn, sin, cos, pages, lay, block_tables, seq_lens,
             q_lens, layer):
    """The attention of a step's flat tokens ``xn [T, D]`` (``lay``, their
    ``StepLayout``) on the paged latents: position ``layer`` of the pool
    ``pages [L, 1, P, page, lanes]`` takes the tokens' ``[cn | k_pe | 0]``
    through ``block_tables`` (``paged_latent_write``, scope ``kv_write``) and
    every query head, as ``[q_abs | q_pe | 0]``, attends over its row's pages
    (``latent_paged_attention``, scope ``mla_core``).  Returns ``(o [T, nh
    dv]`` before ``W_o``, ``pages)``."""
    from ..ops.pallas_ops import latent_paged_attention, paged_latent_write
    nh, r, dr = cfg.num_attention_heads, cfg.kv_lora_rank, \
        cfg.qk_rope_head_dim
    lanes, factor = cfg.latent_lanes, kv_scale(cfg)
    R, Tc, T = lay.R, lay.Tc, lay.T
    q_nope, q_pe, cn, k_pe = project(cfg, lp, xn, sin, cos)
    w_uk, w_uv = up_projections(cfg, lp)
    q_abs = _einsum_scaled("thn,chn->thc", q_nope, w_uk, factor)
    pad = lanes - r - dr
    q_lat = jnp.concatenate(
        [q_abs, q_pe, jnp.zeros((T, nh, pad), q_abs.dtype)], axis=-1)
    new = jnp.concatenate([cn, k_pe, jnp.zeros((T, pad), cn.dtype)], axis=-1)
    new = lay.rows(new)[:, :, None, :]                      # [R, Tc, 1, lanes]
    # the query block and the output are a row's positions in order: gathered
    # row-major, they are the kernel's blocks as they lie
    q_lat = lay.rows(q_lat, row_major=True).reshape(R, 1, Tc * nh, lanes)
    with jax.named_scope("kv_write"):
        pages = paged_latent_write(pages, new, block_tables, seq_lens, q_lens,
                                   layer=layer)
    with jax.named_scope("mla_core"):
        o_lat = latent_paged_attention(
            q_lat, pages, block_tables, seq_lens, q_lens, rep=nh, v_lanes=r,
            scale=cfg.softmax_scale, layer=layer)
    o_lat = lay.flat(o_lat.reshape(R, Tc, nh, r), row_major=True)
    o = _einsum_scaled("thc,chv->thv", o_lat, w_uv, factor)
    return o.reshape(T, nh * cfg.v_head_dim), pages
