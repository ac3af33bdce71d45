"""DeepSeek-V2 (the public ``DeepseekV2ForCausalLM``; the defaults are
DeepSeek-V2-Lite): a decoder whose attention keeps ONE latent vector a token
(multi-head latent attention, MLA) and whose feed-forward layers, after the
first dense ones, route every token to a few of many small experts beside
shared ones.

With L layers, D the hidden size, ``nh`` heads, latent rank r, head sizes
``dn`` (no position), ``dr`` (rotary), ``dv`` (value), every layer on ``u [T,
D]`` is pre-normed (RMSNorm, weight, eps)::

    u <- u + MLA(RMSNorm1(u));   u <- u + FFN(RMSNorm2(u))

then a final RMSNorm and an untied head.

**MLA.**  With h the normed input of a token at position p::

    q                = h W_q                     nh heads of [q_nope dn | q_pe dr]
    [c_raw r | k_pe dr] = h W_kva
    c                = RMSNorm(c_raw)            a weight of r
    q_pe, k_pe       rotated by p (one k_pe for all heads)
    [k_nope_i dn | v_i dv] = c W_kvb             per head i
    s_i              = (q_nope_i . k_nope_i + q_pe_i . k_pe) scale   causal
    o_i              = softmax_float32(s_i) v_i;   out = [o_i] W_o

``scale = (dn + dr)^-0.5 m^2`` with ``m = 0.1 mscale_all_dim ln(factor) + 1``
under YaRN ``rope_scaling`` (1 without).  **YaRN** (``models/rotary.py``):
the ``dr / 2`` pairs' frequencies blended with ``1 / factor`` of them over a
ramp; cos and sin are scaled by ``mscale(factor, mscale) / mscale(factor,
mscale_all_dim)``, which is 1 where the two are equal.  A pair is lanes
``(j, j + dr/2)`` of the rotary part (the source's interleaved lanes ``(2j,
2j + 1)`` after a fixed permutation of W_q's and W_kva's rotary columns: the
same model under seeded weights).

**Feed-forward.**  Layers ``< first_k_dense_replace``: SwiGLU of
``intermediate_size``.  The others (``models/experts.py``): ``p = softmax(h
W_r)`` over ``n_routed_experts`` in float32, the ``num_experts_per_tok``
largest as they are (``norm_topk_prob`` false) times
``routed_scaling_factor``; ``y = shared(h) + sum_e p_e expert_e(h)``, an
expert a SwiGLU of ``moe_intermediate_size``, the shared ones ONE SwiGLU of
``n_shared_experts`` times that.  No token is dropped.  ``experts_held``
names the experts whose weights this device has (None: all); the router
scores all of them and the others' pairs add nothing here.

Parameters: ``embed, lm_head [V, D]``, ``norm_f [D]`` and two stacks, every
leaf stacked over its layers: ``dense`` (``ln1 ln2 wq wkva kv_norm wkvb wo
w_gate w_up w_down``) and ``moe`` (the same attention leaves, ``router [n,
D, E]``, the routed experts ``w_gate w_up [n, Eh, D, F]``, ``w_down [n, Eh,
F, D]``, the shared ``ws_gate ws_up [n, D, S F]``, ``ws_down [n, S F, D]``).

**Serving** (``SERVING``, the ``protocol.ServingModel`` the engine asks a
configuration for).  The cache is one pool of LATENT pages, ``latent [L, 1,
P, page, lanes]`` through the engine's block table like every pool: a token
costs, a layer, ``[c | rotated k_pe | 0]`` of ``lanes = r + dr`` rounded up
to whole 128-lane tiles (512 + 64 -> 640: the device's tiled layout would
pad a 576-lane array to 640 anyway, so the padding is stated and counted in
``cache_bytes``); no K or V head is ever stored.

The engine's step is always the ABSORBED form: with ``W_kvb = [W_UK_i |
W_UV_i]`` per head, ``q_abs_i = q_nope_i W_UK_i^T`` (r wide), ``s_i = (q_abs_i
. c + q_pe_i . k_pe) scale``, ``o_lat_i = sum p c``, ``o_i = o_lat_i W_UV_i``:
attention runs on the latent itself, every query head against the one
vector of a token, which is read once a layer
(``pallas_ops.latent_paged_attention``).  The materialised form would
expand a row's whole cache into ``nh`` K and V heads before attending,
``(dn + dv) / (r + dr)`` times ``nh`` = 7 times the bytes at every step; it
pays only for a long prefill in one piece, and the mixed step feeds chunks
of 16 tokens a row, so nothing here chooses between the two.
``forward_pure`` is the materialised form: that the two agree is the test
of the absorption.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import mla, protocol, rotary
from .experts import route_top_k, routed_experts, shared_experts
from .mla import rms_norm as _rms_norm, swiglu as _swiglu
from .protocol import layer_at as _layer_at
from .step_layout import step_frame

__all__ = ["DeepseekV2Config", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "SERVING"]

YARN_LITE = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
             "mscale": 0.707, "mscale_all_dim": 0.707,
             "original_max_position_embeddings": 4096}
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# what the device counts of a step, in the order of ``forward_paged``'s
# third result: experts with at least one row, summed over the expert
# layers, and the most rows any expert of any layer got
DEVICE_COUNTS = ("experts_hit", "expert_rows_max")


@dataclasses.dataclass
class DeepseekV2Config:
    """Fields are keys of the public ``config.json`` (the defaults are
    DeepSeek-V2-Lite's), but ``experts_held``: the ids of the routed experts
    whose weights this device has, None for all of them."""
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = dataclasses.field(
        default_factory=lambda: dict(YARN_LITE))
    max_position_embeddings: int = 163840
    experts_held: Optional[Tuple[int, ...]] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.q_lora_rank is not None or self.scoring_func != "softmax"
                or self.topk_method != "greedy" or self.moe_layer_freq != 1
                or self.n_group != 1 or self.norm_topk_prob):
            raise ValueError(
                "this model's parameters hold no query latent (models/mla.py "
                "computes one, for models/longcat_flash.py), and its router "
                "is softmax scores, a greedy top-k over one group taken as "
                "it is, an expert layer in every layer after the dense "
                f"ones; got {self}")
        if not 0 < self.first_k_dense_replace < self.num_hidden_layers:
            raise ValueError("dense layers first, then expert layers: "
                             f"{self.first_k_dense_replace} of "
                             f"{self.num_hidden_layers}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary lanes pair up")
        if self.experts_held is not None:
            self.experts_held = tuple(int(e) for e in self.experts_held)

    @property
    def num_moe_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def num_experts_held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else len(self.experts_held))

    @property
    def latent_lanes(self) -> int:
        """Lanes of a cached token's vector: ``r + dr`` in whole tiles."""
        return mla.latent_lanes(self)

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        rs = self.rope_scaling
        if rs and rs.get("mscale_all_dim"):
            scale *= rotary.yarn_mscale(rs["factor"], rs["mscale_all_dim"]) ** 2
        return scale


PRESETS: Dict[str, Dict[str, Any]] = {
    "deepseek-v2-lite": {},
    # one dense and two expert layers, eight experts of which two a token
    # and one shared; widths at which the Pallas kernels qualify (a latent
    # of 128 + 16 -> 256 lanes, 128-lane hidden and expert widths); YaRN
    # over so short an original length that sequences of test length turn
    # differently with and without it
    "deepseek-v2-debug": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=128,
        qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2,
        max_position_embeddings=2048,
        rope_scaling=dict(YARN_LITE, original_max_position_embeddings=16)),
}


preset = partial(protocol.preset, DeepseekV2Config, PRESETS)
config_from_fields = partial(protocol.config_from_fields, DeepseekV2Config)


def rope_frequencies(cfg: DeepseekV2Config):
    """``(inv_freq [dr / 2] float32 numpy, what cos and sin are scaled
    by)``: ``rotary.frequencies`` of the rope lanes under ``rope_scaling``."""
    return rotary.frequencies(cfg.rope_theta, cfg.qk_rope_head_dim,
                              cfg.rope_scaling)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_count(cfg: DeepseekV2Config) -> int:
    """Parameters of the tree ``init_params`` makes: what this device holds
    (``experts_held`` of the routed experts)."""
    D, I, F = (cfg.hidden_size, cfg.intermediate_size,
               cfg.moe_intermediate_size)
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    attn = (D * nh * (dn + dr) + D * (r + dr) + r + r * nh * (dn + dv)
            + nh * dv * D)
    moe = (D * cfg.n_routed_experts + cfg.num_experts_held * 3 * D * F
           + 3 * D * cfg.n_shared_experts * F)
    return (cfg.first_k_dense_replace * (attn + 2 * D + 3 * D * I)
            + cfg.num_moe_layers * (attn + 2 * D + moe)
            + 2 * cfg.vocab_size * D + D)


def init_params(cfg: DeepseekV2Config, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, norm weights 1.  The routed
    experts' stacks are drawn a layer at a time (``lax.map``), so that the
    float32 draws of 1.5 G elements a leaf never stand whole beside the
    weights."""
    D, I, F, V = (cfg.hidden_size, cfg.intermediate_size,
                  cfg.moe_intermediate_size, cfg.vocab_size)
    nh, r = cfg.num_attention_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    E, Eh, S = cfg.n_routed_experts, cfg.num_experts_held, \
        cfg.n_shared_experts
    nd, nm = cfg.first_k_dense_replace, cfg.num_moe_layers
    k = iter(jax.random.split(key, 40))

    def normal(shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * 0.02).astype(cfg.dtype)

    def by_layer(n, shape):
        return lax.map(
            lambda key: (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(cfg.dtype),
            jax.random.split(next(k), n))

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    def attn(n):
        return {"ln1": ones(n, D), "ln2": ones(n, D),
                "wq": normal((n, D, nh * (dn + dr))),
                "wkva": normal((n, D, r + dr)), "kv_norm": ones(n, r),
                "wkvb": normal((n, r, nh * (dn + dv))),
                "wo": normal((n, nh * dv, D))}

    dense = dict(attn(nd), w_gate=normal((nd, D, I)), w_up=normal((nd, D, I)),
                 w_down=normal((nd, I, D)))
    moe = dict(attn(nm), router=normal((nm, D, E)),
               w_gate=by_layer(nm, (Eh, D, F)), w_up=by_layer(nm, (Eh, D, F)),
               w_down=by_layer(nm, (Eh, F, D)),
               ws_gate=normal((nm, D, S * F)), ws_up=normal((nm, D, S * F)),
               ws_down=normal((nm, S * F, D)))
    return {"embed": normal((V, D)), "lm_head": normal((V, D)),
            "norm_f": ones(D), "dense": dense, "moe": moe}


# ---------------------------------------------------------------------------
# the layers, on the flat tokens of a step
# ---------------------------------------------------------------------------

def _expert_ffn(cfg, lp, experts, xn, l, live):
    """The expert layer ``l`` of the stacks for the normed tokens ``xn [T,
    D]``: ``shared(x) + sum_e p_e expert_e(x)``, and the rows each held
    expert got.  ``experts`` are the routed experts' stacks of every layer,
    whole."""
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            weights, chosen = route_top_k(xn, lp["router"],
                                          cfg.num_experts_per_tok)
            weights = weights * cfg.routed_scaling_factor
        routed, rows = routed_experts(
            xn, weights, chosen, *(experts[n] for n in EXPERT_LEAVES),
            num_experts=cfg.n_routed_experts, held=cfg.experts_held, layer=l,
            live=live)
        shared = shared_experts(xn, lp["ws_gate"], lp["ws_up"],
                                lp["ws_down"])
        return (shared + routed).astype(xn.dtype), rows


def _forward(cfg, params, ids, pos, live, mixer, carry):
    """Embedding, the layers in order, final norm, head, on the flat tokens
    ``ids, pos [T]`` (``live [T]``: which of them are tokens); returns the
    logits ``[T, V]`` float32, ``carry`` as the mixers left it, and the two
    ``DEVICE_COUNTS``.  ``mixer(lp, xn, i, sin, cos, carry) -> (out [T, nh
    dv], carry)`` is layer i's attention between its projections' inputs and
    ``W_o``.  The dense layers run one by one, the expert layers as one scan
    over their stack."""
    eps, nd = cfg.rms_norm_eps, cfg.first_k_dense_replace
    inv_freq, attn_factor = rope_frequencies(cfg)
    angle = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None, :]
    sin, cos = jnp.sin(angle) * attn_factor, jnp.cos(angle) * attn_factor

    def attention(lp, h, i, carry):
        with jax.named_scope("attn_mla"):
            out, carry = mixer(lp, _rms_norm(h, lp["ln1"], eps), i, sin, cos,
                               carry)
            return h + out.astype(h.dtype) @ lp["wo"], carry

    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], ids, axis=0)
    with jax.named_scope("layers"):
        for i in range(nd):
            lp = jax.tree_util.tree_map(lambda w: w[i], params["dense"])
            h, carry = attention(lp, h, i, carry)
            with jax.named_scope("mlp"):
                h = h + _swiglu(_rms_norm(h, lp["ln2"], eps), lp["w_gate"],
                                lp["w_up"], lp["w_down"])
        experts = {n: params["moe"][n] for n in EXPERT_LEAVES}
        rest = {n: w for n, w in params["moe"].items() if n not in experts}

        def expert_layer(state, l):
            h, carry, hit, most = state
            lp = _layer_at(rest, l)
            h, carry = attention(lp, h, nd + l, carry)
            y, rows = _expert_ffn(cfg, lp, experts,
                                  _rms_norm(h, lp["ln2"], eps), l, live)
            return (h + y, carry, hit + jnp.sum(rows > 0, dtype=jnp.int32),
                    jnp.maximum(most, jnp.max(rows))), None

        zero = jnp.zeros((), jnp.int32)
        (h, carry, hit, most), _ = lax.scan(
            expert_layer, (h, carry, zero, zero),
            jnp.arange(cfg.num_moe_layers, dtype=jnp.int32))
    with jax.named_scope("lm_head"):
        h = _rms_norm(h, params["norm_f"], eps)
        logits = jnp.einsum("td,vd->tv", h, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, carry, jnp.stack([hit, most])


def forward_pure(cfg: DeepseekV2Config, params, input_ids):
    """Logits ``[B, S, V]`` float32 of whole sequences ``[B, S]``: no cache,
    the MATERIALISED form of the attention (every head's K and V expanded
    from the latent, plain causal softmax)."""
    B, S = input_ids.shape

    def mixer(lp, xn, i, sin, cos, carry):
        return mla.materialised(cfg, lp, xn, sin, cos, B, S), carry

    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    logits, _, _ = _forward(cfg, params, input_ids.reshape(-1), pos,
                            jnp.ones((B * S,), bool), mixer, None)
    return logits.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# serving: the engine's protocol
# ---------------------------------------------------------------------------

def init_cache(cfg: DeepseekV2Config, slots: int, num_pages: int,
               page_size: int, kv_dtype):
    """The cache of an engine: one zeroed pool of latent pages, every layer
    stacked.  Nothing is kept a slot."""
    del slots
    protocol.refuse_narrow_pages(kv_dtype)
    return {"latent": jnp.zeros((cfg.num_hidden_layers, 1, num_pages,
                                 page_size, cfg.latent_lanes), kv_dtype)}


def cache_bytes(cfg: DeepseekV2Config, kv_dtype_bytes: int = 2,
                page_size: int = 128) -> dict:
    """What the cache costs: a token's latent in every layer, ``r + dr``
    elements in whole 128-lane tiles (576 -> 640: the padding is counted);
    nothing a slot."""
    del page_size
    return {"per_token": (cfg.num_hidden_layers * cfg.latent_lanes
                          * kv_dtype_bytes),
            "scales_per_page": 0, "per_slot": 0}


def step_counts(cfg: DeepseekV2Config, seq_lens, q_lens) -> dict:
    """What one step's layers work on, from the host's arrays ``seq_lens,
    q_lens [R]``: ``moe_pairs``, the (token, expert) rows of all expert
    layers; ``latent_kv_tokens``, the cached vectors a layer's attention
    reads (a fed row's, to its length), and ``latent_qk_pairs``, the (query
    token, cached token) pairs it scores.  The engine puts them on its
    ``serve/engine_step`` span."""
    seq, q = np.asarray(seq_lens, np.int64), np.asarray(q_lens, np.int64)
    return {"moe_pairs": int(q.sum()) * cfg.num_experts_per_tok
            * cfg.num_moe_layers,
            "latent_kv_tokens": int(seq[q > 0].sum()),
            "latent_qk_pairs": int(np.dot(q, seq))}


def forward_paged(cfg: DeepseekV2Config, params, tokens, cache, block_tables,
                  seq_lens, q_lens, step_tokens=None, device_counts=False):
    """The engine's step (``protocol.ServingModel``) over ``cache``
    (``init_cache``); ``device_counts``: the ``[2]`` of ``DEVICE_COUNTS``
    third.  The absorbed form (module docstring): a layer writes its tokens'
    ``[c | k_pe | 0]`` into the latent pool through ``block_tables``
    (``paged_latent_write``) and every query head, as ``[q_abs | q_pe | 0]``,
    attends over the row's pages of that ONE pool (``latent_paged_attention``).
    Projections, rotation, the absorption and the expert layers are per token,
    on the flat layout; padding tokens are routed to no expert."""
    lay, ids, pos, live = step_frame(tokens, seq_lens, q_lens, step_tokens)

    def mixer(lp, xn, i, sin, cos, pages):
        return mla.absorbed(cfg, lp, xn, sin, cos, pages, lay, block_tables,
                            seq_lens, q_lens, i)

    logits, pages, counts = _forward(cfg, params, ids, pos, live, mixer,
                                     cache["latent"])
    return lay.result(logits, {"latent": pages},
                      counts if device_counts else None)


SERVING = protocol.ServingModel(
    forward_paged=forward_paged, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    step_counts=step_counts, device_counts=DEVICE_COUNTS)
DeepseekV2Config.serving = SERVING   # cfg.serving, the engine's model
