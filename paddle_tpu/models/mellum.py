"""Mellum 2 (the public ``mellum``; the defaults are Mellum2-12B-A2.5B-Instruct,
"12B-A2.5B"): a decoder of sequential pre-norm blocks whose attention layers
come in periods of three sliding-window layers with plain rotary positions
and one full layer with YaRN-stretched ones, and whose every feed-forward
layer routes each token to 8 of 64 experts by a softmax, renormalised.

With L layers (a whole number of periods of ``layer_types``), D the hidden
size, ``nh`` query heads over ``nkv`` K/V heads of d (head i reads K/V head
``i // (nh / nkv)``), W the window, F an expert's width and ``RMS`` the
RMSNorm (float32 statistics, a weight), layer l on ``x [T, D]`` is::

    h      = RMS1_l(x)
    q, k, v = h W_q, h W_k, h W_v                       no bias, no q/k norm
    window layer:  q, k <- rotary(q), rotary(k), plain, of the window's theta
                   key j is seen by query i iff  i - W < j <= i
    full layer:    q, k <- rotary(q), rotary(k), YaRN, cos and sin times
                   attention_factor;            key j seen iff j <= i
    x      <- x + concat_heads(softmax(q k^T / sqrt(d) over the seen keys) v) W_o
    h      = RMS2_l(x)
    p      = softmax(h W_r)                           [T, E], float32
    idx    = the K largest of p (ties to the lower index);  w = p[idx] / sum(p[idx])
    x      <- x + sum_k w_k E_{idx_k}(h)

``E(h) = W_d (silu(W_g h) * W_u h)``; after the last layer ``logits = RMS_f(x)
W_head^T``, an untied head.  Each kind of layer has its own entry of
``rope_parameters`` (``rotary.frequencies``: the full layers' YaRN blend and
its attention factor, which scales the scores by its square); pair j of a
head's d lanes is lanes ``(j, j + d/2)`` (the configuration's ``assumed``).
The multi-token-prediction head that ``described_as`` names is not built.

Parameters: ``embed, lm_head [V, D]``, ``norm_f [D]``, ``layers`` (every leaf
stacked over the L layers: ``ln1 ln2 [L, D]``, ``wqkv [L, D, (nh + 2 nkv)
d]``, ``wo [L, nh d, D]``, ``router [L, D, E]``) and ``experts`` (``w_gate
w_up [L, E, D, F]``, ``w_down [L, E, F, D]``), every expert held.

**Serving** (``SERVING``).  The cache is the full layers' K/V of every token
in the engine's pages (``k_pages / v_pages [L_full, nkv, P, page, d]``) and
the window layers' K/V in a ring of ``ceil(W / page) + 2`` pages a slot
(``kw_pages / vw_pages``, ``models/rings.py``), so ``rings`` makes the engine
refuse the prefix cache and speculation.  The layers run as
``rings.scan_periods``, as Command A+'s do.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import protocol, rings, rotary
from .experts import route_top_k, routed_experts
from .mla import rms_norm, rotate

__all__ = ["MellumConfig", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "DEVICE_COUNTS",
           "SERVING"]

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# what the device counts of a step, in the order of ``forward_paged``'s third
# result, each summed over the layers: experts with at least one row, the
# most rows any expert of any layer got, and the pairs routed
DEVICE_COUNTS = ("experts_hit", "expert_rows_max", "held_rows")
WINDOW, FULL = "sliding_attention", "full_attention"
PUBLISHED_TYPES = (WINDOW, WINDOW, WINDOW, FULL) * 7
PUBLISHED_ROPE = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    WINDOW: {"rope_type": "default", "rope_theta": 500000}}


@dataclasses.dataclass
class MellumConfig:
    """Fields are keys of the public ``config.json`` (the defaults are
    Mellum2-12B-A2.5B-Instruct's).  ``layer_types`` and ``mlp_layer_types``
    may be longer than the layers kept (the published lists of 28); None is
    the published list.  ``intermediate_size`` (a dense layer's width) is
    kept as published and read by nothing: every layer is an expert
    layer."""
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    sliding_window: int = 1024
    use_sliding_window: bool = True
    max_window_layers: int = 0
    layer_types: Optional[Tuple[str, ...]] = None
    mlp_layer_types: Optional[Tuple[str, ...]] = None
    rope_parameters: Dict[str, dict] = dataclasses.field(
        default_factory=lambda: {k: dict(v) for k, v in
                                 PUBLISHED_ROPE.items()})
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    use_qk_norm: bool = False
    partial_rotary_factor: float = 1.0
    num_shared_experts: int = 0
    hidden_act: str = "silu"
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.num_hidden_layers
        types = tuple(self.layer_types or PUBLISHED_TYPES)
        mlp = tuple(self.mlp_layer_types or ("sparse",) * len(types))
        self.layer_types, self.mlp_layer_types = types, mlp
        if (self.attention_bias or self.use_qk_norm
                or self.partial_rotary_factor != 1
                or self.num_shared_experts or self.tie_word_embeddings
                or self.hidden_act != "silu" or not self.use_sliding_window
                or self.max_window_layers or set(mlp[:L]) != {"sparse"}
                or len(mlp) < L or not self.norm_topk_prob):
            raise ValueError(
                "written for sequential blocks of attention with no bias, no "
                "q/k norm and rotary over every lane, window layers as "
                "layer_types says, an expert layer in every layer with no "
                "shared expert, SwiGLU experts whose top-k weights are "
                f"renormalised, and an untied head; got {self}")
        period = types.index(FULL) + 1 if FULL in types else 0
        want = ((WINDOW,) * (period - 1) + (FULL,)) * (L // max(period, 1))
        if period < 2 or L % period or types[:L] != want:
            raise ValueError(
                "layer_types are whole periods of window layers and then one "
                f"full layer, for every layer kept; got {types[:L]} for {L} "
                "layers")
        if set(self.rope_parameters) != {WINDOW, FULL}:
            raise ValueError("rope_parameters has an entry for each kind of "
                             f"layer; got {sorted(self.rope_parameters)}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError(f"{self.num_attention_heads} query heads on "
                             f"{self.num_key_value_heads}, lanes pair up")

    @property
    def period(self) -> int:
        """Layers of a period: its window layers and then one full layer."""
        return self.layer_types.index(FULL) + 1

    @property
    def num_global_layers(self) -> int:
        return self.num_hidden_layers // self.period

    @property
    def num_window_layers(self) -> int:
        return self.num_hidden_layers - self.num_global_layers

    @property
    def stretched_from(self) -> Optional[int]:
        """The position from which YaRN stretches the full layers' rotary
        (its ``original_max_position_embeddings``), None without YaRN."""
        return self.rope_parameters[FULL].get(
            "original_max_position_embeddings")

    def layer_kinds(self) -> list:
        """The kind of every layer in order."""
        return ["global" if t == FULL else "window"
                for t in self.layer_types[:self.num_hidden_layers]]


PRESETS: Dict[str, Dict[str, Any]] = {
    "mellum2-12b-a2.5b": {},
    # two periods (six window layers, two full), eight experts of which
    # three a token, rep 8 over two K/V heads; a window of 20, shorter than
    # the tests' sequences and no multiple of their pages; YaRN from
    # position 64 with a theta at which its ramp covers pairs 0-5 of 16, so
    # that test lengths run past the stretch
    "mellum-debug": dict(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        moe_intermediate_size=128, num_hidden_layers=8,
        num_attention_heads=16, num_key_value_heads=2, head_dim=32,
        num_experts=8, num_experts_per_tok=3, sliding_window=20,
        max_position_embeddings=1024,
        rope_parameters={
            FULL: dict(PUBLISHED_ROPE[FULL], rope_theta=10000,
                       original_max_position_embeddings=64),
            WINDOW: {"rope_type": "default", "rope_theta": 10000}}),
}


preset = partial(protocol.preset, MellumConfig, PRESETS)
config_from_fields = partial(protocol.config_from_fields, MellumConfig)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_count(cfg: MellumConfig) -> int:
    """Parameters of the tree ``init_params`` makes."""
    D, F, d = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim
    nh, nkv, E = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.num_experts)
    layer = (2 * D + D * (nh + 2 * nkv) * d + nh * d * D + D * E
             + E * 3 * D * F)
    return cfg.num_hidden_layers * layer + 2 * cfg.vocab_size * D + D


def init_params(cfg: MellumConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, norm weights 1.  The large
    leaves are drawn a layer (or an expert) at a time (``lax.map``), so that
    the float32 draws never stand whole beside the weights."""
    D, F, V, d = (cfg.hidden_size, cfg.moe_intermediate_size, cfg.vocab_size,
                  cfg.head_dim)
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    L, E = cfg.num_hidden_layers, cfg.num_experts
    k = iter(jax.random.split(key, 16))

    def normal(lead, shape):
        """``lead + shape``, one draw of ``shape`` at a time."""
        n = int(np.prod(lead))
        w = lax.map(
            lambda key: (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(cfg.dtype),
            jax.random.split(next(k), n))
        return w.reshape(tuple(lead) + tuple(shape))

    layers = {"ln1": jnp.ones((L, D), cfg.dtype),
              "ln2": jnp.ones((L, D), cfg.dtype),
              "wqkv": normal((L,), (D, (nh + 2 * nkv) * d)),
              "wo": normal((L,), (nh * d, D)),
              "router": normal((L,), (D, E))}
    experts = {"w_gate": normal((L, E), (D, F)),
               "w_up": normal((L, E), (D, F)),
               "w_down": normal((L, E), (F, D))}
    return {"embed": normal((1,), (V, D))[0],
            "lm_head": normal((1,), (V, D))[0],
            "norm_f": jnp.ones((D,), cfg.dtype), "layers": layers,
            "experts": experts}


# ---------------------------------------------------------------------------
# the layers, on the flat tokens of a step
# ---------------------------------------------------------------------------

def _rope(cfg, pos):
    """``{kind: (sin, cos) [T, d / 2]}`` float32 at the positions ``pos
    [T]``, each kind's own frequencies, scaled by its attention factor."""
    tables = {}
    with jax.named_scope("rope"):
        for kind, name in (("window", WINDOW), ("global", FULL)):
            rp = cfg.rope_parameters[name]
            inv_freq, scale = rotary.frequencies(rp["rope_theta"],
                                                 cfg.head_dim, rp)
            angle = pos.astype(jnp.float32)[:, None] \
                * jnp.asarray(inv_freq)[None, :]
            tables[kind] = (jnp.sin(angle) * scale, jnp.cos(angle) * scale)
    return tables


def _attention(cfg, lp, xn, kind, at, rope, attend, pools):
    """The attention of a layer of ``kind`` (``window`` or ``global``) on the
    normed flat tokens ``xn [T, D]``: the projections, the kind's rotary,
    ``attend(kind, at, q [T, nh, d], k, v [T, nkv, d], pools) -> (out [T, nh
    d], pools)`` (``at``: the layer among those of its kind), W_o."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    T = xn.shape[0]
    with jax.named_scope("attn"), jax.named_scope(f"attn_{kind}"):
        q, k, v = jnp.split(xn @ lp["wqkv"], [nh * d, (nh + nkv) * d],
                            axis=-1)
        sin, cos = rope[kind]
        q = rotate(q.reshape(T, nh, d), sin, cos)
        k = rotate(k.reshape(T, nkv, d), sin, cos)
        out, pools = attend(kind, at, q, k, v.reshape(T, nkv, d), pools)
        return out.astype(xn.dtype) @ lp["wo"], pools


def _expert_layer(cfg, lp, experts, xn, l, live):
    """The expert layer ``l`` for the normed tokens ``xn [T, D]`` (float32),
    and the rows each expert got ``[E]``.  ``experts`` are the experts'
    stacks of every layer, whole."""
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            weights, chosen = route_top_k(
                xn, lp["router"], cfg.num_experts_per_tok, score="softmax",
                renormalize=True)
        return routed_experts(
            xn, weights, chosen, *(experts[n] for n in EXPERT_LEAVES),
            num_experts=cfg.num_experts, layer=l, live=live)


def _forward(cfg, params, ids, pos, live, attend, pools):
    """Embedding, the layers, final norm, head, on the flat tokens ``ids,
    pos [T]`` (``live [T]``: which of them are tokens); returns the logits
    ``[T, V]`` float32, ``pools`` as ``attend`` left them, and the three
    ``DEVICE_COUNTS``."""
    eps = cfg.rms_norm_eps
    rope = _rope(cfg, pos)
    lw = params["layers"]

    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], ids, axis=0)

    def layer(state, l, kind, at):
        """Layer ``l`` of the stacks, of ``kind``, ``at`` among its kind's.
        Each leaf is indexed ONCE from its whole stack."""
        h, pools, (hit, most, held) = state
        lp = protocol.layer_at(lw, l)
        with jax.named_scope("norm"):
            xn = rms_norm(h, lp["ln1"], eps)
        a, pools = _attention(cfg, lp, xn, kind, at, rope, attend, pools)
        h = h + a
        with jax.named_scope("norm"):
            xn = rms_norm(h, lp["ln2"], eps)
        m, rows = _expert_layer(cfg, lp, params["experts"], xn, l, live)
        h = h + m.astype(h.dtype)
        return h, pools, (hit + jnp.sum(rows > 0, dtype=jnp.int32),
                          jnp.maximum(most, jnp.max(rows)),
                          held + jnp.sum(rows))

    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope("layers"):
        h, pools, counts = rings.scan_periods(
            layer, (h, pools, (zero,) * 3), cfg.num_global_layers,
            cfg.period)
    with jax.named_scope("lm_head"):
        h = rms_norm(h, params["norm_f"], eps)
        logits = jnp.einsum("td,vd->tv", h, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, pools, jnp.stack(counts)


def forward_pure(cfg: MellumConfig, params, input_ids):
    """Logits ``[B, S, V]`` float32 of whole sequences ``[B, S]``: no cache,
    plain masked attention (the window a mask)."""
    B, S = input_ids.shape
    attend = rings.masked_attend(cfg, B, S)
    pos = jnp.tile(jnp.arange(S, dtype=jnp.int32), B)
    logits, _, _ = _forward(cfg, params, input_ids.reshape(-1), pos,
                            jnp.ones((B * S,), bool), attend, None)
    return logits.reshape(B, S, -1)


# ---------------------------------------------------------------------------
# serving: the engine's protocol
# ---------------------------------------------------------------------------

init_cache = rings.init_cache
cache_bytes = rings.cache_bytes


def step_counts(cfg: MellumConfig, seq_lens, q_lens) -> dict:
    """What ONE window layer reads in a step (``rings.window_counts``:
    ``window_kv_tokens``, ``window_qk_pairs``) and ``stretched_tokens``, the
    fed tokens at positions YaRN stretches in the full layers (from its
    ``original_max_position_embeddings`` on), from the host's arrays
    ``seq_lens, q_lens [R]``; the engine puts them on its
    ``serve/engine_step`` span beside ``kv_tokens`` and ``qk_pairs``, which
    count ONE full layer."""
    counts = rings.window_counts(seq_lens, q_lens, cfg.sliding_window)
    if cfg.stretched_from is not None:
        seq = np.asarray(seq_lens, np.int64)
        first = np.maximum(seq - np.asarray(q_lens, np.int64),
                           cfg.stretched_from)
        counts["stretched_tokens"] = int(np.maximum(seq - first, 0).sum())
    return counts


def forward_paged(cfg: MellumConfig, params, tokens, cache, block_tables,
                  seq_lens, q_lens, step_tokens=None, device_counts=False):
    """The engine's step (``protocol.ServingModel``): ``rings.serve_step``
    over ``cache`` (``init_cache``: the global layers' pages, the window
    layers' rings); ``device_counts``: the ``[3]`` of ``DEVICE_COUNTS``
    third.  Projections and the expert layer are per token, on the flat
    layout; padding tokens are routed to no expert."""
    return rings.serve_step(cfg, partial(_forward, cfg, params), tokens,
                            cache, block_tables, seq_lens, q_lens,
                            step_tokens, device_counts)


SERVING = protocol.ServingModel(
    forward_paged=forward_paged, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    step_counts=step_counts, device_counts=DEVICE_COUNTS, rings=True)
MellumConfig.serving = SERVING   # cfg.serving, the engine's model
