"""Command A+ (the public ``cohere2_moe``; the defaults are
command-a-plus-05-2026, "218B-A25B"): a decoder of PARALLEL blocks, attention
and an expert layer side by side on one LayerNorm of the residual, whose
attention layers come in periods of three sliding-window layers with rotary
positions and one global layer with none, and whose expert layers route
each token to 8 of 128 experts by a sigmoid beside four shared experts that
are averaged.

With L layers (``L % layer_switch == 0``), D the hidden size, ``nh`` query
heads over ``nkv`` K/V heads of d (head i reads K/V head ``i // (nh /
nkv)``), W the window, F an expert's width, ``LN`` Cohere's LayerNorm
(``(x - mean) / sqrt(var + eps) g``: a weight and no bias, float32
statistics), layer l on ``x [T, D]`` is::

    h      = LN_l(x)
    q, k, v = h W_q, h W_k, h W_v
    window layer (l % 4 != 3):  q, k <- rotary(q), rotary(k) at the token's position
                                key j is seen by query i iff  i - W < j <= i
    global layer (l % 4 == 3):  no positional term;   key j seen iff j <= i
    A      = concat_heads(softmax(q k^T / sqrt(d) over the seen keys) v) W_o
    s      = sigmoid(h W_r)                          [T, E], float32
    idx    = the K largest of s (ties to the lower index);  w = s[idx] / sum(s[idx])
    M      = sum_k w_k E_{idx_k}(h) + (1 / S) sum_j Shared_j(h)
    x      <- x + A + M

``E(h) = W_d (silu(W_g h) * W_u h)`` for a routed expert and a shared one
alike, ``S = num_shared_experts``; after the last layer ``logits =
logit_scale LN_f(x) Emb^T``, the head tied to the embedding.  The rotary
is of ``rope_theta`` over every lane (``rotary_pct`` 1); pair j is lanes
``(j, j + d/2)``, the published interleaved pairs ``(2j, 2j + 1)`` after a
fixed permutation of the rotary columns of W_q and W_k (the configuration's
``assumed``).

**A chip's share** (``models/experts.py``): ``experts_held`` names the routed
experts whose weights this device has (``num_experts`` of them, of the
``num_experts_published`` the router scores); the others' pairs add nothing
here.  Attention, the router and the shared experts are computed where the
token lives, whole.  ``vocab_size`` may be a slice of
``vocab_size_published``.

Parameters: ``embed [V, D]`` (the head too), ``norm_f [D]``, ``layers``
(every leaf stacked over the L layers: ``ln [L, D]``, ``wqkv [L, D, (nh + 2
nkv) d]``, ``wo [L, nh d, D]``, ``router [L, D, E]``, the shared experts side
by side ``ws_gate ws_up [L, D, S F]``, ``ws_down [L, S F, D]``) and
``experts`` (``w_gate w_up [L, Eh, D, F]``, ``w_down [L, Eh, F, D]``).

**Serving** (``SERVING``).  The cache is the global layers' K/V of every
token in the engine's pages (``k_pages / v_pages [L/4, nkv, P, page, d]``,
through the engine's block table) and the window layers' K/V in a RING of
``ceil(W / page) + 2`` pages a slot (``kw_pages / vw_pages [3 L/4, nkv, 1 +
R x Wp, page, d]``, ``models/rings.py``): a window layer never holds more
than its window, whatever the length of the request.  No recurrent state.
A prefix-cache hit would skip tokens the rings never saw, and a rejected
draft would overwrite ring entries still in the window: ``rings`` makes the
engine refuse both.  The layers run as ``rings.scan_periods``: one scan over
the periods, each a scan over its window layers and then its global layer.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import protocol, rings
from .experts import route_top_k, routed_experts, shared_experts
from .mla import rotate

__all__ = ["Cohere2MoeConfig", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "DEVICE_COUNTS",
           "SERVING"]

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# what the device counts of a step, in the order of ``forward_paged``'s third
# result, each summed over the layers: held experts with at least one row,
# the most rows any held expert of any layer got, and the pairs that landed
# on a held expert
DEVICE_COUNTS = ("experts_hit", "expert_rows_max", "held_rows")


@dataclasses.dataclass
class Cohere2MoeConfig:
    """Fields are keys of the public ``config.json`` (the defaults are
    command-a-plus-05-2026's), but three that state a chip's share:
    ``experts_held``, the ids of the ``num_experts`` routed experts whose
    weights this device has (None: all of them), among the
    ``num_experts_published`` the router scores (None: as many), and
    ``vocab_size_published``, the vocabulary that ``vocab_size`` is a slice
    of (None: the whole).  ``layer_types`` may be longer than the layers
    kept (the published list of 32); None is the pattern of
    ``layer_switch``."""
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 128
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 4
    sliding_window: int = 4096
    layer_switch: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    order_of_interleaved_layers: str = "local_attn_first"
    rope_theta: float = 50000.0
    rotary_pct: float = 1.0
    position_embedding_type: str = "rope_gptj"
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 200000
    expert_selection_fn: str = "sigmoid"
    norm_topk_prob: bool = True
    shared_expert_combination_strategy: str = "average"
    use_parallel_block: bool = True
    first_k_dense_replace: int = 0
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    use_qk_norm: bool = False
    use_gated_activation: bool = True
    hidden_act: str = "silu"
    experts_held: Optional[Tuple[int, ...]] = None
    num_experts_published: Optional[int] = None
    vocab_size_published: Optional[int] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L, S = self.num_hidden_layers, self.layer_switch
        if (not self.use_parallel_block or self.first_k_dense_replace
                or self.rotary_pct != 1 or self.attention_bias
                or self.use_qk_norm or not self.tie_word_embeddings
                or not self.use_gated_activation or self.hidden_act != "silu"
                or self.position_embedding_type != "rope_gptj"
                or self.order_of_interleaved_layers != "local_attn_first"
                or self.expert_selection_fn != "sigmoid"
                or not self.norm_topk_prob
                or self.shared_expert_combination_strategy != "average"):
            raise ValueError(
                "written for parallel blocks with no dense prefix, rotary "
                "over every lane of the window layers, no bias and no q/k "
                "norm, a tied head, SwiGLU experts chosen by a sigmoid and "
                "renormalised beside shared ones averaged, window layers "
                f"first; got {self}")
        if S < 2 or L % S:
            raise ValueError(f"{L} layers are no whole number of periods of "
                             f"{S} (layer_switch)")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            want = tuple(("full_attention" if i % S == S - 1 else
                          "sliding_attention")
                         for i in range(len(self.layer_types)))
            if len(self.layer_types) < L or self.layer_types != want:
                raise ValueError(
                    "layer_types is the pattern of layer_switch "
                    f"{S} (window layers first), for every layer kept; got "
                    f"{self.layer_types}")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.head_dim % 2:
            raise ValueError(f"{self.num_attention_heads} query heads on "
                             f"{self.num_key_value_heads}, lanes pair up")
        self.experts_held = protocol.chip_share(
            self, self.num_experts, self.router_experts, "num_experts")

    @property
    def router_experts(self) -> int:
        """The routed experts the router scores: the published count."""
        return self.num_experts_published or self.num_experts

    @property
    def num_global_layers(self) -> int:
        return self.num_hidden_layers // self.layer_switch

    @property
    def num_window_layers(self) -> int:
        return self.num_hidden_layers - self.num_global_layers

    def layer_kinds(self) -> list:
        """The kind of every layer in order."""
        return ["global" if i % self.layer_switch == self.layer_switch - 1
                else "window" for i in range(self.num_hidden_layers)]


PRESETS: Dict[str, Dict[str, Any]] = {
    "command-a-plus": {},
    # two periods (six window layers, two global), eight routed experts of
    # which three a token beside two shared ones; a window of 20, shorter
    # than the tests' sequences and no multiple of their pages
    "cohere2-moe-debug": dict(
        vocab_size=256, hidden_size=128, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, num_experts=8, num_experts_per_tok=3,
        num_shared_experts=2, sliding_window=20,
        max_position_embeddings=1024),
}


preset = partial(protocol.preset, Cohere2MoeConfig, PRESETS)
config_from_fields = partial(protocol.config_from_fields, Cohere2MoeConfig)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_count(cfg: Cohere2MoeConfig) -> int:
    """Parameters of the tree ``init_params`` makes: what this device holds
    (``num_experts`` of the routed experts, ``vocab_size`` rows)."""
    D, F, d = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    nh, nkv = cfg.num_attention_heads, cfg.num_key_value_heads
    layer = (D + D * (nh + 2 * nkv) * d + nh * d * D
             + D * cfg.router_experts
             + (cfg.num_shared_experts + cfg.num_experts) * 3 * D * F)
    return cfg.num_hidden_layers * layer + cfg.vocab_size * D + D


def init_params(cfg: Cohere2MoeConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, norm weights 1.  The large
    leaves are drawn a layer (or an expert) at a time (``lax.map``), so that
    the float32 draws never stand whole beside the weights."""
    D, F, V, d = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.head_dim)
    nh, nkv, S = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.num_shared_experts)
    L, Eh = cfg.num_hidden_layers, cfg.num_experts
    k = iter(jax.random.split(key, 16))

    def normal(lead, shape):
        """``lead + shape``, one draw of ``shape`` at a time."""
        n = int(np.prod(lead))
        w = lax.map(
            lambda key: (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(cfg.dtype),
            jax.random.split(next(k), n))
        return w.reshape(tuple(lead) + tuple(shape))

    layers = {"ln": jnp.ones((L, D), cfg.dtype),
              "wqkv": normal((L,), (D, (nh + 2 * nkv) * d)),
              "wo": normal((L,), (nh * d, D)),
              "router": normal((L,), (D, cfg.router_experts)),
              "ws_gate": normal((L,), (D, S * F)),
              "ws_up": normal((L,), (D, S * F)),
              "ws_down": normal((L,), (S * F, D))}
    experts = {"w_gate": normal((L, Eh), (D, F)),
               "w_up": normal((L, Eh), (D, F)),
               "w_down": normal((L, Eh), (F, D))}
    return {"embed": normal((1,), (V, D))[0],
            "norm_f": jnp.ones((D,), cfg.dtype), "layers": layers,
            "experts": experts}


# ---------------------------------------------------------------------------
# the layers, on the flat tokens of a step
# ---------------------------------------------------------------------------

def _layer_norm(x, w, eps):
    """Cohere's LayerNorm: the mean subtracted, a weight, no bias; float32
    statistics."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def _attention(cfg, lp, xn, kind, at, sin, cos, attend, pools):
    """``A`` of a layer of ``kind`` (``window`` or ``global``) on the normed
    flat tokens ``xn [T, D]``: the projections, the rotary of a window
    layer, ``attend(kind, at, q [T, nh, d], k, v [T, nkv, d], pools) -> (out
    [T, nh d], pools)`` (``at``: the layer among those of its kind), W_o."""
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    T = xn.shape[0]
    with jax.named_scope("attn"), jax.named_scope(f"attn_{kind}"):
        q, k, v = jnp.split(xn @ lp["wqkv"], [nh * d, (nh + nkv) * d],
                            axis=-1)
        q, k, v = (q.reshape(T, nh, d), k.reshape(T, nkv, d),
                   v.reshape(T, nkv, d))
        if kind == "window":
            q, k = rotate(q, sin, cos), rotate(k, sin, cos)
        out, pools = attend(kind, at, q, k, v, pools)
        return out.astype(xn.dtype) @ lp["wo"], pools


def _expert_layer(cfg, lp, experts, xn, l, live):
    """``M`` of layer ``l`` for the normed tokens ``xn [T, D]`` (float32),
    and the rows each held expert got ``[Eh]``.  ``experts`` are the routed
    experts' stacks of every layer, whole."""
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            weights, chosen = route_top_k(
                xn, lp["router"], cfg.num_experts_per_tok, score="sigmoid",
                renormalize=True)
        y, rows = routed_experts(
            xn, weights, chosen, *(experts[n] for n in EXPERT_LEAVES),
            num_experts=cfg.router_experts, held=cfg.experts_held, layer=l,
            live=live)
        shared = shared_experts(
            xn, lp["ws_gate"], lp["ws_up"], lp["ws_down"],
            count=cfg.num_shared_experts, combine="average")
        return y + shared, rows


def _forward(cfg, params, ids, pos, live, attend, pools):
    """Embedding, the layers, final norm, tied head, on the flat tokens
    ``ids, pos [T]`` (``live [T]``: which of them are tokens); returns the
    logits ``[T, V]`` float32, ``pools`` as ``attend`` left them, and the
    three ``DEVICE_COUNTS``."""
    eps, d, S = cfg.layer_norm_eps, cfg.head_dim, cfg.layer_switch
    inv_freq = cfg.rope_theta ** (-2.0 * np.arange(d // 2, dtype=np.float64)
                                  / d)
    angle = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq.astype(np.float32))[None, :]
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    lw = params["layers"]

    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], ids, axis=0)

    def layer(state, l, kind, at):
        """Layer ``l`` of the stacks, of ``kind``, ``at`` among its kind's.
        Each leaf is indexed ONCE from its whole stack (a slice with one
        reader fuses into its product)."""
        h, pools, (hit, most, held) = state
        lp = {n: lax.dynamic_index_in_dim(w, l, 0, keepdims=False)
              for n, w in lw.items()}
        with jax.named_scope("norm"):
            xn = _layer_norm(h, lp["ln"], eps)
        a, pools = _attention(cfg, lp, xn, kind, at, sin, cos, attend, pools)
        m, rows = _expert_layer(cfg, lp, params["experts"], xn, l, live)
        h = h + a + m.astype(h.dtype)
        return h, pools, (hit + jnp.sum(rows > 0, dtype=jnp.int32),
                          jnp.maximum(most, jnp.max(rows)),
                          held + jnp.sum(rows))

    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope("layers"):
        h, pools, counts = rings.scan_periods(
            layer, (h, pools, (zero,) * 3), cfg.num_global_layers, S)
    with jax.named_scope("lm_head"):
        h = _layer_norm(h, params["norm_f"], eps)
        logits = jnp.einsum("td,vd->tv", h, params["embed"],
                            preferred_element_type=jnp.float32)
        if cfg.logit_scale != 1:
            logits = logits * cfg.logit_scale
    return logits, pools, jnp.stack(counts)


def forward_pure(cfg: Cohere2MoeConfig, params, input_ids):
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


def step_counts(cfg: Cohere2MoeConfig, seq_lens, q_lens) -> dict:
    """What ONE window layer reads in a step, from the host's arrays
    ``seq_lens, q_lens [R]`` (``rings.window_counts``: ``window_kv_tokens``,
    ``window_qk_pairs``); the engine puts them on its ``serve/engine_step``
    span beside ``kv_tokens`` and ``qk_pairs``, which count ONE global
    layer."""
    return rings.window_counts(seq_lens, q_lens, cfg.sliding_window)


def forward_paged(cfg: Cohere2MoeConfig, params, tokens, cache, block_tables,
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
Cohere2MoeConfig.serving = SERVING   # cfg.serving, the engine's model
