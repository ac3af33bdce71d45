"""LongCat-Flash (the public ``LongcatFlashForCausalLM``; the defaults are
LongCat-Flash-Chat): a decoder whose every layer is TWO latent-attention
sublayers and TWO dense SwiGLUs beside ONE expert layer that skips the second
sublayer (a shortcut-connected mixture of experts), with zero-compute experts
among the router's outputs and a query latent in the attention.

With L layers, D the hidden size and ``N`` = RMSNorm (weight, eps), a layer
on ``u [T, D]`` is::

    u  <- u + MLA_0(N_a0(u))
    h  =  N_p0(u)
    s  =  MoE(h)                      the shortcut: needed only at the layer's end
    u  <- u + FFN_0(h)
    u  <- u + MLA_1(N_a1(u))
    u  <- u + FFN_1(N_p1(u)) + s

then a final RMSNorm and an untied head.  L layers are 2 L attention
sublayers; sublayer j of layer i is cache position ``2 i + j``.

**MLA_j** is ``models/mla.py``'s with a query latent: ``q = N_q(x W_qa) W_qb
sqrt(D / q_lora_rank)``, ``c = N_kv(c_raw) sqrt(D / kv_lora_rank)``
(``mla_scale_q_lora``, ``mla_scale_kv_lora``), plain rotary of ``rope_theta``
(no ``rope_scaling``), ``scale = (dn + dr)^-0.5``.

**MoE** on ``h`` (``models/experts.py``), ``E = n_routed_experts`` as
published, ``Z = zero_expert_num`` identity experts, ``K = moe_topk``, ``b [E +
Z]`` the router's ``e_score_correction_bias``::

    p      = softmax_float32(h W_r)                    over all E + Z
    idx    = the K largest of (p + b), ties to the lower index
    w_k    = p[idx_k] routed_scaling_factor            not renormalised, b not in it
    MoE(h) = sum over k with idx_k <  E of  w_k expert_{idx_k}(h)     SwiGLU of expert_ffn_hidden_size
           + (sum over k with idx_k >= E of w_k) h                    zero-compute experts

No shared expert, no dropped token; the experts a token computes number K
less a draw.  **A chip's share**: ``experts_held`` names the routed experts
whose weights this device has (``n_routed_experts`` of them, of the
``n_routed_experts_published`` the router scores); the others' pairs add
nothing here (their chips add them; nothing stands in for those chips or
their exchange), and the zero-compute experts, the attention, the dense FFNs
and the router are computed where the token lives, whole.  ``vocab_size``
may be a slice of ``vocab_size_published``: a smaller vocabulary.

Parameters: ``embed, lm_head [V, D]``, ``norm_f [D]`` and ``layers``, every
leaf stacked over the layers and a sublayer's leaves ``[L, 2, ..]``: ``attn``
(``ln wqa q_norm wqb wkva kv_norm wkvb wo``), ``mlp`` (``ln w_gate w_up
w_down``), ``router [L, D, E + Z]``, ``router_bias [L, E + Z]`` (seeded
weights leave it at the source's initial zeros) and ``experts`` (``w_gate
w_up [L, Eh, D, F]``, ``w_down [L, Eh, F, D]``).

**Serving** (``SERVING``): the cache is one pool of latent pages, ``latent
[2 L, 1, P, page, lanes]``: a token costs, a sublayer, ``[N_kv(c_raw) |
rotated k_pe | 0]`` in ``lanes`` (512 + 64 -> 640).  The engine's step is the
absorbed form (``mla.absorbed``); ``sqrt(D / kv_lora_rank)`` rides in float32
on the absorbed query and on the output, not in the cache or the weights.
``forward_pure`` is the materialised form.  The expert layer is computed
where the equations put it, before ``FFN_0``; what a scheduler makes of its
independence from the second sublayer is the compiler's (ROADMAP R1).
"""
from __future__ import annotations

import dataclasses
import types
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import mla
from .experts import route_top_k, routed_experts
from .step_layout import StepLayout

__all__ = ["LongcatFlashConfig", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "DEVICE_COUNTS",
           "SERVING"]

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
# what the device counts of a step, in the order of ``forward_paged``'s third
# result, each summed over the layers: held experts with at least one row,
# the most rows any held expert of any layer got, the pairs that landed on a
# held expert, and the pairs that chose a zero-compute expert
DEVICE_COUNTS = ("experts_hit", "expert_rows_max", "held_rows", "zero_pairs")


@dataclasses.dataclass
class LongcatFlashConfig:
    """Fields are keys of the public ``config.json`` (the defaults are
    LongCat-Flash-Chat's), but three that state a chip's share:
    ``experts_held``, the ids of the ``n_routed_experts`` routed experts whose
    weights this device has (None: all of them), among the
    ``n_routed_experts_published`` the router scores (None: as many), and
    ``vocab_size_published``, the vocabulary that ``vocab_size`` is a slice
    of (None: the whole)."""
    vocab_size: int = 131072
    hidden_size: int = 6144
    ffn_hidden_size: int = 12288
    expert_ffn_hidden_size: int = 2048
    num_layers: int = 28
    num_attention_heads: int = 64
    kv_lora_rank: int = 512
    q_lora_rank: int = 1536
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    mla_scale_q_lora: bool = True
    mla_scale_kv_lora: bool = True
    routed_scaling_factor: float = 6.0
    n_routed_experts: int = 512
    zero_expert_num: int = 256
    zero_expert_type: str = "identity"
    moe_topk: int = 12
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e7
    attention_bias: bool = False
    attention_method: str = "MLA"
    experts_held: Optional[Tuple[int, ...]] = None
    n_routed_experts_published: Optional[int] = None
    vocab_size_published: Optional[int] = None
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        if (self.attention_bias or self.attention_method != "MLA"
                or not self.q_lora_rank
                or (self.zero_expert_num
                    and self.zero_expert_type != "identity")):
            raise ValueError(
                "written for latent attention with a query latent and no "
                "bias, and zero-compute experts that are the identity; got "
                f"{self}")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rotary lanes pair up")
        if self.experts_held is not None:
            self.experts_held = tuple(int(e) for e in self.experts_held)
            if len(self.experts_held) != self.n_routed_experts or not all(
                    0 <= e < self.router_experts for e in self.experts_held):
                raise ValueError(
                    f"experts_held names {len(self.experts_held)} experts; "
                    f"n_routed_experts says {self.n_routed_experts} are here "
                    f"of the {self.router_experts} the router scores")
        elif self.router_experts != self.n_routed_experts:
            raise ValueError("a device that holds a share of the experts "
                             "says which (experts_held)")
        if self.vocab_size > (self.vocab_size_published or self.vocab_size):
            raise ValueError("a slice of the vocabulary is no larger than it")

    @property
    def router_experts(self) -> int:
        """The routed experts the router scores: the published count."""
        return self.n_routed_experts_published or self.n_routed_experts

    @property
    def latent_lanes(self) -> int:
        return mla.latent_lanes(self)

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def serving(self):
        return SERVING


PRESETS: Dict[str, Dict[str, Any]] = {
    "longcat-flash-chat": {},
    # two layers (four attention sublayers), eight routed experts beside
    # four zero-compute ones of which three a token; widths at which the
    # Pallas kernels qualify (a latent of 128 + 16 -> 256 lanes, 128-lane
    # hidden and expert widths)
    "longcat-flash-debug": dict(
        vocab_size=256, hidden_size=128, ffn_hidden_size=256,
        expert_ffn_hidden_size=128, num_layers=2, num_attention_heads=4,
        kv_lora_rank=128, q_lora_rank=64, qk_nope_head_dim=32,
        qk_rope_head_dim=16, v_head_dim=32, n_routed_experts=8,
        zero_expert_num=4, moe_topk=3, max_position_embeddings=2048),
}


def preset(name: str, **overrides) -> LongcatFlashConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown longcat_flash preset {name!r}; available: "
                       f"{sorted(PRESETS)}")
    return LongcatFlashConfig(**dict(PRESETS[name], **overrides))


def config_from_fields(fields: dict) -> LongcatFlashConfig:
    """A ``LongcatFlashConfig`` from a ``config.json``-shaped dict: every key
    that is a field, ``dtype`` by name; where the file states a chip's share
    (``experts_held``; a sliced ``vocab_size``) its ``published`` group gives
    the counts the share is of.  Other keys are not this model's."""
    names = {f.name for f in dataclasses.fields(LongcatFlashConfig)}
    kw = {k: v for k, v in fields.items() if k in names}
    for key in ("n_routed_experts", "vocab_size"):
        if key in fields.get("published", {}):
            kw.setdefault(key + "_published", fields["published"][key])
    kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16")).type
    return LongcatFlashConfig(**kw)


# ---------------------------------------------------------------------------
# parameters
# ---------------------------------------------------------------------------

def param_count(cfg: LongcatFlashConfig) -> int:
    """Parameters of the tree ``init_params`` makes: what this device holds
    (``n_routed_experts`` of the routed experts, ``vocab_size`` rows)."""
    D, I, F = cfg.hidden_size, cfg.ffn_hidden_size, cfg.expert_ffn_hidden_size
    nh, r, rq = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    scored = cfg.router_experts + cfg.zero_expert_num
    attn = (D + D * rq + rq + rq * nh * (dn + dr) + D * (r + dr) + r
            + r * nh * (dn + dv) + nh * dv * D)
    layer = (2 * attn + 2 * (D + 3 * D * I) + D * scored + scored
             + cfg.n_routed_experts * 3 * D * F)
    return cfg.num_layers * layer + 2 * cfg.vocab_size * D + D


def init_params(cfg: LongcatFlashConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, norm weights 1, the router's
    bias 0.  The large leaves are drawn a sublayer (or a layer of experts) at
    a time (``lax.map``), so that the float32 draws never stand whole beside
    the weights."""
    D, I, F, V = (cfg.hidden_size, cfg.ffn_hidden_size,
                  cfg.expert_ffn_hidden_size, cfg.vocab_size)
    nh, r, rq = cfg.num_attention_heads, cfg.kv_lora_rank, cfg.q_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    L, Eh = cfg.num_layers, cfg.n_routed_experts
    scored = cfg.router_experts + cfg.zero_expert_num
    k = iter(jax.random.split(key, 20))

    def normal(lead, shape):
        """``lead + shape``, one draw of ``shape`` at a time."""
        n = int(np.prod(lead))
        w = lax.map(
            lambda key: (jax.random.normal(key, shape, jnp.float32)
                         * 0.02).astype(cfg.dtype),
            jax.random.split(next(k), n))
        return w.reshape(tuple(lead) + tuple(shape))

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    attn = {"ln": ones(L, 2, D), "wqa": normal((L, 2), (D, rq)),
            "q_norm": ones(L, 2, rq),
            "wqb": normal((L, 2), (rq, nh * (dn + dr))),
            "wkva": normal((L, 2), (D, r + dr)), "kv_norm": ones(L, 2, r),
            "wkvb": normal((L, 2), (r, nh * (dn + dv))),
            "wo": normal((L, 2), (nh * dv, D))}
    mlp = {"ln": ones(L, 2, D), "w_gate": normal((L, 2), (D, I)),
           "w_up": normal((L, 2), (D, I)), "w_down": normal((L, 2), (I, D))}
    experts = {"w_gate": normal((L,), (Eh, D, F)),
               "w_up": normal((L,), (Eh, D, F)),
               "w_down": normal((L,), (Eh, F, D))}
    layers = {"attn": attn, "mlp": mlp, "router": normal((L,), (D, scored)),
              "router_bias": jnp.zeros((L, scored), cfg.dtype),
              "experts": experts}
    return {"embed": normal((1,), (V, D))[0],
            "lm_head": normal((1,), (V, D))[0], "norm_f": ones(D),
            "layers": layers}


# ---------------------------------------------------------------------------
# the layers, on the flat tokens of a step
# ---------------------------------------------------------------------------

def _sublayer(stack, l, j):
    """Sublayer ``j`` (static) of layer ``l`` (traced) of leaves ``[L, 2,
    ..]``, each taken from the whole stack by ONE index, ``2 l + j`` into the
    stack seen as ``[2 L, ..]``: indexed by ``l`` and then by ``j``, a leaf's
    two sublayers share one slice ``[2, ..]`` with two readers, which XLA
    copies out of the stack every layer (0.6 GB of a layer's dense FFNs
    read and written again: 13 ms of a 39 ms step on the chip, PERF.md
    section 6, PR 37); a slice with one reader fuses into its product."""
    return jax.tree_util.tree_map(
        lambda w: lax.dynamic_index_in_dim(
            w.reshape((-1,) + w.shape[2:]), 2 * l + j, 0, keepdims=False),
        stack)


def _expert_layer(cfg, router, bias, experts, xn, l, live):
    """``MoE(xn)`` of layer ``l`` for the normed tokens ``xn [T, D]``, and
    what the device counts of it: the rows each held expert got ``[Eh]`` and
    the live pairs that chose a zero-compute expert.  ``experts`` are the
    routed experts' stacks of every layer, whole."""
    with jax.named_scope("moe"):
        with jax.named_scope("moe_router"):
            weights, chosen = route_top_k(xn, router, cfg.moe_topk, bias=bias)
            weights = weights * cfg.routed_scaling_factor
            zero = chosen >= cfg.router_experts
            if live is not None:
                zero &= live[:, None]
        y, rows = routed_experts(
            xn, weights, chosen, *(experts[n] for n in EXPERT_LEAVES),
            num_experts=cfg.router_experts, held=cfg.experts_held, layer=l,
            live=live, zero_experts=cfg.zero_expert_num)
        return y.astype(xn.dtype), rows, jnp.sum(zero, dtype=jnp.int32)


def _forward(cfg, params, ids, pos, live, mixer, carry):
    """Embedding, the layers as one scan, final norm, head, on the flat
    tokens ``ids, pos [T]`` (``live [T]``: which of them are tokens); returns
    the logits ``[T, V]`` float32, ``carry`` as the mixers left it, and the
    four ``DEVICE_COUNTS``.  ``mixer(lp, xn, i, sin, cos, carry) -> (out [T,
    nh dv], carry)`` is attention sublayer i (``2 l + j``) between its
    projections' inputs and ``W_o``."""
    eps, dr = cfg.rms_norm_eps, cfg.qk_rope_head_dim
    inv_freq = cfg.rope_theta ** (-2.0 * np.arange(dr // 2, dtype=np.float64)
                                  / dr)
    angle = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(inv_freq.astype(np.float32))[None, :]
    sin, cos = jnp.sin(angle), jnp.cos(angle)
    lw = params["layers"]

    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], ids, axis=0)

    def layer(state, l):
        h, carry, counts = state

        def attention(h, j, carry):
            with jax.named_scope("attn_mla"):
                lp = _sublayer(lw["attn"], l, j)
                out, carry = mixer(lp, mla.rms_norm(h, lp["ln"], eps),
                                   2 * l + j, sin, cos, carry)
                return h + out.astype(h.dtype) @ lp["wo"], carry

        h, carry = attention(h, 0, carry)
        with jax.named_scope("mlp"):
            mp = _sublayer(lw["mlp"], l, 0)
            xn = mla.rms_norm(h, mp["ln"], eps)
        shortcut, rows, zero = _expert_layer(
            cfg, lax.dynamic_index_in_dim(lw["router"], l, 0, False),
            lax.dynamic_index_in_dim(lw["router_bias"], l, 0, False),
            lw["experts"], xn, l, live)
        with jax.named_scope("mlp"):
            h = h + mla.swiglu(xn, mp["w_gate"], mp["w_up"], mp["w_down"])
        h, carry = attention(h, 1, carry)
        with jax.named_scope("mlp"):
            mp = _sublayer(lw["mlp"], l, 1)
            h = h + mla.swiglu(mla.rms_norm(h, mp["ln"], eps), mp["w_gate"],
                               mp["w_up"], mp["w_down"]) + shortcut
        hit, most, held, zeros = counts
        counts = (hit + jnp.sum(rows > 0, dtype=jnp.int32),
                  jnp.maximum(most, jnp.max(rows)), held + jnp.sum(rows),
                  zeros + zero)
        return (h, carry, counts), None

    zero = jnp.zeros((), jnp.int32)
    with jax.named_scope("layers"):
        (h, carry, counts), _ = lax.scan(
            layer, (h, carry, (zero,) * 4),
            jnp.arange(cfg.num_layers, dtype=jnp.int32))
    with jax.named_scope("lm_head"):
        h = mla.rms_norm(h, params["norm_f"], eps)
        logits = jnp.einsum("td,vd->tv", h, params["lm_head"],
                            preferred_element_type=jnp.float32)
    return logits, carry, jnp.stack(counts)


def forward_pure(cfg: LongcatFlashConfig, params, input_ids):
    """Logits ``[B, S, V]`` float32 of whole sequences ``[B, S]``: no cache,
    the MATERIALISED form of the attention (``mla.materialised``)."""
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

def init_cache(cfg: LongcatFlashConfig, slots: int, num_pages: int,
               page_size: int, kv_dtype):
    """The cache of an engine: one zeroed pool of latent pages, the two
    sublayers of every layer stacked (position ``2 l + j``).  Nothing is kept
    a slot."""
    del slots
    if jnp.dtype(kv_dtype).itemsize < 2:
        raise ValueError(
            f"kv_dtype {jnp.dtype(kv_dtype)} pages need the per-page scale "
            "pools that only models/llama.py's step writes")
    return {"latent": jnp.zeros((2 * cfg.num_layers, 1, num_pages, page_size,
                                 cfg.latent_lanes), kv_dtype)}


def cache_bytes(cfg: LongcatFlashConfig, kv_dtype_bytes: int = 2,
                page_size: int = 128) -> dict:
    """What the cache costs: a token's latent in each of the ``2 L``
    sublayers, ``r + dr`` elements in whole 128-lane tiles (576 -> 640: the
    padding is counted); nothing a slot."""
    del page_size
    return {"per_token": (2 * cfg.num_layers * cfg.latent_lanes
                          * kv_dtype_bytes),
            "scales_per_page": 0, "per_slot": 0}


def step_counts(cfg: LongcatFlashConfig, seq_lens, q_lens) -> dict:
    """What one step's layers work on, from the host's arrays ``seq_lens,
    q_lens [R]``: ``moe_pairs``, the (token, expert) pairs of all layers
    (``fed tokens x moe_topk x num_layers``: zero-compute and absent experts'
    pairs among them); ``latent_kv_tokens``, the cached vectors ONE
    attention sublayer reads (a fed row's, to its length), and
    ``latent_qk_pairs``, the (query token, cached token) pairs it scores:
    each of the ``2 x num_layers`` sublayers reads and scores as many.  The
    engine puts them on its ``serve/engine_step`` span."""
    seq, q = np.asarray(seq_lens, np.int64), np.asarray(q_lens, np.int64)
    return {"moe_pairs": int(q.sum()) * cfg.moe_topk * cfg.num_layers,
            "latent_kv_tokens": int(seq[q > 0].sum()),
            "latent_qk_pairs": int(np.dot(q, seq))}


def forward_paged(cfg: LongcatFlashConfig, params, tokens, cache,
                  block_tables, seq_lens, q_lens, step_tokens=None,
                  device_counts=False):
    """The engine's step: ragged mixed prefill and decode rows ``tokens [R,
    Tc]`` (row r feeds ``tokens[r, :q_lens[r]]`` and then holds ``seq_lens[r]``
    tokens) over ``cache`` (``init_cache``).  Returns ``(logits [R, Tc, V]
    float32, cache)``, or with ``step_tokens = T`` the logits flat ``[T, V]``
    (``StepLayout``), as ``deepseek_v2.forward_paged``; with ``device_counts``
    a third result, the int32 ``[4]`` of ``DEVICE_COUNTS``.

    Every attention sublayer is the absorbed form on its own position of the
    one latent pool (``mla.absorbed``); projections, the dense FFNs and the
    expert layer are per token, on the flat layout; padding tokens are routed
    to no expert."""
    R, Tc = tokens.shape
    lay = StepLayout(q_lens, Tc, step_tokens)
    t_off = jnp.arange(Tc, dtype=jnp.int32)[None, :]
    start = (seq_lens - q_lens).astype(jnp.int32)[:, None]
    pos = lay.flat(jnp.maximum(start + t_off, 0))
    live = lay.flat(t_off < q_lens[:, None])

    def mixer(lp, xn, i, sin, cos, pages):
        return mla.absorbed(cfg, lp, xn, sin, cos, pages, lay, block_tables,
                            seq_lens, q_lens, i)

    logits, pages, counts = _forward(cfg, params, lay.flat(tokens), pos, live,
                                     mixer, cache["latent"])
    out = (logits if lay.compact else lay.rows(logits)), {"latent": pages}
    return out + (counts,) if device_counts else out


# what serving.LLMEngine asks a configuration for (``cfg.serving``)
SERVING = types.SimpleNamespace(
    forward_paged=forward_paged, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    prepare_params=lambda cfg, params: params,   # no weight is converted
    step_counts=step_counts, device_counts=DEVICE_COUNTS,
    recurrent_state=False)
