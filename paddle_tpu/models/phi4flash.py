"""Phi-4-mini-flash (the public ``Phi4FlashForCausalLM``, "SambaY"): a decoder
whose first half interleaves Mamba-1 mixers with sliding-window attention
and whose second half reads, in every layer, what ONE full-attention layer
and the last Mamba layer left behind.

With L layers (L % 4 == 0), D the hidden size, E = expand x D, N the state
size, K the convolution width, r the dt rank, F the MLP width, ``nh`` query
heads and ``nkv`` K/V heads of d, W the window, LN a LayerNorm with weight
and bias, every layer i on ``u [T, D]`` is::

    u <- u + Mixer_i(LN1_i(u));  u <- u + MLP_i(LN2_i(u))
    MLP(x) = (y * silu(g)) W_2,  [g, y] = x W_1      (W_1 [D, 2F], no bias)

then a final LayerNorm and the tied head ``h embed^T``.  No positional term
anywhere.  The mixers, with h = LN1_i(u):

* **i even, i <= L/2: Mamba-1** (``models/jamba.py``'s recurrence without
  the RMSNorms on dt, B, C)::

      [x, z] = h W_in
      x_t   <- silu(b_conv + sum_k w_conv[k] * x_{t-K+1+k})
      [dt, B, C] = x W_x;   Delta = softplus(dt W_dt + b_dt)
      S_t   = exp(Delta_t (x) A) * S_{t-1} + (Delta_t * x_t) (x) B_t
      y_t   = S_t C_t + D_skip * x_t                    A = -exp(A_log)
      out   = (y * silu(z)) W_out

  At i = L/2 the scan's output ``m := y`` (``[T, E]``, before the gate) is
  kept for the layers after it.
* **i odd, i < L/2: differential attention with window W**; **i = L/2 + 1:
  the same without a window**, whose K and V the cross layers read.
  ``[q, k, v] = h W_qkv + b`` (nh + nkv + nkv heads of d).  Heads pair up:
  ``q1_j, q2_j`` are query heads ``2j, 2j+1`` (j < nh/2); ``k1_g, k2_g, v1_g,
  v2_g`` are K and V heads ``2g, 2g+1`` (g < nkv/2); pair j reads pair
  ``g(j) = j // (nh / nkv)``.  With ``A(q, k) = softmax(q k^T / sqrt(d) +
  mask)``::

      a1_j = A(q1_j, k1_g) [v1_g | v2_g]        a2_j = A(q2_j, k2_g) [v1_g | v2_g]
      lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(i)
      lambda_init(i) = 0.8 - 0.6 exp(-0.3 i)
      o_j  = (1 - lambda_init(i)) RMSNorm_2d(a1_j - lambda a2_j)
      out  = [o_0 | ... | o_{nh/2-1}] W_o + b_o

  Position p sees the keys ``p - W < j <= p`` (W keys with itself) in a
  window layer and ``j <= p`` in layer L/2 + 1.
* **i even, i >= L/2 + 2: gated memory unit**: ``out = (m * silu(h W_1))
  W_2`` with ``W_1 [D, E]``, ``W_2 [E, D]`` and m of the SAME token.
* **i odd, i >= L/2 + 3: cross layer**: ``q = h W_q + b_q`` only; K and V
  are layer L/2 + 1's; no window; this layer's own ``lambda`` vectors and
  RMSNorm; ``W_o + b_o``.

Parameters: ``embed [V, D]``, ``norm_f_w / norm_f_b [D]``, and four stacks,
each leaf stacked over its layers: ``mamba`` (L/4 + 1), ``attn`` (the L/4
window layers, then the full one), ``gmu`` and ``cross`` (L/4 - 1 each);
every stack holds its layers' ``ln1_w ln1_b ln2_w ln2_b w1 w2`` too.  What
has a state axis keeps it second to last (``A_log [M, N, E]``).

**Serving** (``SERVING``, the protocol ``serving.LLMEngine`` asks a
configuration for).  Three kinds of state live in one cache dict:

* ``k_pages / v_pages [1, nkv/2, P, page, 2d]``: the full layer's K and V of
  every token, in the engine's pages through the engine's block table, the
  only pages the ``PagedKVCache`` accounts.  A pair-head is ``[k1_g | k2_g]``
  (``[v1_g | v2_g]``): the plain reshape of ``nkv`` heads of d.  The full
  layer AND every cross layer read this one pool;
* ``kw_pages / vw_pages [L/4, nkv/2, 1 + R x Wp, page, 2d]``: for the window
  layers a RING of ``Wp = ceil(W / page) + 2`` pages a slot (6 at W 512 and
  page 128: what ``ceil((W + chunk - 1) / page) + 1`` gives at chunk 16),
  logical page b of slot r at pool page ``1 + r x Wp + b % Wp``, through a
  table built inside the program from ``arange`` (nothing is uploaded);
  pool page 0 is the null page that ``paged_kv_write`` parks its idle tiles
  on, as the allocator's page 0 is in the engine's pool;
* ``conv [M, K-1, R, E]`` and ``ssm [M, N, R, E]`` float32: the Mamba layers'
  recurrent state of every slot, as in ``models/jamba.py``.

Why a ring of ``Wp`` pages is enough.  A step writes row r's chunk, positions
``p0 .. p0 + q - 1`` with ``q <= page``, and then its queries read the keys
``p0 - W + 1 .. p0 + q - 1``: at most ``W + page - 1`` positions in a row,
which touch at most ``ceil(W / page) + 2 = Wp`` logical pages in a row, and
``Wp`` pages in a row sit on ``Wp`` different ring pages.  So the page a
chunk overwrites when it enters logical page b (the ring page that held
page ``b - Wp``) is out of every live query's window: at W 512, page 128 the
chunk's first query, at ``p0 >= 128 b - 15``, needs keys ``> p0 - 512 >= 128
(b - 5) + 113``, and the page overwritten ends at ``128 (b - 5) - 1``.
The windowed kernel never reads before the first page a row's queries see
(``pallas_ops._rpa_walk``), and stale rows of the pages it does read are
masked as the future or as out of the window.

Replay from token 0 (admission into a used slot, preemption, a rebuilt
engine) rewrites rings and state as it rewrites pages: see
``forward_paged``.  A prefix-cache hit would skip tokens the rings and the
state never saw, a rejected draft would have advanced them:
``recurrent_state`` makes the engine refuse both.
"""
from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .jamba import _layer_at, _mixer_core
from .step_layout import StepLayout

__all__ = ["Phi4FlashConfig", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "SERVING"]


@dataclasses.dataclass
class Phi4FlashConfig:
    """The first ten fields are keys of the public ``config.json``; the
    four ``mamba_*`` sizes are the family's, which that file leaves to its
    class's defaults.  The defaults are Phi-4-mini-flash-reasoning."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L, nh, nkv = (self.num_hidden_layers, self.num_attention_heads,
                      self.num_key_value_heads)
        if self.mb_per_layer != 2 or L % 4 or L < 8:
            raise ValueError(
                "the layer rule is written for mb_per_layer 2 and a depth "
                f"that is a multiple of 4, at least 8; got {self}")
        if nh % 2 or nkv % 2 or nh % nkv or self.hidden_size % nh:
            raise ValueError(f"heads pair up: {nh} query heads on {nkv}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def num_window_layers(self) -> int:
        """Layers 1, 3, .. L/2 - 1; the (Mamba, window) pairs of the first
        half, and with layer L/2 + 1 the layers that hold K/V."""
        return self.num_hidden_layers // 4

    @property
    def num_mamba_layers(self) -> int:
        """Layers 0, 2, .. L/2."""
        return self.num_hidden_layers // 4 + 1

    @property
    def num_cross_layers(self) -> int:
        """Layers L/2 + 3, .. L - 1; as many gated memory units before."""
        return self.num_hidden_layers // 4 - 1

    def layer_kinds(self) -> list:
        """The kind of every layer in order."""
        half = self.num_hidden_layers // 2
        return [("mamba" if i <= half else "gmu") if i % 2 == 0 else
                ("window" if i < half else "global" if i == half + 1
                 else "cross") for i in range(self.num_hidden_layers)]

    def ring_pages(self, page_size: int) -> int:
        """``Wp``: the pages of one slot's ring in a window layer."""
        return -(-self.sliding_window // page_size) + 2

    @property
    def serving(self):
        return SERVING


PRESETS: Dict[str, Dict[str, Any]] = {
    "phi4-mini-flash": {},
    # eight layers that keep all five kinds: Mamba 0 2 4, window 1 3, the
    # full layer 5, one gated memory unit 6, one cross layer 7; a window
    # shorter than the CPU tests' sequences and no multiple of their pages
    "phi4flash-debug": dict(vocab_size=256, hidden_size=64,
                            intermediate_size=128, num_hidden_layers=8,
                            num_attention_heads=8, num_key_value_heads=4,
                            sliding_window=20, mamba_dt_rank=4,
                            max_position_embeddings=512),
}


def preset(name: str, **overrides) -> Phi4FlashConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown phi4flash preset {name!r}; available: "
                       f"{sorted(PRESETS)}")
    return Phi4FlashConfig(**dict(PRESETS[name], **overrides))


def config_from_fields(fields: dict) -> Phi4FlashConfig:
    """A ``Phi4FlashConfig`` from a ``config.json``-shaped dict: every key
    that is a field, ``dtype`` by name; other keys are not this model's."""
    names = {f.name for f in dataclasses.fields(Phi4FlashConfig)}
    kw = {k: v for k, v in fields.items() if k in names}
    kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16")).type
    return Phi4FlashConfig(**kw)


def _lambdas(lp, i):
    """``(lambda, lambda_init)`` of attention layer i (a number or a traced
    one) with the vectors ``lq*, lk*`` of ``lp``: ``lambda_init = 0.8 - 0.6
    exp(-0.3 i)``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``."""
    f32 = jnp.float32
    init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(i, f32))
    return (jnp.exp(jnp.sum(lp["lq1"].astype(f32) * lp["lk1"].astype(f32)))
            - jnp.exp(jnp.sum(lp["lq2"].astype(f32) * lp["lk2"].astype(f32)))
            + init), init


def param_count(cfg: Phi4FlashConfig) -> int:
    D, F, E = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner
    N, K, r = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    d, Q = cfg.head_dim, cfg.num_attention_heads * cfg.head_dim
    KV = cfg.num_key_value_heads * d
    mlp_and_norms = 3 * D * F + 4 * D
    mamba = (2 * D * E + K * E + E + E * (r + 2 * N) + r * E + E + N * E + E
             + E * D)
    diff = 4 * d + 2 * d                    # lq1 lk1 lq2 lk2, the RMSNorm
    attn = D * (Q + 2 * KV) + Q + 2 * KV + Q * D + D + diff
    cross = D * Q + Q + Q * D + D + diff
    gmu = 2 * D * E
    return (cfg.num_mamba_layers * (mamba + mlp_and_norms)
            + (cfg.num_window_layers + 1) * (attn + mlp_and_norms)
            + cfg.num_cross_layers * (cross + gmu + 2 * mlp_and_norms)
            + cfg.vocab_size * D + 2 * D)


def init_params(cfg: Phi4FlashConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, Mamba's standard start for
    the recurrence (as ``jamba.init_params``), ``lq*, lk*`` normal(0, 0.1),
    norms 1 and biases 0."""
    D, F, E, V = (cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner,
                  cfg.vocab_size)
    N, K, r = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    d, Q = cfg.head_dim, cfg.num_attention_heads * cfg.head_dim
    KV = cfg.num_key_value_heads * d
    M, A, C = (cfg.num_mamba_layers, cfg.num_window_layers + 1,
               cfg.num_cross_layers)
    k = iter(jax.random.split(key, 40))

    def normal(shape, std=0.02):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * std).astype(cfg.dtype)

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    def zeros(*shape):
        return jnp.zeros(shape, cfg.dtype)

    def shared(n):
        return {"ln1_w": ones(n, D), "ln1_b": zeros(n, D),
                "ln2_w": ones(n, D), "ln2_b": zeros(n, D),
                "w1": normal((n, D, 2 * F)), "w2": normal((n, F, D))}

    def diff(n):
        return {"lq1": normal((n, d), 0.1), "lk1": normal((n, d), 0.1),
                "lq2": normal((n, d), 0.1), "lk2": normal((n, d), 0.1),
                "subln": ones(n, 2 * d), "wo": normal((n, Q, D)),
                "bo": zeros(n, D)}

    dt = jnp.exp(jax.random.uniform(next(k), (M, E), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    mamba = {
        "w_in": normal((M, D, 2 * E)),
        "conv_w": (jax.random.uniform(next(k), (M, K, E), jnp.float32, -1, 1)
                   / math.sqrt(K)).astype(cfg.dtype),
        "conv_b": zeros(M, E),
        "w_x": normal((M, E, r + 2 * N)),
        "w_dt": (jax.random.uniform(next(k), (M, r, E), jnp.float32, -1, 1)
                 / math.sqrt(r)).astype(cfg.dtype),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (M, N, E)).astype(cfg.dtype),
        "D_skip": ones(M, E), "w_out": normal((M, E, D)), **shared(M)}
    attn = {"wqkv": normal((A, D, Q + 2 * KV)), "bqkv": zeros(A, Q + 2 * KV),
            **diff(A), **shared(A)}
    cross = {"wq": normal((C, D, Q)), "bq": zeros(C, Q), **diff(C),
             **shared(C)}
    gmu = {"w_gate": normal((C, D, E)), "w_out": normal((C, E, D)),
           **shared(C)}
    return {"embed": normal((V, D)), "mamba": mamba, "attn": attn,
            "gmu": gmu, "cross": cross, "norm_f_w": ones(D),
            "norm_f_b": zeros(D)}


# ---------------------------------------------------------------------------
# the layers, on the flat tokens of a step
# ---------------------------------------------------------------------------

def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _mlp(cfg, lp, h):
    with jax.named_scope("mlp"):
        x = _layer_norm(h, lp["ln2_w"], lp["ln2_b"], cfg.layer_norm_eps)
        g, y = jnp.split(x @ lp["w1"], 2, axis=-1)
        return h + (y * jax.nn.silu(g)) @ lp["w2"]


def _mamba_layer(cfg, lp, h, state, l, q_lens, fresh, lay):
    """Mamba layer ``l`` of the stack on the flat tokens ``h [T, D]``, its
    state read from and written back into ``state["conv"] [M, K-1, R, E]`` and
    ``state["ssm"] [M, N, R, E]`` at ``l``; returns the layer's output, the
    scan's output ``m [T, E]`` before the gate, and the state.  Between
    ``w_in`` and ``w_out`` it is ``jamba._mixer_core``, flat throughout,
    under the same four names in a profile (``ssm_proj``, ``ssm_conv``,
    ``ssm_scan``, ``step_layout``); what is this model's own: the LayerNorm
    before ``w_in``, no norm on ``dt``, ``B`` and ``C``, and ``m`` handed to
    the gated memory units."""
    N, r, f32 = cfg.mamba_d_state, cfg.mamba_dt_rank, jnp.float32

    def dt_b_c(x):
        dt, Bm, Cm = jnp.split(x.astype(h.dtype) @ lp["w_x"], [r, r + N],
                               axis=-1)
        dt = jax.nn.softplus((dt @ lp["w_dt"]).astype(f32)
                             + lp["b_dt"].astype(f32))
        return dt, Bm.astype(f32), Cm.astype(f32)

    with jax.named_scope("mamba"):
        with jax.named_scope("ssm_proj"):
            xn = _layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.layer_norm_eps)
            x, z = jnp.split(xn @ lp["w_in"], 2, axis=-1)
        gated, m, conv, ssm = _mixer_core(
            lp, x, z, state["conv"], state["ssm"], l, q_lens, fresh, lay,
            dt_b_c)
        with jax.named_scope("ssm_proj"):
            out = gated @ lp["w_out"]
        h = h + out
    return _mlp(cfg, lp, h), m.astype(h.dtype), dict(state, conv=conv,
                                                     ssm=ssm)


def _padded_queries(cfg, q):
    """``q [R, Tc, nh x d]`` as the kernel's rows ``[R, nkv/2, Tc x rep, 2d]``,
    ``rep = 2 nh / nkv``: for a token and a K/V pair-head g the query heads
    ``rep g .. rep g + rep - 1`` in order, an even one (a ``q1``) as ``[q |
    0]`` against ``[k1 | k2]``, an odd one (a ``q2``) as ``[0 | q]``, scaled
    so that the kernel's ``1 / sqrt(2d)`` leaves ``q k^T / sqrt(d)``."""
    R, Tc, _ = q.shape
    d, nkvp = cfg.head_dim, cfg.num_key_value_heads // 2
    rep = cfg.num_attention_heads // nkvp
    q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
    q = q.reshape(R, Tc, nkvp, rep // 2, 2, d)
    zero = jnp.zeros_like(q[..., :1, :])
    q = jnp.concatenate(
        [jnp.concatenate([q[..., :1, :], zero], -2),       # [q1 ; 0]: left
         jnp.concatenate([zero, q[..., 1:, :]], -2)], -1)  # [0 ; q2]: right
    return q.reshape(R, Tc, nkvp, rep, 2 * d).transpose(
        0, 2, 1, 3, 4).reshape(R, nkvp, Tc * rep, 2 * d)


def _diff_attention(cfg, lp, h, i, kind, l, state, attend, lay):
    """The attention mixer of layer i (depth, for ``_lambdas``) of
    ``kind`` ``window``, ``global`` or ``cross``, layer ``l`` of its stack, on
    the flat tokens ``h [T, D]``.  ``attend(kind, l, q, k, v, state)`` mixes
    the kernel-shaped queries (``_padded_queries``) with ``k, v [R, Tc,
    nkv/2, 2d]`` (None for a cross layer) and what came before them, and
    returns rows ``[R, nkv/2, Tc x rep, 2d]``: ``a1`` for a ``q1`` row, ``a2``
    for a ``q2`` row.  What follows the kernel is per token and flat."""
    d, nh, nkv = cfg.head_dim, cfg.num_attention_heads, \
        cfg.num_key_value_heads
    R, Tc, T, f32 = lay.R, lay.Tc, lay.T, jnp.float32
    with jax.named_scope("attn"), jax.named_scope(f"attn_{kind}"):
        xn = _layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.layer_norm_eps)
        if kind == "cross":
            q, k, v = xn @ lp["wq"] + lp["bq"], None, None
        else:
            q, k, v = jnp.split(xn @ lp["wqkv"] + lp["bqkv"],
                                [nh * d, (nh + nkv) * d], axis=-1)
            k, v = (lay.rows(t).reshape(R, Tc, nkv // 2, 2 * d)
                    for t in (k, v))
        a, state = attend(kind, l, _padded_queries(cfg, lay.rows(q)), k, v,
                          state)
        # [R, nkv/2, Tc x rep, 2d] -> flat [T, nh/2 pairs, (a1, a2), 2d]
        a = a.reshape(R, nkv // 2, Tc, nh // nkv, 2, 2 * d).transpose(
            0, 2, 1, 3, 4, 5).reshape(R, Tc, nh * 2 * d)
        a = lay.flat(a).reshape(T, nh // 2, 2, 2 * d).astype(f32)
        lam, lam0 = _lambdas(lp, i)
        o = a[:, :, 0] - lam * a[:, :, 1]
        o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.layer_norm_eps) * lp["subln"].astype(f32)
        o = ((1.0 - lam0) * o).astype(h.dtype).reshape(T, nh * d)
        h = h + o @ lp["wo"] + lp["bo"]
    return _mlp(cfg, lp, h), state


def _gmu_layer(cfg, lp, h, m):
    with jax.named_scope("gmu"):
        xn = _layer_norm(h, lp["ln1_w"], lp["ln1_b"], cfg.layer_norm_eps)
        gate = jax.nn.silu((xn @ lp["w_gate"]).astype(jnp.float32))
        h = h + (m.astype(jnp.float32) * gate).astype(h.dtype) @ lp["w_out"]
    return _mlp(cfg, lp, h)


def _forward(cfg, params, tokens, state, q_lens, fresh, attend, lay):
    """Embedding, the layers in order, final norm, tied head, on the flat
    tokens ``[T, ...]`` of the step ``lay``; returns the logits ``[T, V]`` and
    the state.  ``state`` is a dict that holds ``conv`` and ``ssm`` and
    whatever ``attend`` keeps (``_diff_attention``).  Three runs of layers:
    the (Mamba, window) pairs as one scan over both stacks, the last Mamba
    layer and the full layer, the (gated memory unit, cross) pairs as one
    scan, so the program holds six layer bodies whatever the depth."""
    n_w, n_c = cfg.num_window_layers, cfg.num_cross_layers
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], lay.flat(tokens), axis=0)
    with jax.named_scope("layers"):
        def pair(carry, l):
            h, state = carry
            h, _, state = _mamba_layer(cfg, _layer_at(params["mamba"], l), h,
                                       state, l, q_lens, fresh, lay)
            h, state = _diff_attention(cfg, _layer_at(params["attn"], l), h,
                                       2 * l + 1, "window", l, state, attend,
                                       lay)
            return (h, state), None

        (h, state), _ = lax.scan(pair, (h, state),
                                 jnp.arange(n_w, dtype=jnp.int32))
        at = lambda stack, l: jax.tree_util.tree_map(   # noqa: E731
            lambda w: w[l], stack)
        h, m, state = _mamba_layer(cfg, at(params["mamba"], n_w), h, state,
                                   n_w, q_lens, fresh, lay)
        h, state = _diff_attention(cfg, at(params["attn"], n_w), h,
                                   2 * n_w + 1, "global", 0, state, attend,
                                   lay)

        def tail(carry, l):
            h, state = carry
            h = _gmu_layer(cfg, _layer_at(params["gmu"], l), h, m)
            h, state = _diff_attention(cfg, _layer_at(params["cross"], l), h,
                                       2 * (n_w + l) + 3, "cross", l, state,
                                       attend, lay)
            return (h, state), None

        (h, state), _ = lax.scan(tail, (h, state),
                                 jnp.arange(n_c, dtype=jnp.int32))
    with jax.named_scope("lm_head"):
        h = _layer_norm(h, params["norm_f_w"], params["norm_f_b"],
                        cfg.layer_norm_eps)
        logits = jnp.einsum("td,vd->tv", h, params["embed"],
                            preferred_element_type=jnp.float32)
    return logits, state


def _fresh_state(cfg, rows: int) -> dict:
    E, M = cfg.mamba_inner, cfg.num_mamba_layers
    return {"conv": jnp.zeros((M, cfg.mamba_d_conv - 1, rows, E), cfg.dtype),
            "ssm": jnp.zeros((M, cfg.mamba_d_state, rows, E), jnp.float32)}


def forward_pure(cfg: Phi4FlashConfig, params, input_ids):
    """Logits ``[B, S, V]`` float32 of whole sequences ``[B, S]``: no cache,
    plain masked attention on the same zero-padded query rows that the
    paged kernel is given.  The recurrence is unrolled over S
    (``pallas_ops._ssm_scan_jnp``), so this is for sequences of test
    length."""
    B, S = input_ids.shape
    d, nkvp = cfg.head_dim, cfg.num_key_value_heads // 2
    rep = cfg.num_attention_heads // nkvp
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]
    near = causal & (pos[None, :] > pos[:, None] - cfg.sliding_window)

    def attend(kind, l, q, k, v, state):
        if kind == "cross":
            k, v = state["k"], state["v"]
        elif kind == "global":
            state = dict(state, k=k, v=v)
        q = q.reshape(B, nkvp, S, rep, 2 * d)
        s = jnp.einsum("bgqrd,bkgd->bgrqk", q, k,
                       preferred_element_type=jnp.float32)
        s = jnp.where(near if kind == "window" else causal,
                      s / math.sqrt(2 * d), -jnp.inf)
        a = jnp.einsum("bgrqk,bkgd->bgqrd",
                       jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
        return a.reshape(B, nkvp, S * rep, 2 * d), state

    q_lens = jnp.full((B,), S, jnp.int32)
    lay = StepLayout(q_lens, S)
    blank = jnp.zeros((B, S, nkvp, 2 * d), cfg.dtype)
    state = dict(_fresh_state(cfg, B), k=blank, v=blank)
    return lay.rows(_forward(cfg, params, input_ids, state, q_lens,
                             jnp.ones((B,), bool), attend, lay)[0])


# ---------------------------------------------------------------------------
# serving: the engine's protocol
# ---------------------------------------------------------------------------

def init_cache(cfg: Phi4FlashConfig, slots: int, num_pages: int,
               page_size: int, kv_dtype):
    """The cache of an engine with ``slots`` rows: the full layer's zeroed
    page pool, the window layers' rings (and their null page), zero
    recurrent state of every slot."""
    if jnp.dtype(kv_dtype).itemsize < 2:
        raise ValueError(
            f"kv_dtype {jnp.dtype(kv_dtype)} pages need the per-page scale "
            "pools that only models/llama.py's step writes")
    nkvp, width = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
    pool = (1, nkvp, num_pages, page_size, width)
    ring = (cfg.num_window_layers, nkvp,
            1 + slots * cfg.ring_pages(page_size), page_size, width)
    return {"k_pages": jnp.zeros(pool, kv_dtype),
            "v_pages": jnp.zeros(pool, kv_dtype),
            "kw_pages": jnp.zeros(ring, kv_dtype),
            "vw_pages": jnp.zeros(ring, kv_dtype), **_fresh_state(cfg, slots)}


def cache_bytes(cfg: Phi4FlashConfig, kv_dtype_bytes: int = 2,
                page_size: int = 128) -> dict:
    """What the cache costs: K/V bytes a token in the ONE layer that keeps
    every token; a slot's rings in the window layers (``Wp`` pages of
    ``page_size`` tokens each) and its recurrent state, whatever the length
    of the request in it; and, whatever the engine's size, the rings' null
    page (``fixed``)."""
    E = cfg.mamba_inner
    token = 2 * cfg.num_key_value_heads * cfg.head_dim * kv_dtype_bytes
    ring_page = cfg.num_window_layers * token * page_size
    state = cfg.num_mamba_layers * E * (
        cfg.mamba_d_state * 4
        + (cfg.mamba_d_conv - 1) * jnp.dtype(cfg.dtype).itemsize)
    return {"per_token": token, "scales_per_page": 0,
            "per_slot": cfg.ring_pages(page_size) * ring_page + state,
            "fixed": ring_page}


def step_counts(cfg: Phi4FlashConfig, seq_lens, q_lens) -> dict:
    """What one step's window and Mamba layers read, a layer, from the
    host's arrays ``seq_lens, q_lens [R]``: ``window_kv_tokens``, over the
    fed rows the keys some query of the row sees (``min(seq_len, W + q_len -
    1)``), ``window_qk_pairs``, over the fed tokens the keys each sees
    (``min(p + 1, W)`` at position p), and ``scan_positions``, as
    ``jamba.step_counts`` counts them.  The engine puts them on its
    ``serve/engine_step`` span beside ``kv_tokens`` and ``qk_pairs``, which
    count the full layer."""
    from ..ops.pallas_ops import scan_positions
    W = cfg.sliding_window
    seq, q = np.asarray(seq_lens, np.int64), np.asarray(q_lens, np.int64)
    t = np.arange(int(q.max(initial=0)))[None, :]
    seen = np.minimum((seq - q)[:, None] + t + 1, W)
    return {"window_kv_tokens": int(np.minimum(seq, W + q - 1)[q > 0].sum()),
            "window_qk_pairs": int(seen[t < q[:, None]].sum()),
            "scan_positions": scan_positions(q_lens)}


def forward_paged(cfg: Phi4FlashConfig, params, tokens, cache, block_tables,
                  seq_lens, q_lens, step_tokens=None):
    """The engine's step: ragged mixed prefill and decode rows ``tokens [R,
    Tc]`` (row r feeds ``tokens[r, :q_lens[r]]`` and then holds ``seq_lens[r]``
    tokens) over ``cache`` (``init_cache``).  Returns ``(logits [R, Tc, V]
    float32, cache)``, or with ``step_tokens = T`` the logits flat ``[T, V]``
    (``StepLayout``), as ``jamba.forward_paged``.

    The full layer writes its K/V into the engine's pages through
    ``block_tables`` and the cross layers read them there
    (``ragged_paged_attention(..., layer=0)`` on the one pool); a window
    layer writes and reads its slot's ring through a table made here, with
    ``window=W``.  Rings and recurrent state are per ROW (engine slot): a
    row whose chunk starts at position 0 (``seq_lens[r] == q_lens[r] > 0``)
    has its state zeroed inside this step, and its ring needs no reset,
    since a request that starts at 0 rewrites every ring page before it
    reads it (a query never sees past its own position).  That is all
    admission into a used slot, preemption-replay and a rebuilt engine
    need.  Positions ``t >= q_lens[r]`` write no K/V and advance no state;
    a row with ``q_lens[r] == 0`` comes back as it was."""
    from ..ops.pallas_ops import paged_kv_write, ragged_paged_attention
    R, Tc = tokens.shape
    page = cache["k_pages"].shape[3]
    Wp = cfg.ring_pages(page)
    if Tc > page:
        raise ValueError(
            f"a chunk of {Tc} tokens is longer than a page of {page}: a "
            f"ring of {Wp} pages a slot holds a window and a chunk of at "
            "most a page")
    rep = 2 * cfg.num_attention_heads // cfg.num_key_value_heads
    ring_table = (1 + jnp.arange(R, dtype=jnp.int32)[:, None] * Wp
                  + jnp.arange(block_tables.shape[1],
                               dtype=jnp.int32)[None, :] % Wp)

    def attend(kind, l, q, k, v, state):
        names = ("kw_pages", "vw_pages") if kind == "window" else \
            ("k_pages", "v_pages")
        table = ring_table if kind == "window" else block_tables
        pools = [state[n] for n in names]
        if kind != "cross":
            with jax.named_scope("kv_write"):
                pools = paged_kv_write(*pools, k, v, table, seq_lens, q_lens,
                                       layer=l)
            state = dict(state, **dict(zip(names, pools)))
        out = ragged_paged_attention(
            q, *pools, table, seq_lens, q_lens, rep=rep,
            layer=l if kind == "window" else 0,
            window=cfg.sliding_window if kind == "window" else None)
        return out, state

    fresh = (q_lens > 0) & (seq_lens == q_lens)
    lay = StepLayout(q_lens, Tc, step_tokens)
    logits, cache = _forward(cfg, params, tokens, dict(cache), q_lens, fresh,
                             attend, lay)
    return (logits if lay.compact else lay.rows(logits)), cache


# what serving.LLMEngine asks a configuration for (``cfg.serving``)
SERVING = types.SimpleNamespace(
    forward_paged=forward_paged, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    prepare_params=lambda cfg, params: params,   # no weight is converted
    step_counts=step_counts, recurrent_state=True)
