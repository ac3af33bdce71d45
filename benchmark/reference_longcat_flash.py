"""The plain reference of the LongCat-Flash configurations (``"model":
"longcat_flash"``), and the comparison that decides ``correct`` for what an
engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, no sort and no grouped product;
attention in its MATERIALISED form (every head's K and V expanded from the
latent, ``[S, S]`` masked scores), which the program's engine never computes
(it attends on the latent itself, absorbed); the experts as a loop over the
experts, each applied to every token and kept where the token chose it.  It
is the benchmark's own copy, written from the published equations: it shares
no code with ``paddle_tpu/models``, only the layout of the parameter tree it
is handed (``embed, lm_head [V, D]``, ``norm_f``, ``layers`` with ``attn``
(``ln wqa q_norm wqb wkva kv_norm wkvb wo``, each ``[L, 2, ..]``), ``mlp``
(``ln w_gate w_up w_down [L, 2, ..]``), ``router [L, D, E + Z]``,
``router_bias [L, E + Z]`` and ``experts`` (``w_gate w_up [L, Eh, D, F]``,
``w_down [L, Eh, F, D]``)).

The model (the public ``LongcatFlashForCausalLM``).  A layer on ``u``, with
``N`` = RMSNorm::

    u <- u + MLA_0(N_a0(u));  h = N_p0(u);  s = MoE(h);  u <- u + FFN_0(h)
    u <- u + MLA_1(N_a1(u));  u <- u + FFN_1(N_p1(u)) + s

then a final RMSNorm and the untied head.  MLA on a normed token x at
position p: ``q = N_q(x W_qa) W_qb sqrt(D / q_lora_rank)`` as heads of ``[q_nope
| q_pe]``; ``[c_raw | k_pe] = x W_kva``, ``c = N_kv(c_raw) sqrt(D /
kv_lora_rank)``; ``q_pe`` and the one ``k_pe`` rotated by p (plain rotary of
``rope_theta``; pair j of the rotary lanes is ``(j, j + dr/2)``); ``[k_nope_i |
v_i] = c W_kvb``; causal ``softmax((q_nope_i . k_nope_i + q_pe_i . k_pe) (dn +
dr)^-0.5) v_i``; ``W_o``.  MoE on h: ``p = softmax(h W_r)`` over the ``E``
routed and ``Z`` zero-compute experts, the ``moe_topk`` largest of ``p + b``
(ties to the lower index), weights ``p routed_scaling_factor`` as they are;
a routed expert adds ``w expert(h)`` (a SwiGLU), a zero-compute one ``w h``.

**A chip's share.**  Given ``experts_held`` (ids among the ``published``
group's ``n_routed_experts``; the key ``n_routed_experts`` then counts the
experts held) the reference leaves out what the absent experts would add, as
the program does; the zero-compute experts are everyone's.  A sliced
vocabulary is a smaller vocabulary.

Everything is computed in pieces, so that 2,048 tokens at the published
widths fit beside a live engine: a sublayer at a time (one jitted piece per
attention, dense FFN and expert layer, the layer a traced index into the
whole stacks, so nothing is sliced out on the host), the attention a group
of heads at a time, a dense FFN a block of its width at a time, the experts
one at a time, the head a block of the vocabulary at a time with the logits
kept on the host.  Weights are cast to float32 where they are used.

``route_flips`` counts, on the reference's own activations, the tokens of an
expert layer whose chosen set differs when the router's product is taken as
the program takes it (inputs and weights in the weights' dtype, float32
accumulation): how often rounding alone changes the routing; logged, not a
limit.  ``replay_logits`` is ``reference_deepseek_v2.py``'s (the engine's own
``forward_paged`` on its live cache; nothing of the model).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference_deepseek_v2 import _rel, replay_logits

FIELDS = ("hidden_size", "num_layers", "num_attention_heads", "kv_lora_rank",
          "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "mla_scale_q_lora", "mla_scale_kv_lora", "routed_scaling_factor",
          "n_routed_experts", "zero_expert_num", "moe_topk", "rms_norm_eps",
          "rope_theta")
PAD_TO = 1024           # rows are padded to a multiple: one program a size
VOCAB_BLOCK = 16384     # rows of the head a call takes
HEAD_GROUP = 8          # heads whose [S, S] scores stand at once
FFN_BLOCKS = 4          # pieces of a dense FFN's width


def _f32(a):
    return a.astype(jnp.float32)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def _at(w, l, j=None):
    """Layer ``l`` (traced), and sublayer ``j``, of a stacked leaf."""
    w = lax.dynamic_index_in_dim(w, l, 0, keepdims=False)
    return w if j is None else lax.dynamic_index_in_dim(w, j, 0, False)


def scored_experts(fields: dict) -> int:
    """The routed experts the router scores: the published count where the
    file states a chip's share, else ``n_routed_experts``."""
    return fields.get("published", {}).get("n_routed_experts",
                                           fields["n_routed_experts"])


def _rotate(x, angle):
    """``x [S, ..., dr]`` turned by ``angle [S, dr / 2]``: pair j is lanes
    ``(j, j + dr / 2)``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = jnp.sin(angle).reshape(shape), jnp.cos(angle).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(f, attn, x, l, j):
    """``x + MLA_j(N_aj(x))`` of layer ``l`` on ``x [S, D]``, materialised,
    ``HEAD_GROUP`` heads at a time."""
    nh, r, rq = f["num_attention_heads"], f["kv_lora_rank"], f["q_lora_rank"]
    dn, dr, dv = f["qk_nope_head_dim"], f["qk_rope_head_dim"], \
        f["v_head_dim"]
    D, eps = f["hidden_size"], f["rms_norm_eps"]
    S = x.shape[0]
    lp = {n: _at(w, l, j) for n, w in attn.items()}
    inv_freq = jnp.asarray([f["rope_theta"] ** (-2.0 * i / dr)
                            for i in range(dr // 2)], jnp.float32)
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    h = _rms_norm(x, lp["ln"], eps)
    q_lat = _rms_norm(h @ _f32(lp["wqa"]), lp["q_norm"], eps)
    if f["mla_scale_q_lora"]:
        q_lat = q_lat * math.sqrt(D / rq)
    kv = h @ _f32(lp["wkva"])
    c = _rms_norm(kv[:, :r], lp["kv_norm"], eps)
    if f["mla_scale_kv_lora"]:
        c = c * math.sqrt(D / r)
    k_pe = _rotate(kv[:, r:], angle)                             # [S, dr]
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    scale = (dn + dr) ** -0.5
    g = min(HEAD_GROUP, nh)
    wqb = lp["wqb"].reshape(rq, nh // g, g, dn + dr)
    wkvb = lp["wkvb"].reshape(r, nh // g, g, dn + dv)
    wo = lp["wo"].reshape(nh // g, g * dv, D)

    def heads(out, at):
        wq, wkv, w_o = at
        q = jnp.einsum("sc,cgd->sgd", q_lat, _f32(wq))           # [S, g, ..]
        up = jnp.einsum("sc,cgd->sgd", c, _f32(wkv))
        q_pe = _rotate(q[..., dn:], angle)
        s = (jnp.einsum("qgd,kgd->gqk", q[..., :dn], up[..., :dn])
             + jnp.einsum("qgd,kd->gqk", q_pe, k_pe)) * scale
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gqk,kgv->qgv", p, up[..., dn:])
        return out + o.reshape(S, g * dv) @ _f32(w_o), None

    out, _ = lax.scan(heads, jnp.zeros_like(x),
                      (jnp.moveaxis(wqb, 1, 0), jnp.moveaxis(wkvb, 1, 0), wo))
    return x + out


def _normed(f, mlp, x, l, j):
    return _rms_norm(x, _at(mlp["ln"], l, j), f["rms_norm_eps"])


def _ffn(mlp, h, l, j):
    """``FFN_j(h)`` of layer ``l``: a SwiGLU, ``FFN_BLOCKS`` pieces of its
    width one after the other."""
    w_gate, w_up, w_down = (_at(mlp[n], l, j)
                            for n in ("w_gate", "w_up", "w_down"))
    width = w_gate.shape[1]
    n = FFN_BLOCKS if width % FFN_BLOCKS == 0 else 1
    step = width // n

    def piece(i, y):
        gate = _f32(lax.dynamic_slice_in_dim(w_gate, i * step, step, 1))
        up = _f32(lax.dynamic_slice_in_dim(w_up, i * step, step, 1))
        down = _f32(lax.dynamic_slice_in_dim(w_down, i * step, step, 0))
        return y + (jax.nn.silu(h @ gate) * (h @ up)) @ down

    return lax.fori_loop(0, n, piece, jnp.zeros_like(h))


def _choose(f, logits, bias):
    """``(p [S, E + Z], chosen [S, K])``: the softmax over every expert and
    the K largest of ``p + bias``, equal ones to the lower index."""
    p = jax.nn.softmax(logits, axis=-1)
    return p, jnp.argsort(-(p + _f32(bias)), axis=-1,
                          stable=True)[:, :f["moe_topk"]]


def _moe(f, layers, h, l, length):
    """``MoE(h)`` of layer ``l``, and the tokens before ``length`` whose
    chosen set changes under the program's router product."""
    E = scored_experts(f)
    router, bias = _at(layers["router"], l), _at(layers["router_bias"], l)
    p, chosen = _choose(f, h @ _f32(router), bias)
    w = jnp.take_along_axis(p, chosen, axis=-1) * f["routed_scaling_factor"]
    _, theirs = _choose(f, jnp.dot(h.astype(router.dtype), router,
                                   preferred_element_type=jnp.float32), bias)
    flipped = jnp.any(jnp.sort(chosen, -1) != jnp.sort(theirs, -1), -1)
    # zero-compute experts: the identity, on every chip
    y = jnp.sum(jnp.where(chosen >= E, w, 0.0), axis=-1)[:, None] * h
    held = f.get("experts_held")
    ids = jnp.asarray(list(range(E)) if held is None else list(held),
                      jnp.int32)
    stacks = tuple(layers["experts"][n]
                   for n in ("w_gate", "w_up", "w_down"))

    def expert(y, at):
        e, eid = at               # its place in the stacks, its id
        w_gate, w_up, w_down = (_f32(_at(s, l, e)) for s in stacks)
        weight = jnp.sum(jnp.where(chosen == eid, w, 0.0), axis=-1)   # [S]
        out = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return y + weight[:, None] * out, None

    y, _ = lax.scan(expert, y, (jnp.arange(ids.shape[0]), ids))
    return y, jnp.sum(flipped & (jnp.arange(h.shape[0]) < length))


def _frozen(fields: dict):
    """The keys of ``fields`` the reference reads, hashable."""
    held = fields.get("experts_held")
    return (tuple((k, fields[k]) for k in FIELDS), scored_experts(fields),
            None if held is None else tuple(held))


def _thawed(frozen) -> dict:
    keys, scored, held = frozen
    return dict(keys, published={"n_routed_experts": scored},
                experts_held=held)


@functools.partial(jax.jit, static_argnums=0)
def _attention_jit(frozen, attn, x, l, j):
    return _attention(_thawed(frozen), attn, x, l, j)


@functools.partial(jax.jit, static_argnums=0)
def _first_half_jit(frozen, layers, x, l, length):
    """``h = N_p0(x)``; returns ``x + FFN_0(h)``, ``MoE(h)`` and its flips."""
    f = _thawed(frozen)
    h = _normed(f, layers["mlp"], x, l, 0)
    s, flips = _moe(f, layers, h, l, length)
    return x + _ffn(layers["mlp"], h, l, 0), s, flips


@functools.partial(jax.jit, static_argnums=0)
def _second_half_jit(frozen, layers, x, s, l):
    f = _thawed(frozen)
    return x + _ffn(layers["mlp"], _normed(f, layers["mlp"], x, l, 1), l,
                    1) + s


@jax.jit
def _embed_jit(embed, ids):
    return _f32(jnp.take(embed, ids, axis=0))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head_jit(eps, x, norm_f, head, start, rows):
    block = lax.dynamic_slice_in_dim(head, start, rows, 0)
    return _rms_norm(x, norm_f, eps) @ _f32(block).T


def _run(fields: dict, params, row: list):
    """Reference logits ``[len, V]`` (numpy) of one token row and its
    ``route_flips``, the row padded on the right to a multiple of ``PAD_TO``:
    a causal model cannot see the padding from the left."""
    ids = np.zeros((-(-len(row) // PAD_TO) * PAD_TO,), np.int32)
    ids[:len(row)] = row
    frozen, layers = _frozen(fields), params["layers"]
    V = params["lm_head"].shape[0]
    flips = 0
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params["embed"], jnp.asarray(ids))
        for l in range(fields["num_layers"]):
            l = jnp.int32(l)
            x = _attention_jit(frozen, layers["attn"], x, l, jnp.int32(0))
            x, s, n = _first_half_jit(frozen, layers, x, l,
                                      jnp.int32(len(row)))
            x = _attention_jit(frozen, layers["attn"], x, l, jnp.int32(1))
            x = _second_half_jit(frozen, layers, x, s, l)
            flips += int(n)
        out = np.concatenate([
            np.asarray(_head_jit(fields["rms_norm_eps"], x, params["norm_f"],
                                 params["lm_head"], jnp.int32(v0),
                                 min(VOCAB_BLOCK, V - v0)))[:len(row)]
            for v0 in range(0, V, VOCAB_BLOCK)], axis=1)
    return out, flips


def forward(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 (numpy) of the token ids ``[B, S]``
    under the configuration ``fields`` (a config file's keys)."""
    return np.stack([_run(fields, params, list(map(int, row)))[0]
                     for row in np.asarray(ids)])


def expert_layer(fields: dict, params, h, l: int):
    """``MoE(h)`` of layer ``l`` on ``h [S, D]`` float32, for the tests that
    hold the program's expert layer and a chip's share to it."""
    with jax.default_matmul_precision("highest"):
        y, _ = _moe(_thawed(_frozen(fields)), params["layers"],
                    jnp.asarray(h, jnp.float32), jnp.int32(l), h.shape[0])
    return np.asarray(y)


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time."""
    return [_run(fields, params, row)[0] for row in rows]


# -- what the engine served, against the reference --------------------------

def served_checks(fields: dict, eng, params, served: list) -> dict:
    """What the engine served against the reference on the weights
    ``params`` it was built from; ``served`` is ``[(prompt, output), ...]``.
    ``token_gap_sigma``: over every served token, how far the reference's
    logit of that token trails the reference's best, in standard deviations
    of that row of logits, teacher forced on the engine's own stream (the
    worst one).  ``logits_rel_err``: ``||served - ref|| / ||ref||`` over the
    logits of the request with the most tokens, replayed on the live engine:
    the absorbed attention on the paged latents, the sorted grouped experts
    and bfloat16 everywhere against materialised heads, a loop over experts
    and float32.  ``route_flip_share``: the share of (token, layer) pairs of
    the served rows whose chosen experts the program's router product
    changes on the reference's own activations (``route_flips``); logged, no
    limit."""
    ref, flips, routed = [], 0, 0
    for prompt, out in served:
        rows, n = _run(fields, params, prompt + out[:-1])
        ref.append(rows)
        flips += n
        routed += len(rows) * fields["num_layers"]
    worst, exact, total = 0.0, 0, 0
    for (prompt, out), rows in zip(served, ref):
        rows = rows[len(prompt) - 1:]                 # one per token served
        gap = (rows.max(-1) - rows[np.arange(len(out)), out]) / rows.std(-1)
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += len(out)
    i = max(range(len(served)), key=lambda j: sum(map(len, served[j])))
    prompt, out = served[i]
    got = replay_logits(eng, prompt, out[:-1])
    if got.shape != ref[i].shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"replayed logits of shape {got.shape} against "
                           f"{ref[i].shape}, or not finite")
    return {"token_gap_sigma": worst, "tokens_argmax": exact,
            "tokens": total, "logits_rel_err": _rel(got, ref[i]),
            "route_flip_share": flips / routed,
            "replayed_tokens": len(got), "replayed_prompt": len(prompt)}
