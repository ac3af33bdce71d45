"""The plain reference of the Command A+ configurations (``"model":
"cohere2_moe"``), and the comparison that decides ``correct`` for what an
engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, no sort and no grouped product;
every head's ``[S, S]`` scores masked (the window a mask), the routed experts
a loop over the experts held, each applied to every token and kept where the
token chose it, the shared experts a loop too.  It is the benchmark's own
copy, written from the published equations: it shares no code with
``paddle_tpu/models``, only the layout of the parameter tree it is handed
(``embed [V, D]`` (the head too), ``norm_f [D]``, ``layers`` with ``ln [L,
D]``, ``wqkv [L, D, (nh + 2 nkv) d]``, ``wo [L, nh d, D]``, ``router [L, D,
E]``, ``ws_gate ws_up [L, D, S F]``, ``ws_down [L, S F, D]``, and ``experts``
with ``w_gate w_up [L, Eh, D, F]``, ``w_down [L, Eh, F, D]``).

The model (``cohere2_moe``).  Layer l on ``x``, ``LN`` the mean-subtracted
norm with a weight and no bias::

    h = LN_l(x);  x <- x + Attn_l(h) + MoE_l(h)          (the parallel block)

Attn: ``nh`` query heads over ``nkv`` K/V heads of d (head i reads K/V head
``i // (nh / nkv)``); in a window layer (``l % layer_switch != layer_switch
- 1``) q and k are turned by rotary of ``rope_theta`` at the token's
position, pair j being lanes ``(j, j + d/2)``, and query i sees the keys ``i
- W < j <= i``; a global layer has no positional term and sees ``j <= i``;
``softmax(q k^T / sqrt(d)) v``, then ``W_o``.  MoE: ``s = sigmoid(h W_r)``
over the E routed experts, the K largest (ties to the lower index), weights
``s / sum`` over the K; ``sum_k w_k E_k(h) + (1/S) sum_j Shared_j(h)``, a
SwiGLU each.  Then ``LN_f`` and the tied head, times ``logit_scale``.

**A chip's share.**  Given ``experts_held`` (ids among the ``published``
group's ``num_experts``; the key ``num_experts`` then counts the experts
held) the reference leaves out what the absent experts would add, as the
program does; the shared experts are everyone's.  A sliced vocabulary is a
smaller vocabulary.

**Variants** (``VARIANTS``): the same keys read otherwise, for the tests and
the chip's readings that show what the limits of ``correct`` tell apart: no
window, rotary on the global layers too (``rope_on_global``), a softmax
router, the K weights not renormalised, the shared experts summed, a
sequential block (the experts reading ``LN(x + Attn(h))`` with the same
norm weight), and ``int8_weights`` (every matrix rounded to int8 per output
channel where it is used, the precision below the configuration's).

Everything is computed in pieces, so that 8,192 tokens at the published
widths fit beside a live engine: a layer's attention a K/V head (its group
of query heads) and a block of queries at a time, the experts one at a time,
the head a block of the vocabulary at a time with the logits kept on the
host.  Weights are cast to float32 where they are used.

``route_flips`` counts, on the reference's own activations, the (token,
layer) pairs whose chosen set differs when the router's product is taken as
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

FIELDS = ("hidden_size", "intermediate_size", "num_hidden_layers",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "num_experts", "num_experts_per_tok", "num_shared_experts",
          "sliding_window", "layer_switch", "rope_theta", "layer_norm_eps",
          "logit_scale", "expert_selection_fn", "norm_topk_prob",
          "shared_expert_combination_strategy", "use_parallel_block")
# what the published config.json states, for a file that leaves it out
DEFAULTS = {"layer_switch": 4, "logit_scale": 1, "layer_norm_eps": 1e-5,
            "rope_theta": 50000,
            "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
            "shared_expert_combination_strategy": "average",
            "use_parallel_block": True}
VARIANTS = {
    "no_window": {"sliding_window": 10 ** 9},
    "rope_on_global": {"rope_on_global": True},
    "softmax_router": {"expert_selection_fn": "softmax"},
    "unnormalised": {"norm_topk_prob": False},
    "shared_summed": {"shared_expert_combination_strategy": "sum"},
    "sequential_block": {"use_parallel_block": False},
    "int8": {"int8_weights": True},
}
FLAGS = ("rope_on_global", "int8_weights")     # the variants' own keys
PAD_TO = 2048           # rows are padded to a multiple: one program a size
VOCAB_BLOCK = 8192      # rows of the head a call takes
Q_BLOCK = 512           # query rows whose scores stand at once


def _f32(a):
    return a.astype(jnp.float32)


def _int8_scale(w, axis=-2):
    """Per output channel: the absmax over the input axis ``axis`` / 127."""
    return jnp.maximum(jnp.max(jnp.abs(_f32(w)), axis=axis, keepdims=True),
                       1e-30) / 127.0


def _w(f, w, axis=-2, scale=None):
    """A weight matrix in float32; under the variant ``int8_weights``
    rounded to int8 per output channel first, by ``scale`` where a slice of
    the input axis is given (the whole matrix's), else by its own."""
    x = _f32(w)
    if not f.get("int8_weights"):
        return x
    if scale is None:
        scale = _int8_scale(w, axis)
    return jnp.round(x / scale) * scale


def _layer_norm(x, w, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * _f32(w)


def _at(w, l):
    return lax.dynamic_index_in_dim(w, l, 0, keepdims=False)


def scored_experts(fields: dict) -> int:
    """The routed experts the router scores: the published count where the
    file states a chip's share, else ``num_experts``."""
    return fields.get("published", {}).get("num_experts",
                                           fields["num_experts"])


def _rotate(x, pos, theta):
    """``x [S, .., d]`` turned by its position ``pos [S]``: pair j is lanes
    ``(j, j + d / 2)``, angle ``pos theta^(-2j/d)``."""
    half = x.shape[-1] // 2
    inv = theta ** (-2.0 * jnp.arange(half, dtype=jnp.float32)
                    / x.shape[-1])
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = jnp.sin(angle).reshape(shape), jnp.cos(angle).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(f, layers, h, l, window):
    """``Attn_l(h)`` on ``h [S, D]`` (normed), a K/V head's group of query
    heads and ``Q_BLOCK`` queries at a time; ``window`` (traced bool): this
    is a window layer."""
    nh, nkv, d = (f["num_attention_heads"], f["num_key_value_heads"],
                  f["head_dim"])
    S, D = h.shape
    rep = nh // nkv
    wqkv, wo = _at(layers["wqkv"], l), _at(layers["wo"], l)
    wo_scale = _int8_scale(wo)             # a group's rows are a slice
    turned = window | f.get("rope_on_global", False)
    wq = wqkv[:, :nh * d].reshape(D, nkv, rep * d)
    wk = wqkv[:, nh * d:(nh + nkv) * d].reshape(D, nkv, d)
    wv = wqkv[:, (nh + nkv) * d:].reshape(D, nkv, d)
    wo = wo.reshape(nkv, rep * d, D)
    pos = jnp.arange(S)
    W = jnp.where(window, f["sliding_window"], S + 1)
    qb = min(Q_BLOCK, S)

    def group(out, at):
        wq_g, wk_g, wv_g, wo_g = at     # columns of W_q, W_k, W_v; rows of W_o
        q = (h @ _w(f, wq_g)).reshape(S, rep, d)
        k, v = h @ _w(f, wk_g), h @ _w(f, wv_g)
        wo_g = _w(f, wo_g, scale=wo_scale)
        q = jnp.where(turned, _rotate(q, pos, f["rope_theta"]), q)
        k = jnp.where(turned, _rotate(k, pos, f["rope_theta"]), k)

        def block(i, out):
            qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)    # [qb, rep, d]
            at_q = i * qb + jnp.arange(qb)
            seen = (pos[None, :] <= at_q[:, None]) \
                & (pos[None, :] > at_q[:, None] - W)
            s = jnp.einsum("qrd,kd->rqk", qi, k) / math.sqrt(d)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            o = jnp.einsum("rqk,kd->qrd", p, v).reshape(qb, rep * d)
            return lax.dynamic_update_slice_in_dim(
                out, lax.dynamic_slice_in_dim(out, i * qb, qb, 0)
                + o @ wo_g, i * qb, 0)

        return lax.fori_loop(0, S // qb, block, out), None

    out, _ = lax.scan(group, jnp.zeros((S, D), jnp.float32),
                      (jnp.moveaxis(wq, 1, 0), jnp.moveaxis(wk, 1, 0),
                       jnp.moveaxis(wv, 1, 0), wo))
    return out


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _choose(f, logits):
    """``(w [S, K], chosen [S, K])``: the router's scores, the K largest
    (equal ones to the lower index) and their weights."""
    if f["expert_selection_fn"] == "softmax":
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    chosen = jnp.argsort(-s, axis=-1, stable=True)[:, :f["num_experts_per_tok"]]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if f["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, chosen


def _moe(f, params, h, l, length):
    """``MoE_l(h)`` on ``h [S, D]`` (normed), and the tokens before
    ``length`` whose chosen set changes under the program's router
    product."""
    layers = params["layers"]
    router = _at(layers["router"], l)
    w, chosen = _choose(f, h @ _w(f, router))
    _, theirs = _choose(f, jnp.dot(h.astype(router.dtype), router,
                                   preferred_element_type=jnp.float32))
    flipped = jnp.any(jnp.sort(chosen, -1) != jnp.sort(theirs, -1), -1)
    held = f.get("experts_held")
    ids = jnp.asarray(list(range(scored_experts(f))) if held is None
                      else list(held), jnp.int32)
    stacks = tuple(params["experts"][n] for n in ("w_gate", "w_up",
                                                  "w_down"))

    def expert(y, at):
        e, eid = at               # its place in the stacks, its id
        weight = jnp.sum(jnp.where(chosen == eid, w, 0.0), axis=-1)   # [S]
        out = _swiglu(h, *(_w(f, _at(_at(s, l), e)) for s in stacks))
        return y + weight[:, None] * out, None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    (jnp.arange(ids.shape[0]), ids))
    n, F = f["num_shared_experts"], f["intermediate_size"]
    ws = tuple(_at(layers[k], l) for k in ("ws_gate", "ws_up", "ws_down"))
    down_scale = _int8_scale(ws[2])        # an expert's rows are a slice

    def shared(y, j):
        gate, up = (_w(f, lax.dynamic_slice_in_dim(w_, j * F, F, 1))
                    for w_ in ws[:2])
        down = _w(f, lax.dynamic_slice_in_dim(ws[2], j * F, F, 0),
                  scale=down_scale)
        return y + _swiglu(h, gate, up, down), None

    s, _ = lax.scan(shared, jnp.zeros_like(h), jnp.arange(n))
    if f["shared_expert_combination_strategy"] == "average":
        s = s / n
    return y + s, jnp.sum(flipped & (jnp.arange(h.shape[0]) < length))


def _frozen(fields: dict):
    """The keys of ``fields`` the reference reads, hashable."""
    held = fields.get("experts_held")
    return (tuple((k, fields.get(k, DEFAULTS.get(k))) for k in FIELDS),
            tuple((k, bool(fields.get(k, False))) for k in FLAGS),
            scored_experts(fields), None if held is None else tuple(held))


def _thawed(frozen) -> dict:
    keys, flags, scored, held = frozen
    return dict(keys + flags, published={"num_experts": scored},
                experts_held=held)


@functools.partial(jax.jit, static_argnums=0)
def _layer_jit(frozen, params, x, l, length):
    """Layer ``l`` on ``x [S, D]``, and its route flips."""
    f = _thawed(frozen)
    eps = f["layer_norm_eps"]
    ln = _at(params["layers"]["ln"], l)
    window = l % f["layer_switch"] != f["layer_switch"] - 1
    h = _layer_norm(x, ln, eps)
    a = _attention(f, params["layers"], h, l, window)
    if not f["use_parallel_block"]:
        h = _layer_norm(x + a, ln, eps)
    m, flips = _moe(f, params, h, l, length)
    return x + a + m, flips


@jax.jit
def _embed_jit(embed, ids):
    return _f32(jnp.take(embed, ids, axis=0))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head_jit(frozen, x, norm_f, head, start, rows):
    f = _thawed(frozen)
    block = _w(f, lax.dynamic_slice_in_dim(head, start, rows, 0), axis=-1)
    return _layer_norm(x, norm_f, f["layer_norm_eps"]) @ block.T \
        * f["logit_scale"]


def _run(fields: dict, params, row: list):
    """Reference logits ``[len, V]`` (numpy) of one token row and its
    ``route_flips``, the row padded on the right to a multiple of ``PAD_TO``:
    a causal model cannot see the padding from the left."""
    ids = np.zeros((-(-len(row) // PAD_TO) * PAD_TO,), np.int32)
    ids[:len(row)] = row
    frozen = _frozen(fields)
    V = params["embed"].shape[0]
    flips = 0
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params["embed"], jnp.asarray(ids))
        for l in range(fields["num_hidden_layers"]):
            x, n = _layer_jit(frozen, params, x, jnp.int32(l),
                              jnp.int32(len(row)))
            flips += int(n)
        out = np.concatenate([
            np.asarray(_head_jit(frozen, x, params["norm_f"],
                                 params["embed"], jnp.int32(v0),
                                 min(VOCAB_BLOCK, V - v0)))
            [:len(row)] for v0 in range(0, V, VOCAB_BLOCK)], axis=1)
    return out, flips


def forward(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 (numpy) of the token ids ``[B, S]``
    under the configuration ``fields`` (a config file's keys)."""
    return np.stack([_run(fields, params, list(map(int, row)))[0]
                     for row in np.asarray(ids)])


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time."""
    return [_run(fields, params, row)[0] for row in rows]


def expert_layer(fields: dict, params, h, l: int):
    """``MoE_l(h)`` on ``h [S, D]`` float32, for the tests that hold the
    program's expert layer and a chip's share to it."""
    with jax.default_matmul_precision("highest"):
        y, _ = _moe(_thawed(_frozen(fields)), params,
                    jnp.asarray(h, jnp.float32), jnp.int32(l), h.shape[0])
    return np.asarray(y)


# -- what the engine served, against the reference --------------------------

def served_checks(fields: dict, eng, params, served: list) -> dict:
    """What the engine served against the reference on the weights
    ``params`` it was built from; ``served`` is ``[(prompt, output), ...]``.
    ``token_gap_sigma``: over every served token, how far the reference's
    logit of that token trails the reference's best, in standard deviations
    of that row of logits, teacher forced on the engine's own stream (the
    worst one).  ``logits_rel_err``: ``||served - ref|| / ||ref||`` over the
    logits of the request with the most tokens, replayed on the live engine:
    the rings, the pages, the kernels and bfloat16 against masked heads, a
    loop over experts and float32.  ``route_flip_share``: the share of
    (token, layer) pairs of the served rows whose chosen experts the
    program's router product changes on the reference's own activations
    (``route_flips``); logged, no limit."""
    ref, flips, routed = [], 0, 0
    for prompt, out in served:
        rows, n = _run(fields, params, prompt + out[:-1])
        ref.append(rows)
        flips += n
        routed += len(rows) * fields["num_hidden_layers"]
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
            "replayed_tokens": len(got), "replayed_prompt": len(prompt),
            "sampled_tokens": sorted(sum(map(len, s)) for s in served)}
