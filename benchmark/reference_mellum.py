"""The plain reference of the Mellum 2 configurations (``"model": "mellum"``),
and the comparison that decides ``correct`` for what an engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, no sort and no grouped product;
every head's ``[S, S]`` scores masked (the window a mask), the experts a loop,
each applied to every token and kept where the token chose it.  It is the
benchmark's own copy, written from the published equations: it shares no code
with ``paddle_tpu/models``, only the layout of the parameter tree it is handed
(``embed, lm_head [V, D]``, ``norm_f [D]``, ``layers`` with ``ln1 ln2 [L,
D]``, ``wqkv [L, D, (nh + 2 nkv) d]``, ``wo [L, nh d, D]``, ``router [L, D,
E]``, and ``experts`` with ``w_gate w_up [L, E, D, F]``, ``w_down [L, E, F,
D]``).

The model (``mellum``).  Layer l on ``x``, ``RMS`` the RMSNorm with a
weight::

    h = RMS1_l(x);  x <- x + Attn_l(h);  h = RMS2_l(x);  x <- x + MoE_l(h)

Attn: ``nh`` query heads over ``nkv`` K/V heads of d (head i reads K/V head
``i // (nh / nkv)``); q and k turned by rotary at the token's position, pair
j being lanes ``(j, j + d/2)``, of the layer's kind's entry of
``rope_parameters``: a window layer (``layer_types[l]`` "sliding_attention")
plain rotary, and query i sees the keys ``i - W < j <= i``; a full layer
YaRN (``yarn_parameters``, HF's ``_compute_yarn_parameters`` transcribed in
float64) with cos and sin times its attention factor, and sees ``j <= i``;
``softmax(q k^T / sqrt(d)) v``, then ``W_o``.  The rotary's cos and sin are
computed on the host in float64 from the positions, then cast.  MoE: ``p =
softmax(h W_r)`` over the E experts, the K largest (ties to the lower
index), weights ``p / sum`` over the K; ``sum_k w_k E_k(h)``, a SwiGLU each.
Then ``RMS_f`` and the untied head.

**Variants** (``VARIANTS``): the same keys read otherwise, for the tests and
the chip's readings that show what the limits of ``correct`` tell apart:
every matrix rounded to int8 per output channel where it is used
(``int8_weights``, the precision below the configuration's), plain rotary on
the full layers (``yarn_off``), YaRN without its attention factor
(``no_attention_factor``), no window, the K weights not renormalised, a
sigmoid router, and an RMSNorm of weight 1 on every head's q and k before the
rotary (``qk_norm``, the other reading of the assumed "no q/k norm").

Everything is computed in pieces, so that 12,288 tokens at the published
widths fit beside a live engine: a layer's attention a K/V head (its group
of query heads) and a block of queries at a time, the experts one at a time,
the head a block of the vocabulary at a time with the logits kept on the
host.  Weights are cast to float32 where they are used.

``route_flips`` counts, on the reference's own activations, the (token,
layer) pairs whose chosen set differs when the router's product is taken as
the program takes it (inputs and weights in the weights' dtype, float32
accumulation); logged, not a limit.  ``replay_logits`` is
``reference_deepseek_v2.py``'s (the engine's own ``forward_paged`` on its
live cache; nothing of the model).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.reference_deepseek_v2 import _rel, replay_logits

FIELDS = ("hidden_size", "moe_intermediate_size", "num_hidden_layers",
          "num_attention_heads", "num_key_value_heads", "head_dim",
          "num_experts", "num_experts_per_tok", "sliding_window",
          "rms_norm_eps", "norm_topk_prob")
WINDOW, FULL = "sliding_attention", "full_attention"
# what the published config.json states, for a file that leaves it out
DEFAULTS = {"rms_norm_eps": 1e-6, "norm_topk_prob": True,
            "layer_types": [WINDOW, WINDOW, WINDOW, FULL] * 7}
VARIANTS = {
    "int8": {"int8_weights": True},
    "yarn_off": {"yarn_off": True},
    "no_attention_factor": {"no_attention_factor": True},
    "no_window": {"sliding_window": 10 ** 9},
    "unnormalised": {"norm_topk_prob": False},
    "sigmoid_router": {"sigmoid_router": True},
    "qk_norm": {"qk_norm": True},
}
# the variants' own keys
FLAGS = ("int8_weights", "yarn_off", "no_attention_factor", "sigmoid_router",
         "qk_norm")
PAD_TO = 2048           # rows are padded to a multiple: one program a size
VOCAB_BLOCK = 4096      # rows of the head a call takes
Q_BLOCK = 256           # query rows whose scores stand at once


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


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * _f32(w)


def _at(w, l):
    return lax.dynamic_index_in_dim(w, l, 0, keepdims=False)


# -- rotary ---------------------------------------------------------------------

def yarn_parameters(theta: float, dim: int, rope: dict):
    """``(inv_freq [dim / 2] float64, attention_factor)``: HF's
    ``_compute_yarn_parameters`` (truncated correction range, as it
    defaults), transcribed in float64."""
    factor = rope["factor"]
    orig = rope["original_max_position_embeddings"]

    def get_mscale(scale, mscale=1.0):
        return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0

    attention_factor = rope.get("attention_factor")
    mscale, mscale_all_dim = rope.get("mscale"), rope.get("mscale_all_dim")
    if attention_factor is None:
        if mscale and mscale_all_dim:
            attention_factor = (get_mscale(factor, mscale)
                                / get_mscale(factor, mscale_all_dim))
        else:
            attention_factor = get_mscale(factor)
    beta_fast = rope.get("beta_fast") or 32
    beta_slow = rope.get("beta_slow") or 1

    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) \
            / (2 * math.log(theta))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    pos_freqs = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    extrapolation, interpolation = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    keep = 1.0 - ramp
    return (interpolation * (1.0 - keep) + extrapolation * keep,
            float(attention_factor))


def rope_tables(f: dict, kind: str, n: int):
    """``(sin, cos) [n, d / 2]`` float32 of positions ``0 .. n - 1`` for the
    layers of ``kind`` (``rope_parameters`` key): angles and their sines in
    float64 on the host, times the attention factor, then cast."""
    rp, d = dict(f["rope_parameters"])[kind], f["head_dim"]
    yarn = rp.get("rope_type", rp.get("type")) == "yarn" \
        and not f.get("yarn_off")
    if yarn:
        inv_freq, factor = yarn_parameters(rp["rope_theta"], d, rp)
        if f.get("no_attention_factor"):
            factor = 1.0
    else:
        inv_freq = rp["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64)
                                        / d)
        factor = 1.0
    angle = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    return ((np.sin(angle) * factor).astype(np.float32),
            (np.cos(angle) * factor).astype(np.float32))


def _rotate(x, sin, cos):
    """``x [S, .., d]`` turned by ``sin, cos [S, d / 2]``: pair j is lanes
    ``(j, j + d / 2)``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = sin.reshape(shape), cos.reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -- a layer ------------------------------------------------------------------

def _attention(f, layers, h, l, window, sin, cos):
    """``Attn_l(h)`` on ``h [S, D]`` (normed), a K/V head's group of query
    heads and ``Q_BLOCK`` queries at a time; ``window`` (traced bool): this
    is a window layer; ``sin, cos``: its kind's rotary."""
    nh, nkv, d = (f["num_attention_heads"], f["num_key_value_heads"],
                  f["head_dim"])
    S, D = h.shape
    rep = nh // nkv
    wqkv, wo = _at(layers["wqkv"], l), _at(layers["wo"], l)
    wo_scale = _int8_scale(wo)             # a group's rows are a slice
    wq = wqkv[:, :nh * d].reshape(D, nkv, rep * d)
    wk = wqkv[:, nh * d:(nh + nkv) * d].reshape(D, nkv, d)
    wv = wqkv[:, (nh + nkv) * d:].reshape(D, nkv, d)
    wo = wo.reshape(nkv, rep * d, D)
    pos = jnp.arange(S)
    W = jnp.where(window, f["sliding_window"], S + 1)
    qb = min(Q_BLOCK, S)
    eps = f["rms_norm_eps"]

    def group(out, at):
        wq_g, wk_g, wv_g, wo_g = at     # columns of W_q, W_k, W_v; rows of W_o
        q = (h @ _w(f, wq_g)).reshape(S, rep, d)
        k, v = h @ _w(f, wk_g), h @ _w(f, wv_g)
        wo_g = _w(f, wo_g, scale=wo_scale)
        if f.get("qk_norm"):
            q, k = _rms(q, jnp.ones((d,)), eps), _rms(k, jnp.ones((d,)), eps)
        q, k = _rotate(q, sin, cos), _rotate(k, sin, cos)

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
    if f.get("sigmoid_router"):
        p = jax.nn.sigmoid(logits)
    else:
        p = jax.nn.softmax(logits, axis=-1)
    chosen = jnp.argsort(-p, axis=-1, stable=True)[:, :f["num_experts_per_tok"]]
    w = jnp.take_along_axis(p, chosen, axis=-1)
    if f["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, chosen


def _moe(f, params, h, l, length):
    """``MoE_l(h)`` on ``h [S, D]`` (normed), and the tokens before
    ``length`` whose chosen set changes under the program's router
    product."""
    router = _at(params["layers"]["router"], l)
    w, chosen = _choose(f, h @ _w(f, router))
    _, theirs = _choose(f, jnp.dot(h.astype(router.dtype), router,
                                   preferred_element_type=jnp.float32))
    flipped = jnp.any(jnp.sort(chosen, -1) != jnp.sort(theirs, -1), -1)
    stacks = tuple(params["experts"][n] for n in ("w_gate", "w_up",
                                                  "w_down"))

    def expert(y, e):
        weight = jnp.sum(jnp.where(chosen == e, w, 0.0), axis=-1)   # [S]
        out = _swiglu(h, *(_w(f, _at(_at(s, l), e)) for s in stacks))
        return y + weight[:, None] * out, None

    y, _ = lax.scan(expert, jnp.zeros_like(h),
                    jnp.arange(f["num_experts"], dtype=jnp.int32))
    return y, jnp.sum(flipped & (jnp.arange(h.shape[0]) < length))


def _frozen(fields: dict):
    """The keys of ``fields`` the reference reads, hashable."""
    rope = tuple(sorted((kind, tuple(sorted(p.items())))
                        for kind, p in fields["rope_parameters"].items()))
    L = fields["num_hidden_layers"]
    types = fields.get("layer_types") or DEFAULTS["layer_types"]
    return (tuple((k, fields.get(k, DEFAULTS.get(k))) for k in FIELDS),
            tuple((k, bool(fields.get(k, False))) for k in FLAGS),
            tuple(t == WINDOW for t in types[:L]), rope)


def _thawed(frozen) -> dict:
    keys, flags, windows, rope = frozen
    return dict(keys + flags, windows=windows,
                rope_parameters={k: dict(p) for k, p in rope})


@functools.partial(jax.jit, static_argnums=0)
def _layer_jit(frozen, params, x, l, tables, length):
    """Layer ``l`` on ``x [S, D]``, and its route flips; ``tables``: the
    window layers' and the full layers' ``(sin, cos)``."""
    f = _thawed(frozen)
    eps = f["rms_norm_eps"]
    window = jnp.asarray(f["windows"])[l]
    sin, cos = (jnp.where(window, a, b) for a, b in zip(*tables))
    h = _rms(x, _at(params["layers"]["ln1"], l), eps)
    x = x + _attention(f, params["layers"], h, l, window, sin, cos)
    h = _rms(x, _at(params["layers"]["ln2"], l), eps)
    m, flips = _moe(f, params, h, l, length)
    return x + m, flips


@jax.jit
def _embed_jit(embed, ids):
    return _f32(jnp.take(embed, ids, axis=0))


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head_jit(frozen, x, norm_f, head, start, rows):
    f = _thawed(frozen)
    block = _w(f, lax.dynamic_slice_in_dim(head, start, rows, 0), axis=-1)
    return _rms(x, norm_f, f["rms_norm_eps"]) @ block.T


def _run(fields: dict, params, row: list, head_from: int = 0):
    """Reference logits ``[len - head_from, V]`` (numpy) of the positions
    ``head_from ..`` of one token row, and its ``route_flips``; the row
    padded on the right to a multiple of ``PAD_TO``: a causal model cannot
    see the padding from the left."""
    n = -(-len(row) // PAD_TO) * PAD_TO
    ids = np.zeros((n,), np.int32)
    ids[:len(row)] = row
    frozen = _frozen(fields)
    f = _thawed(frozen)
    tables = tuple(tuple(jnp.asarray(t) for t in rope_tables(f, kind, n))
                   for kind in (WINDOW, FULL))
    V = params["lm_head"].shape[0]
    flips = 0
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params["embed"], jnp.asarray(ids))
        for l in range(fields["num_hidden_layers"]):
            x, k = _layer_jit(frozen, params, x, jnp.int32(l), tables,
                              jnp.int32(len(row)))
            flips += int(k)
        out = np.empty((len(row) - head_from, V), np.float32)
        for v0 in range(0, V, VOCAB_BLOCK):
            rows = min(VOCAB_BLOCK, V - v0)
            out[:, v0:v0 + rows] = np.asarray(_head_jit(
                frozen, x, params["norm_f"], params["lm_head"],
                jnp.int32(v0), rows))[head_from:len(row)]
    return out, flips


def forward(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 (numpy) of the token ids ``[B, S]``
    under the configuration ``fields`` (a config file's keys)."""
    return np.stack([_run(fields, params, list(map(int, row)))[0]
                     for row in np.asarray(ids)])


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time."""
    return [_run(fields, params, row)[0] for row in rows]


def token_gaps(rows, out) -> np.ndarray:
    """How far the reference's logit of each served token ``out`` trails the
    reference's best, in standard deviations of its row of ``rows`` (one
    row per token served)."""
    return (rows.max(-1) - rows[np.arange(len(out)), out]) / rows.std(-1)


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
    (``route_flips``); logged, no limit.  A request's logits are kept only
    as long as its tokens are read, but the longest one's."""
    i = max(range(len(served)), key=lambda j: sum(map(len, served[j])))
    worst, exact, total, flips, routed, kept = 0.0, 0, 0, 0, 0, None
    for j, (prompt, out) in enumerate(served):
        rows, n = _run(fields, params, prompt + out[:-1],
                       0 if j == i else len(prompt) - 1)
        flips += n
        routed += (len(prompt) + len(out) - 1) * fields["num_hidden_layers"]
        if j == i:
            kept, rows = rows, rows[len(prompt) - 1:]
        gap = token_gaps(rows, out)
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += len(out)
        del rows
    prompt, out = served[i]
    got = replay_logits(eng, prompt, out[:-1])
    if got.shape != kept.shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"replayed logits of shape {got.shape} against "
                           f"{kept.shape}, or not finite")
    return {"token_gap_sigma": worst, "tokens_argmax": exact,
            "tokens": total, "logits_rel_err": _rel(got, kept),
            "route_flip_share": flips / routed,
            "replayed_tokens": len(got), "replayed_prompt": len(prompt),
            "replayed_past_stretch": len(got) > fields["rope_parameters"][
                FULL].get("original_max_position_embeddings", len(got)),
            "sampled_tokens": sorted(sum(map(len, s)) for s in served)}
