"""The plain reference of the Phi-4-mini-flash configurations (``"model":
"phi4flash"``), and the comparison that decides ``correct`` for what an
engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, no zero-padded queries; the
recurrence a plain ``lax.scan`` over positions, attention ``[S, S]`` masked
scores with the window as a mask.  It is the benchmark's own copy: it shares
no code with ``paddle_tpu/models/phi4flash.py``, only the layout of the
parameter tree it is handed (``embed``, ``norm_f_w``, ``norm_f_b``; ``mamba/
{w_in, conv_w, conv_b, w_x, w_dt, b_dt, A_log, D_skip, w_out}`` stacked over
the L/4 + 1 Mamba layers, ``attn/{wqkv, bqkv, lq1, lk1, lq2, lk2, subln, wo,
bo}`` over the L/4 window layers and then the full one, ``gmu/{w_gate,
w_out}`` and ``cross/{wq, bq, lq1, .., bo}`` over the L/4 - 1 layers of the
second half's pairs; every stack also ``ln1_w, ln1_b, ln2_w, ln2_b, w1, w2``;
``A_log [M, N, E]`` and ``conv_w [M, K, E]`` keep E last).  Weights are cast
to float32 one layer at a time.

The model (the public ``Phi4FlashForCausalLM``; the configuration file lists
what of this is ``assumed``).  Every layer i of L is ``u <- u + Mixer_i(LN1_i
(u))``, ``u <- u + MLP_i(LN2_i(u))``, ``MLP(x) = (y * silu(g)) W_2`` with ``[g,
y] = x W_1``, LN a LayerNorm with weight and bias; then a final LayerNorm
and the tied head.  No positional term.  Mixers: i even and ``<= L/2`` a
Mamba-1 (no norms on dt, B, C), whose scan output before the gate at ``i =
L/2`` is the memory m; i odd and ``< L/2`` differential attention over a
window of ``sliding_window`` keys (the token itself counted); ``i = L/2 + 1``
the same over every earlier key, and its K, V are what the later layers
read; i even and ``>= L/2 + 2`` a gated memory unit ``(m * silu(h W_1)) W_2``;
i odd and ``>= L/2 + 3`` a cross layer: its own queries and ``lambda``, layer
``L/2 + 1``'s K and V.  Differential attention with query pairs ``(2j, 2j+1)``,
K and V pairs ``(2g, 2g+1)``, ``g = j // (heads / kv heads)``::

    a1 = A(q1, k1) [v1 | v2],  a2 = A(q2, k2) [v1 | v2],  A = softmax(q k^T / sqrt d)
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,  lambda_init = 0.8 - 0.6 exp(-0.3 i)
    o = (1 - lambda_init) RMSNorm(a1 - lambda a2), the pairs side by side, then W_o + b_o

with the FOUR products ``A(q1,k1) v1``, ``A(q1,k1) v2``, ``A(q2,k2) v1``,
``A(q2,k2) v2`` computed apart, one pair of heads at a time.

``replay_logits`` and ``served_checks`` follow ``benchmark/reference_jamba.py``'s.
What is computed in blocks so that 2,048 tokens at the published widths fit
beside a live engine: attention a pair of heads at a time, the head a block
of the vocabulary at a time with the logits kept on the host.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FIELDS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
          "hidden_size", "sliding_window", "mamba_d_state", "mamba_dt_rank",
          "layer_norm_eps")
PAD_TO = 1024           # rows are padded to a multiple: one program a size
VOCAB_BLOCK = 65536     # rows of the embedding a call of the head takes


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _mlp(x, lp, eps):
    g, y = jnp.split(_layer_norm(x, lp["ln2_w"], lp["ln2_b"], eps) @ lp["w1"],
                     2, axis=-1)
    return x + (y * jax.nn.silu(g)) @ lp["w2"]


def _mamba(x, lp, f, length):
    """One Mamba-1 mixer on ``x [S, D]`` from zero state.  Returns ``x +
    mixer``, the scan's output m ``[S, E]`` before the gate, and the state
    ``[N, E]`` after position ``length - 1`` (positions past it are padding
    and leave the state alone)."""
    N, r = f["mamba_d_state"], f["mamba_dt_rank"]
    S = x.shape[0]
    h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], f["layer_norm_eps"])
    u, z = jnp.split(h @ lp["w_in"], 2, axis=-1)
    K, E = lp["conv_w"].shape
    padded = jnp.pad(u, ((K - 1, 0), (0, 0)))               # zeros before 0
    u = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][k] * padded[k:k + S] for k in range(K)))
    dt, Bm, Cm = jnp.split(u @ lp["w_x"], [r, r + N], axis=-1)
    dt = jax.nn.softplus(dt @ lp["w_dt"] + lp["b_dt"])
    A = -jnp.exp(lp["A_log"])                                # [N, E]

    def position(state, at):                                 # state [N, E]
        t, dt_t, u_t, b_t, c_t = at
        new = jnp.exp(dt_t[None, :] * A) * state \
            + (dt_t * u_t)[None, :] * b_t[:, None]
        return jnp.where(t < length, new, state), c_t @ new

    last, y = lax.scan(position, jnp.zeros((N, E), jnp.float32),
                       (jnp.arange(S), dt, u, Bm, Cm))
    m = y + lp["D_skip"] * u
    return x + (m * jax.nn.silu(z)) @ lp["w_out"], m, last


def _diff_attention(x, lp, f, i, q, k, v, window):
    """Differential attention of layer i on ``q [S, heads, d]`` against ``k,
    v [S, kv heads, d]``, a pair of query heads at a time."""
    S, nh, d = q.shape
    nkv = k.shape[1]
    pos = jnp.arange(S)
    seen = pos[None, :] <= pos[:, None]
    if window is not None:
        seen &= pos[None, :] > pos[:, None] - window
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.float32(i))
    lam = jnp.exp(lp["lq1"] @ lp["lk1"]) - jnp.exp(lp["lq2"] @ lp["lk2"]) + lam0

    def attn(qh, kh):                                        # [S, d] each
        s = jnp.where(seen, qh @ kh.T / math.sqrt(d), -jnp.inf)
        return jax.nn.softmax(s, axis=-1)

    def pair(j):
        g = j // (nh // nkv)
        p1, p2 = attn(q[:, 2 * j], k[:, 2 * g]), \
            attn(q[:, 2 * j + 1], k[:, 2 * g + 1])
        v1, v2 = v[:, 2 * g], v[:, 2 * g + 1]
        a1 = jnp.concatenate([p1 @ v1, p1 @ v2], -1)         # [S, 2d]
        a2 = jnp.concatenate([p2 @ v1, p2 @ v2], -1)
        o = a1 - lam * a2
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                         + f["layer_norm_eps"]) * lp["subln"]
        return (1.0 - lam0) * o

    o = lax.map(pair, jnp.arange(nh // 2))                   # [nh/2, S, 2d]
    return x + jnp.moveaxis(o, 0, 1).reshape(S, nh * d) @ lp["wo"] + lp["bo"]


def _self_attention(x, lp, f, i, window):
    """A layer that holds K/V: window (``window`` keys) or full (None).
    Returns the layer's output and its ``k, v [S, kv heads, d]``."""
    nh, nkv = f["num_attention_heads"], f["num_key_value_heads"]
    S, D = x.shape
    d = D // nh
    h = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], f["layer_norm_eps"])
    q, k, v = jnp.split(h @ lp["wqkv"] + lp["bqkv"],
                        [nh * d, (nh + nkv) * d], axis=-1)
    k, v = k.reshape(S, nkv, d), v.reshape(S, nkv, d)
    return _diff_attention(x, lp, f, i, q.reshape(S, nh, d), k, v,
                           window), k, v


def forward_and_state(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 of the token ids ``[B, S]`` under the
    configuration ``fields`` (a config file's keys), and the recurrent state
    ``[M, B, N, E]`` of the M Mamba layers after position S-1: the whole
    model in one call, for sequences of test length."""
    def one(row):
        x, states = _hidden(fields, params, row, row.shape[0])
        return x @ params["embed"].astype(jnp.float32).T, states

    out, states = jax.vmap(one)(ids)
    return out, jnp.moveaxis(states, 0, 1)


def _hidden(fields: dict, params, ids, length):
    """The final-normed hidden states ``[S, D]`` and the Mamba states.  The
    (Mamba, window) pairs and the (memory unit, cross) pairs each run as one
    ``lax.scan`` over their slices of the stacks (one program text for all of
    them; the result is that of a Python loop over the layers)."""
    f = fields
    eps, L, W = f["layer_norm_eps"], f["num_hidden_layers"], \
        f["sliding_window"]
    nh = f["num_attention_heads"]
    n_w = L // 4
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    head = lambda stack, n: jax.tree_util.tree_map(   # noqa: E731
        lambda a: a[:n], stack)
    one = lambda stack, n: jax.tree_util.tree_map(    # noqa: E731
        lambda a: a[n], stack)

    def first_half(x, at):
        l, mp, ap = at
        mp, ap = _f32(mp), _f32(ap)
        x, _, state = _mamba(x, mp, f, length)               # layer 2l
        x = _mlp(x, mp, eps)
        x, _, _ = _self_attention(x, ap, f, 2 * l + 1, W)    # layer 2l + 1
        return _mlp(x, ap, eps), state

    x, states = lax.scan(first_half, x, (
        jnp.arange(n_w), head(params["mamba"], n_w),
        head(params["attn"], n_w)))
    mp, ap = _f32(one(params["mamba"], n_w)), _f32(one(params["attn"], n_w))
    x, m, state = _mamba(x, mp, f, length)                   # layer L/2
    x = _mlp(x, mp, eps)
    x, k, v = _self_attention(x, ap, f, L // 2 + 1, None)    # layer L/2 + 1
    x = _mlp(x, ap, eps)

    def second_half(x, at):
        l, gp, cp = at
        gp, cp = _f32(gp), _f32(cp)
        i = L // 2 + 2 + 2 * l
        h = _layer_norm(x, gp["ln1_w"], gp["ln1_b"], eps)
        x = x + (m * jax.nn.silu(h @ gp["w_gate"])) @ gp["w_out"]   # layer i
        x = _mlp(x, gp, eps)
        h = _layer_norm(x, cp["ln1_w"], cp["ln1_b"], eps)
        q = (h @ cp["wq"] + cp["bq"]).reshape(x.shape[0], nh, -1)
        x = _diff_attention(x, cp, f, i + 1, q, k, v, None)  # layer i + 1
        return _mlp(x, cp, eps), None

    x, _ = lax.scan(second_half, x, (
        jnp.arange(L // 4 - 1), params["gmu"], params["cross"]))
    x = _layer_norm(x, params["norm_f_w"].astype(jnp.float32),
                    params["norm_f_b"].astype(jnp.float32), eps)
    return x, jnp.concatenate([states, state[None]])


def forward(fields: dict, params, ids):
    """The logits of ``forward_and_state``."""
    return forward_and_state(fields, params, ids)[0]


@functools.partial(jax.jit, static_argnums=0)
def _hidden_jit(frozen, params, ids, length):
    return _hidden(dict(frozen), params, ids, length)


@functools.partial(jax.jit, static_argnums=3)
def _head_jit(x, embed, start, rows):
    block = lax.dynamic_slice_in_dim(embed, start, rows, 0)
    return x @ block.astype(jnp.float32).T


def _run(fields: dict, params, row: list):
    """Reference logits ``[len, V]`` (numpy) and final state ``[M, N, E]`` of
    one token row, padded on the right to a multiple of ``PAD_TO``: a causal
    model cannot see the padding from the left, and the state stops at the
    row's last token.  The head runs a block of the vocabulary at a time and
    the logits are assembled on the host."""
    frozen = tuple((k, fields[k]) for k in FIELDS)
    ids = np.zeros((-(-len(row) // PAD_TO) * PAD_TO,), np.int32)
    ids[:len(row)] = row
    V = params["embed"].shape[0]
    with jax.default_matmul_precision("highest"):
        x, state = _hidden_jit(frozen, params, jnp.asarray(ids),
                               jnp.int32(len(row)))
        # the padded rows go through the head too: one program a size
        out = np.concatenate([
            np.asarray(_head_jit(x, params["embed"], jnp.int32(v0),
                                 min(VOCAB_BLOCK, V - v0)))[:len(row)]
            for v0 in range(0, V, VOCAB_BLOCK)], axis=1)
    return out, np.asarray(state)


def final_state(fields: dict, params, row: list):
    """The reference's recurrent state ``[M, N, E]`` after the last token of
    ``row``."""
    return _run(fields, params, row)[1]


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time."""
    return [_run(fields, params, row)[0] for row in rows]


# -- what the engine served, against the reference --------------------------

def replay_logits(eng, prompt: list, generated: list):
    """Logits of one served request, replayed on the engine's own state
    (``eng.params``, its cache as serving left it, a block table from
    ``eng.kv``): the prompt in ``eng.chunk`` pieces in slot 0 of the
    ``max_running``-wide batch, then ``generated`` one token at a time, the
    way ``step()`` fed them.  The first piece starts at position 0, which
    is what makes the model zero slot 0's recurrent state and rewrite its
    rings.  The engine's executables return argmaxes only, so the logits
    come from the same ``forward_paged`` under a jit of the benchmark's, which
    hands back slot 0's fed rows and nothing else of the ``[R, Tc, V]``."""
    R, chunk = eng.max_running, eng.chunk
    ids = list(prompt) + list(generated)
    owner = "benchmark.replay"
    if not eng.kv.grow(owner, len(ids)):
        raise RuntimeError("no free pages for the replay")
    tbl = np.zeros((R, eng.max_blocks), np.int32)
    tbl[0] = eng.kv.block_row(owner)

    @functools.partial(jax.jit, donate_argnums=(2,) if eng._donate else ())
    def fwd(params, tokens, cache, tbl, lens, qlens):
        out, cache = eng._model.forward_paged(
            eng.cfg, params, tokens, cache, tbl, lens, qlens)
        return out[0], cache           # slot 0 is the only row that is fed

    rows, pos = [], 0
    while pos < len(ids):
        q = min(chunk, len(prompt) - pos) if pos < len(prompt) else 1
        tokens = np.zeros((R, chunk if pos < len(prompt) else 1), np.int32)
        tokens[0, :q] = ids[pos:pos + q]
        lens = np.zeros((R,), np.int32)
        qlens = np.zeros((R,), np.int32)
        lens[0], qlens[0] = pos + q, q
        out, eng._pools = fwd(
            eng.params, jnp.asarray(tokens), eng._pools, jnp.asarray(tbl),
            jnp.asarray(lens), jnp.asarray(qlens))
        rows.append(np.asarray(out[:q]))
        pos += q
    eng.kv.release(owner)
    return np.concatenate(rows)


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def served_checks(fields: dict, eng, params, served: list) -> dict:
    """What the engine served against the reference on the weights
    ``params`` it was built from; ``served`` is ``[(prompt, output), ...]``.
    The four numbers are ``benchmark/reference_jamba.py``'s, read the same
    way.  ``token_gap_sigma``: over every served token, how far the
    reference's logit of that token trails the reference's best, in standard
    deviations of that row of logits, teacher forced on the engine's own
    stream (the worst one).  ``logits_rel_err``: ``||served - ref|| / ||ref||``
    over the logits of the request with the most tokens, replayed on the
    live engine; a window ignored, a ring page overwritten too early or a
    cross layer on the wrong pool shows here.  ``state_rel_err``: the same
    over each Mamba layer's recurrent state that the replay left in slot 0
    (``ssm [M, N, R, E]``), the worst layer.  ``state_slow_rel_err``: over the
    tenth of the FIRST Mamba layer's elements that forget most slowly (the
    least ``softplus(b_dt) exp(A_log)``): the precision the state is kept in
    between steps."""
    ref, want = [], None
    i = max(range(len(served)), key=lambda j: sum(map(len, served[j])))
    for j, (prompt, out) in enumerate(served):
        rows, state = _run(fields, params, prompt + out[:-1])
        ref.append(rows)
        if j == i:
            want = state
    worst, exact, total = 0.0, 0, 0
    for (prompt, out), rows in zip(served, ref):
        rows = rows[len(prompt) - 1:]                 # one per token served
        gap = (rows.max(-1) - rows[np.arange(len(out)), out]) / rows.std(-1)
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += len(out)
    prompt, out = served[i]
    got = replay_logits(eng, prompt, out[:-1])
    if got.shape != ref[i].shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"replayed logits of shape {got.shape} against "
                           f"{ref[i].shape}, or not finite")
    state = np.asarray(eng._pools["ssm"][:, :, 0].astype(jnp.float32))
    if state.shape != want.shape or not np.all(np.isfinite(state)):
        raise RuntimeError(f"recurrent state of shape {state.shape} against "
                           f"{want.shape}, or not finite")
    by_layer = [_rel(a, b) for a, b in zip(state, want)]
    first = {k: np.asarray(params["mamba"][k][0], np.float32)
             for k in ("b_dt", "A_log")}
    decay = np.logaddexp(0, first["b_dt"]) * np.exp(first["A_log"])  # [N, E]
    slow = decay <= np.quantile(decay, 0.1)
    return {"token_gap_sigma": worst, "tokens_argmax": exact,
            "tokens": total, "logits_rel_err": _rel(got, ref[i]),
            "state_rel_err": max(by_layer),
            "state_rel_err_by_layer": [round(e, 5) for e in by_layer],
            "state_slow_rel_err": _rel(state[0][slow], want[0][slow]),
            "replayed_tokens": len(got), "replayed_prompt": len(prompt)}
