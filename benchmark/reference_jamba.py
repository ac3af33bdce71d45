"""The plain reference of the Jamba configurations (``"model": "jamba"``),
and the comparison that decides ``correct`` for what an engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, the recurrence a plain
``lax.scan`` over positions.  It is the benchmark's own copy: it shares no
code with ``paddle_tpu/models/jamba.py``, only the layout of the parameter
tree it is handed (``embed``, ``norm_f``, ``mamba/{ln1, w_in, conv_w, conv_b,
w_x, dt_norm, b_norm, c_norm, w_dt, b_dt, A_log, D_skip, w_out, ln2, w_gate,
w_up, w_down}`` stacked over the Mamba layers, ``attn/{ln1, wq, wk, wv, wo,
ln2, w_gate, w_up, w_down}`` stacked over the attention layers; ``A_log [M,
N, E]`` and ``conv_w [M, K, E]`` keep E last).  Weights are cast to float32
one layer at a time.

The model (the public ``JambaForCausalLM``): layer ``i`` is attention where
``i % attn_layer_period == attn_layer_offset`` and a Mamba-1 mixer otherwise
(HF Jamba's rule; the catalog does not give the order), each followed by a
SwiGLU MLP, all pre-normed with RMSNorm; no positional term anywhere; the
head is the embedding.

``replay_logits`` and ``served_checks`` follow ``benchmark/reference.py``'s,
through the engine's model protocol (``eng._model.forward_paged`` on the one
cache pytree ``eng._pools``) instead of Llama's pool arguments.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FIELDS = ("num_hidden_layers", "attn_layer_period", "attn_layer_offset",
          "num_attention_heads", "num_key_value_heads", "hidden_size",
          "mamba_d_state", "mamba_dt_rank", "rms_norm_eps")


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(x, lp, eps):
    h = _rms_norm(x, lp["ln2"], eps)
    return x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
        @ lp["w_down"]


def _mamba(x, lp, f):
    """One Mamba-1 mixer on ``x [B, S, D]``, from zero state; with it the
    state ``S [B, N, E]`` after the last position."""
    eps, N, r = f["rms_norm_eps"], f["mamba_d_state"], f["mamba_dt_rank"]
    B, S, _ = x.shape
    u, z = jnp.split(_rms_norm(x, lp["ln1"], eps) @ lp["w_in"], 2, axis=-1)
    K, E = lp["conv_w"].shape
    padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))     # zeros before 0
    u = jax.nn.silu(lp["conv_b"] + sum(
        lp["conv_w"][k] * padded[:, k:k + S] for k in range(K)))
    dt, Bm, Cm = jnp.split(u @ lp["w_x"], [r, r + N], axis=-1)
    dt = jax.nn.softplus(
        _rms_norm(dt, lp["dt_norm"], eps) @ lp["w_dt"] + lp["b_dt"])
    Bm = _rms_norm(Bm, lp["b_norm"], eps)
    Cm = _rms_norm(Cm, lp["c_norm"], eps)
    A = -jnp.exp(lp["A_log"])                              # [N, E]

    def position(state, at):                               # state [B, N, E]
        dt_t, u_t, b_t, c_t = at
        state = (jnp.exp(dt_t[:, None, :] * A) * state
                 + (dt_t * u_t)[:, None, :] * b_t[:, :, None])
        return state, jnp.einsum("bne,bn->be", state, c_t)

    last, y = lax.scan(position, jnp.zeros((B, N, E), jnp.float32),
                       tuple(jnp.moveaxis(t, 1, 0) for t in (dt, u, Bm, Cm)))
    y = jnp.moveaxis(y, 0, 1) + lp["D_skip"] * u
    return x + (y * jax.nn.silu(z)) @ lp["w_out"], last


def _attention(x, lp, f):
    """Causal softmax attention without any positional term; query head i
    reads key/value head ``i // (heads // kv heads)``."""
    nh, nkv = f["num_attention_heads"], f["num_key_value_heads"]
    B, S, D = x.shape
    d = D // nh
    h = _rms_norm(x, lp["ln1"], f["rms_norm_eps"])
    q = (h @ lp["wq"]).reshape(B, S, nh, d)
    k, v = (jnp.repeat((h @ lp[w]).reshape(B, S, nkv, d), nh // nkv, axis=2)
            for w in ("wk", "wv"))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    p = jax.nn.softmax(
        jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf), axis=-1)
    return (x + jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, D)
            @ lp["wo"], None)


def forward_and_state(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 of token ids ``[B, S]`` under the
    configuration ``fields`` (a config file's keys), and the recurrent state
    ``[M, B, N, E]`` of the M Mamba layers after position S-1.  Consecutive
    Mamba layers run as one ``lax.scan`` over their slice of the stack (one
    program text for all of them; the result is that of a Python loop)."""
    eps = fields["rms_norm_eps"]
    is_attn = [i % fields["attn_layer_period"] == fields["attn_layer_offset"]
               for i in range(fields["num_hidden_layers"])]

    def block(mixer):
        def one(x, lp):
            lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
            x, state = mixer(x, lp, fields)
            return _mlp(x, lp, eps), state
        return one

    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    i = mamba = attn = 0
    states = []
    while i < len(is_attn):
        if is_attn[i]:
            x, _ = block(_attention)(x, jax.tree_util.tree_map(
                lambda a: a[attn], params["attn"]))
            i, attn = i + 1, attn + 1
            continue
        n = (is_attn[i:] + [True]).index(True)     # Mamba layers in a row
        x, state = lax.scan(block(_mamba), x, jax.tree_util.tree_map(
            lambda a: a[mamba:mamba + n], params["mamba"]))
        states.append(state)
        i, mamba = i + n, mamba + n
    x = _rms_norm(x, params["norm_f"].astype(jnp.float32), eps)
    return (x @ params["embed"].astype(jnp.float32).T,
            jnp.concatenate(states))


def forward(fields: dict, params, ids):
    """The logits of ``forward_and_state``."""
    return forward_and_state(fields, params, ids)[0]


@functools.partial(jax.jit, static_argnums=0)
def _logits_jit(frozen, params, ids):
    return forward(dict(frozen), params, ids)


@functools.partial(jax.jit, static_argnums=0)
def _state_jit(frozen, params, ids):
    return forward_and_state(dict(frozen), params, ids)[1]


def final_state(fields: dict, params, row: list):
    """The reference's recurrent state ``[M, N, E]`` after the last token
    of ``row``: one forward over exactly these tokens, no padding, since
    every position moves the state."""
    frozen = tuple((k, fields[k]) for k in FIELDS)
    with jax.default_matmul_precision("highest"):
        return np.asarray(_state_jit(
            frozen, params, jnp.asarray([row], jnp.int32)))[:, 0]


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time (a row's
    float32 logits over a wide vocabulary are large), each padded on the
    right to a multiple of 128, which a causal model cannot see from the
    left."""
    frozen = tuple((k, fields[k]) for k in FIELDS)
    out = []
    with jax.default_matmul_precision("highest"):
        for row in rows:
            ids = np.zeros((1, -(-len(row) // 128) * 128), np.int32)
            ids[0, :len(row)] = row
            out.append(np.asarray(
                _logits_jit(frozen, params, jnp.asarray(ids)))[0, :len(row)])
    return out


# -- what the engine served, against the reference --------------------------

def replay_logits(eng, prompt: list, generated: list):
    """Logits of one served request, replayed on the engine's own state
    (``eng.params``, its cache as serving left it, a block table from
    ``eng.kv``): the prompt in ``eng.chunk`` pieces in slot 0 of the
    ``max_running``-wide batch, then ``generated`` one token at a time, the
    way ``step()`` fed them.  The first piece starts at position 0, which
    is what makes the model zero slot 0's recurrent state.  The engine's
    executables return argmaxes only, so the logits come from the same
    ``forward_paged`` under a jit of the benchmark's."""
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
        rows.append(out[:q])
        pos += q
    eng.kv.release(owner)
    return np.concatenate([np.asarray(r) for r in rows])


def _rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def served_checks(fields: dict, eng, params, served: list) -> dict:
    """What the engine served against the reference on the weights
    ``params`` it was built from; ``served`` is ``[(prompt, output), ...]``.
    The first two numbers are those of ``benchmark/reference.py``:
    ``token_gap_sigma``, over every served token, how far the reference's
    logit of that token trails the reference's best, in standard deviations
    of that row of logits, teacher forced on the engine's own stream (the
    worst one); ``logits_rel_err``, ``||served - ref|| / ||ref||`` over the
    logits of one request replayed on the live engine: here the request
    with the most tokens, prompt and output, because what a recurrence
    rounds away grows with every step.

    Two more are this model's, over the recurrent state that the replay
    left in slot 0 of the engine's cache (``ssm [M, N, R, E]``) against the
    reference's after the same tokens.  ``state_rel_err``: ``||served - ref||
    / ||ref||`` of each Mamba layer's state, the worst layer; a state not
    reset, advanced by padding or written to another layer's place reads
    near 1.  ``state_slow_rel_err``: the same over the tenth of the FIRST
    Mamba layer's elements that forget most slowly (the least ``softplus(
    b_dt) exp(A_log)``, the decay a step).  That number reads the precision
    in which the state is kept between steps, which neither the logits nor
    the whole state show: rounding a stored state adds an error at every
    step that lasts as long as the element remembers, while the rounding
    of the bf16 activations that feed the state averages out over the same
    span; and only the first Mamba layer's inputs are clean enough (an
    embedding, one norm, one matmul) for the difference to stand out."""
    ref = logits(fields, params, [p + out[:-1] for p, out in served])
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
    state = np.asarray(eng._pools["ssm"][:, :, 0].astype(jnp.float32))
    want = final_state(fields, params, prompt + out[:-1])
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
