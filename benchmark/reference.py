"""The plain reference of the configurations the benchmark runs, and the
comparisons that decide ``correct``.

Both configurations are decoder-only transformers of one shape family
(RMSNorm, rotary embeddings in the half-split "neox" layout, grouped-query
attention, SwiGLU, untied head), so one straightforward ``jax.numpy``
forward pass in float32 serves both: no kernel, no cache, no batching
tricks, matmul precision "highest". It is the benchmark's own copy — it
shares no code with ``paddle_tpu/models/llama.py``, only the layout of the
parameter tree it is handed (``embed``, ``layers/{ln1,wq,wk,wv,wo,ln2,
w_gate,w_up,w_down}`` stacked on a leading layer axis, ``norm_f``,
``lm_head``). Weights are cast to float32 one layer at a time inside the
scan, so the reference needs no second copy of the model on the device.

Departures from the published models, the same in the program: the linear
``rope_scaling`` of deepseek-coder is not applied, and internlm2's fused
``wqkv`` is three separate projections computing the same function.

``replay_logits`` and ``served_checks`` are copied from ``chip_smoke.py``
(PR 21), which stays the program's to change.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """Rotary embedding on ``x`` ``[B, S, heads, d]``, half-split layout."""
    S, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


def forward(fields: dict, params, ids, remat: bool = False):
    """Logits ``[B, S, V]`` in float32 of token ids ``[B, S]`` under the
    configuration ``fields`` (the ``LlamaConfig`` fields of a config file).
    ``remat`` recomputes each layer in a backward pass instead of keeping
    its attention scores (for ``loss_and_grads``)."""
    nh, nkv = fields["num_attention_heads"], fields["num_key_value_heads"]
    d = fields["hidden_size"] // nh
    eps, theta = fields["rms_norm_eps"], fields["rope_theta"]
    B, S = ids.shape
    f32 = functools.partial(jax.tree_util.tree_map,
                            lambda a: a.astype(jnp.float32))
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        lp = f32(lp)
        h = _rms_norm(x, lp["ln1"], eps)
        q = _rope((h @ lp["wq"]).reshape(B, S, nh, d), theta)
        k = _rope((h @ lp["wk"]).reshape(B, S, nkv, d), theta)
        v = (h @ lp["wv"]).reshape(B, S, nkv, d)
        # query head i reads key/value head i // (nh // nkv)
        k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        a = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(B, S, nh * d)
        x = x + a @ lp["wo"]
        h = _rms_norm(x, lp["ln2"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) \
            @ lp["w_down"]
        return x, None

    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    x, _ = lax.scan(jax.checkpoint(layer) if remat else layer, x,
                    params["layers"])
    x = _rms_norm(x, params["norm_f"].astype(jnp.float32), eps)
    return x @ params["lm_head"].astype(jnp.float32)


def _freeze(fields: dict) -> tuple:
    """What ``forward`` reads of a configuration, hashable for ``jit``."""
    return tuple((k, fields[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "hidden_size",
        "rms_norm_eps", "rope_theta"))


@functools.partial(jax.jit, static_argnums=0)
def _logits_jit(frozen, params, ids):
    return forward(dict(frozen), params, ids)


# the leaves whose gradient the training check compares: every norm gain
# (``ln1[l]`` is reached through layer ``l``'s attention backward and all
# that lies above it, ``ln2[l]`` through its MLP backward) and one weight
# matrix per layer (``wv``: a weight-gradient matmul fed by the attention
# backward). Small enough to hold in float32 beside a trainer's state.
GRAD_LEAVES = ("ln1", "ln2", "wv", "norm_f")


def _pick(tree: dict) -> dict:
    """The ``GRAD_LEAVES`` of a parameter-shaped tree."""
    return {k: tree[k] if k in tree else tree["layers"][k]
            for k in GRAD_LEAVES}


def _put(params: dict, picked: dict) -> dict:
    top = {k: v for k, v in picked.items() if k in params}
    rest = {k: v for k, v in picked.items() if k not in params}
    return dict(params, layers=dict(params["layers"], **rest), **top)


@functools.partial(jax.jit, static_argnums=0)
def _ce_and_grads_jit(frozen, params, ids, labels):
    def ce(picked):
        logits = forward(dict(frozen), _put(params, picked), ids, remat=True)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - tgt)

    picked = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    _pick(params))
    return jax.value_and_grad(ce)(picked)


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row. The rows are padded on the right
    to one length (a multiple of 128, so that few shapes compile), which a
    causal model cannot see from the left."""
    width = -(-max(map(len, rows)) // 128) * 128
    ids = np.zeros((len(rows), width), np.int32)
    for i, row in enumerate(rows):
        ids[i, :len(row)] = row
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(_logits_jit(_freeze(fields), params,
                                     jnp.asarray(ids)))
    return [ref[i, :len(row)] for i, row in enumerate(rows)]


def loss_and_grads(fields: dict, params, batch: dict) -> tuple:
    """Mean next-token cross entropy of ``batch`` under the reference and
    its float32 gradient with respect to ``GRAD_LEAVES`` (a dict of device
    arrays), one sequence at a time (one ``[1, S]`` program, each layer
    recomputed in the backward pass: little memory)."""
    n = len(batch["input_ids"])
    ces, total = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(n):
            ce, g = _ce_and_grads_jit(
                _freeze(fields), params,
                jnp.asarray(batch["input_ids"][i:i + 1]),
                jnp.asarray(batch["labels"][i:i + 1]))
            ces.append(ce)
            total = g if total is None else \
                jax.tree_util.tree_map(jnp.add, total, g)
    grads = jax.tree_util.tree_map(lambda g: g / n, total)
    return float(np.mean([float(ce) for ce in ces])), grads


@jax.jit
def _direction_error(a, b):
    a, b = a.astype(jnp.float32).ravel(), b.astype(jnp.float32).ravel()
    return jnp.linalg.norm(a / jnp.linalg.norm(a) - b / jnp.linalg.norm(b))


def direction_errors(first_moment: dict, grads: dict) -> dict:
    """How far a trainer's gradient points from the reference's, leaf by
    leaf of ``GRAD_LEAVES``: ``|| a/||a|| - b/||b|| ||``, where ``a`` is
    the trainer's first Adam moment after its first step (``(1 - b1)``
    times its gradient, whatever ``b1`` is) and ``b`` the reference's
    gradient. 0 is the same direction, 1.41 an unrelated one."""
    mine = _pick(first_moment)
    return {k: float(_direction_error(jnp.asarray(mine[k]), jnp.asarray(b)))
            for k, b in grads.items()}


# -- what the engine served, against the reference --------------------------

def replay_logits(eng, prompt: list, generated: list):
    """Logits of one served request, replayed on the engine's own state:
    ``eng.params``, its page pools as serving left them and a block table
    from ``eng.kv`` — the prompt in ``eng.chunk`` pieces in slot 0 of the
    ``max_running``-wide batch, then ``generated`` one token at a time, the
    way ``step()`` fed them. The engine's executables return argmaxes only,
    so the logits come from the same ``forward_paged`` under a jit of the
    benchmark's."""
    R, chunk = eng.max_running, eng.chunk
    ids = list(prompt) + list(generated)
    owner = "benchmark.replay"
    if not eng.kv.grow(owner, len(ids)):
        raise RuntimeError("no free pages for the replay")
    tbl = np.zeros((R, eng.max_blocks), np.int32)
    tbl[0] = eng.kv.block_row(owner)

    @functools.partial(jax.jit, donate_argnums=(2,) if eng._donate else ())
    def fwd(params, tokens, pools, tbl, lens, qlens):
        kp, vp, *scales = pools
        out, pools = eng._forward_paged(
            eng.cfg, params, tokens, kp, vp, tbl, lens, qlens,
            **dict(zip(("k_scales", "v_scales"), scales)))
        return out[0], pools           # slot 0 is the only row that is fed

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


def served_checks(fields: dict, eng, params, served: list) -> dict:
    """What the engine served against the reference on the dense weights
    ``params`` it was built from. ``served`` is ``[(prompt, output), ...]``.

    ``token_gap_sigma``: over every served token, how far the reference's
    logit of that token trails the reference's best, in standard deviations
    of that row of logits (0 where the served token is the reference's
    argmax), teacher forced on the engine's own stream; the worst one.
    ``logits_rel_err``: ``||served - ref|| / ||ref||`` over the logits of
    the request with the longest prompt, replayed on the live engine."""
    ref = logits(fields, params, [p + out[:-1] for p, out in served])
    worst, exact, total = 0.0, 0, 0
    for (prompt, out), rows in zip(served, ref):
        rows = rows[len(prompt) - 1:]                 # one per token served
        gap = (rows.max(-1) - rows[np.arange(len(out)), out]) / rows.std(-1)
        worst = max(worst, float(gap.max()))
        exact += int((gap == 0).sum())
        total += len(out)
    i = max(range(len(served)), key=lambda j: len(served[j][0]))
    prompt, out = served[i]
    got = replay_logits(eng, prompt, out[:-1])
    if got.shape != ref[i].shape or not np.all(np.isfinite(got)):
        raise RuntimeError(f"replayed logits of shape {got.shape} against "
                           f"{ref[i].shape}, or not finite")
    return {"token_gap_sigma": worst, "tokens_argmax": exact,
            "tokens": total,
            "logits_rel_err": float(np.linalg.norm(got - ref[i])
                                    / np.linalg.norm(ref[i])),
            "replayed_tokens": len(got), "replayed_prompt": len(prompt)}
