"""The plain reference of the DeepSeek-V2 configurations (``"model":
"deepseek_v2"``), and the comparison that decides ``correct`` for what an
engine served.

One straightforward ``jax.numpy`` forward pass in float32, matmul precision
"highest": no kernel, no cache, no chunking, no sort and no grouped matmul;
attention in its MATERIALISED form (every head's K and V expanded from the
latent, ``[S, S]`` masked scores), which the program's engine never computes
(it attends on the latent itself, absorbed).  It is the benchmark's own copy:
it shares no code with ``paddle_tpu/models/deepseek_v2.py`` or
``models/experts.py``, only the layout of the parameter tree it is handed
(``embed, lm_head [V, D]``, ``norm_f``; the stacks ``dense`` and ``moe`` with
``ln1 ln2 wq wkva kv_norm wkvb wo``, the dense ``w_gate w_up w_down``, the
expert layers' ``router``, routed ``w_gate w_up [n, Eh, D, F]``, ``w_down [n,
Eh, F, D]`` and shared ``ws_gate ws_up ws_down``).  Weights are cast to
float32 one layer, and one expert, at a time.

The model (the public ``DeepseekV2ForCausalLM``).  Every layer is ``u <- u +
MLA(RMSNorm(u))``, ``u <- u + FFN(RMSNorm(u))``; then a final RMSNorm and the
untied head.  MLA, for a token at position p with normed input h: ``q = h
W_q`` as heads of ``[q_nope | q_pe]``; ``[c_raw | k_pe] = h W_kva``, ``c =
RMSNorm(c_raw)``; ``q_pe`` and the one ``k_pe`` rotated by p with YaRN's
frequencies (pair j of the rotary lanes is ``(j, j + dr/2)``); ``[k_nope_i |
v_i] = c W_kvb`` per head; causal ``softmax((q_nope_i . k_nope_i + q_pe_i .
k_pe) scale) v_i``, ``scale = (dn + dr)^-0.5 m^2``, ``m = 0.1 mscale_all_dim
ln(factor) + 1``; ``W_o``.  FFN of the first ``first_k_dense_replace`` layers:
SwiGLU.  Of the others: ``p = softmax(h W_r)`` over all routed experts, the
``num_experts_per_tok`` largest (ties to the lower index) as they are, ``y =
shared(h) + sum_e p_e expert_e(h)``.

The expert sum is written twice.  ``_experts_by_token`` is the definition: a
loop over tokens, each through its chosen experts, one after the other; it
gathers an expert's three matrices for every pair, 8,192 x 6 x 17 MB a layer
at the cell's widths, and is what sequences of test length use.
``_experts_by_expert`` loops over the experts instead, each applied to every
token and kept where the token chose it: the same sums in another order, 64
dense products a layer, which 2,048 tokens at the published widths can
afford.  ``forward`` takes the first up to ``BY_TOKEN_MAX`` tokens; a test
holds the two to each other.

``route_flips`` counts, on the reference's own activations, the tokens of an
expert layer whose chosen set differs when the router's product is taken as
the program takes it (inputs and weights in the weights' dtype, float32
accumulation): how often rounding alone changes the routing; logged, not a
limit.

``replay_logits`` and ``served_checks`` follow ``reference_phi4flash.py``'s.
The head runs a block of the vocabulary at a time with the logits kept on the
host, so that 2,048 tokens fit beside a live engine.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

FIELDS = ("num_hidden_layers", "first_k_dense_replace",
          "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
          "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
          "rope_theta")
PAD_TO = 1024           # rows are padded to a multiple: one program a size
VOCAB_BLOCK = 16384     # rows of the head a call takes
BY_TOKEN_MAX = 256      # sequences up to this take the per-token loop


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn(fields: dict):
    """``(inv_freq [dr / 2], what cos and sin are scaled by, the softmax
    scale)`` of the configuration, from its ``rope_scaling`` (None: plain
    frequencies and ``(dn + dr)^-0.5``)."""
    dr, theta = fields["qk_rope_head_dim"], fields["rope_theta"]
    rs = fields.get("rope_scaling")
    scale = (fields["qk_nope_head_dim"] + dr) ** -0.5
    freq = np.array([theta ** (-2.0 * j / dr) for j in range(dr // 2)])
    if not rs:
        return freq, 1.0, scale
    span = rs["original_max_position_embeddings"]

    def dim_of(rotations):      # the pair that turns `rotations` times in span
        return dr * math.log(span / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dr - 1)
    ramp = np.clip((np.arange(dr // 2) - low) / max(high - low, 0.001), 0, 1)
    inv_freq = freq * (1 - ramp) + freq / rs["factor"] * ramp
    all_dim = rs.get("mscale_all_dim", 0)
    if all_dim:
        scale *= _mscale(rs["factor"], all_dim) ** 2
    return inv_freq, (_mscale(rs["factor"], rs.get("mscale", 1))
                      / _mscale(rs["factor"], all_dim)), scale


def _rotate(x, angle, factor):
    """``x [S, ..., dr]`` turned by ``angle [S, dr / 2]``: pair j is lanes
    ``(j, j + dr / 2)``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    sin, cos = (jnp.sin(angle) * factor).reshape(shape), \
        (jnp.cos(angle) * factor).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(x, lp, f, rope):
    """MLA of one layer on ``x [S, D]``, materialised."""
    nh, r = f["num_attention_heads"], f["kv_lora_rank"]
    dn, dr, dv = f["qk_nope_head_dim"], f["qk_rope_head_dim"], \
        f["v_head_dim"]
    inv_freq, factor, scale = rope
    S = x.shape[0]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    h = _rms_norm(x, lp["ln1"], f["rms_norm_eps"])
    q = (h @ lp["wq"]).reshape(S, nh, dn + dr)
    kv = h @ lp["wkva"]
    c = _rms_norm(kv[:, :r], lp["kv_norm"], f["rms_norm_eps"])
    k_pe = _rotate(kv[:, r:], angle, factor)                 # [S, dr]
    q_pe = _rotate(q[..., dn:], angle, factor)               # [S, nh, dr]
    up = (c @ lp["wkvb"]).reshape(S, nh, dn + dv)
    k = jnp.concatenate(
        [up[..., :dn], jnp.broadcast_to(k_pe[:, None, :], (S, nh, dr))], -1)
    q = jnp.concatenate([q[..., :dn], q_pe], -1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * scale
    seen = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khv->qhv", p, up[..., dn:])
    return x + o.reshape(S, nh * dv) @ lp["wo"]


def _choose(logits, f):
    """``(p [S, E], experts [S, K])``: the softmax over all experts and its
    K largest, equal ones to the lower index."""
    p = jax.nn.softmax(logits, axis=-1)
    return p, jnp.argsort(-p, axis=-1, stable=True)[
        :, :f["num_experts_per_tok"]]


def _route(h, router, f):
    """``(p [S, K], experts [S, K])``: the chosen experts and their
    probabilities as they are (times ``routed_scaling_factor``)."""
    p, chosen = _choose(h @ router, f)
    return (jnp.take_along_axis(p, chosen, axis=-1)
            * f["routed_scaling_factor"]), chosen


def _local(f, chosen):
    """The chosen experts as indices into the stacks this device holds
    (``experts_held``, in the stacks' order; absent: every expert), -1 for an
    expert it does not hold."""
    held = f.get("experts_held")
    if held is None:
        return chosen
    table = np.full((f["n_routed_experts"],), -1, np.int32)
    table[np.asarray(held)] = np.arange(len(held))
    return jnp.asarray(table)[chosen]


def _experts_by_token(h, p, local, stacks, l):
    """The definition: token t through each of its chosen experts held
    here, ``sum_k p[t, k] expert(h[t])``."""
    def expert(x, e):
        w_gate, w_up, w_down = (w[l, e].astype(jnp.float32) for w in stacks)
        return _swiglu(x, w_gate, w_up, w_down)

    def token(at):
        x, p_t, e_t = at
        y = jnp.zeros_like(x)
        for k in range(p.shape[1]):
            y = y + jnp.where(e_t[k] >= 0, p_t[k], 0.0) \
                * expert(x, jnp.maximum(e_t[k], 0))
        return y

    return lax.map(token, (h, p, local))


def _experts_by_expert(h, p, local, stacks, l):
    """The same sums an expert at a time: expert e on every token, kept
    where the token chose it."""
    def expert(y, e):
        w_gate, w_up, w_down = (w[l, e].astype(jnp.float32) for w in stacks)
        weight = jnp.sum(jnp.where(local == e, p, 0.0), axis=-1)   # [S]
        return y + weight[:, None] * _swiglu(h, w_gate, w_up, w_down), None

    y, _ = lax.scan(expert, jnp.zeros_like(h), jnp.arange(stacks[0].shape[1]))
    return y


def _hidden(fields: dict, params, ids, length):
    """The final-normed hidden states ``[S, D]`` of the token ids ``[S]``,
    and ``route_flips``: over the expert layers, the tokens before ``length``
    whose chosen set changes under the program's router product."""
    f, eps = fields, fields["rms_norm_eps"]
    nd = f["first_k_dense_replace"]
    rope = yarn(f)
    S = ids.shape[0]
    x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
    for i in range(nd):
        lp = _f32(jax.tree_util.tree_map(lambda a: a[i], params["dense"]))
        x = _attention(x, lp, f, rope)
        x = x + _swiglu(_rms_norm(x, lp["ln2"], eps), lp["w_gate"],
                        lp["w_up"], lp["w_down"])
    stacks = tuple(params["moe"][n] for n in ("w_gate", "w_up", "w_down"))
    rest = {n: w for n, w in params["moe"].items()
            if n not in ("w_gate", "w_up", "w_down")}
    experts = _experts_by_token if S <= BY_TOKEN_MAX else _experts_by_expert

    def layer(x, at):
        l, raw = at
        lp = _f32(raw)
        x = _attention(x, lp, f, rope)
        h = _rms_norm(x, lp["ln2"], eps)
        p, chosen = _route(h, lp["router"], f)
        # the routing under the program's product: operands in the weights'
        # dtype, float32 accumulation
        _, theirs = _choose(
            jnp.dot(h.astype(raw["router"].dtype), raw["router"],
                    preferred_element_type=jnp.float32), f)
        flipped = jnp.any(jnp.sort(chosen, -1) != jnp.sort(theirs, -1), -1)
        y = _swiglu(h, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) \
            + experts(h, p, _local(f, chosen), stacks, l)
        return x + y, jnp.sum(flipped & (jnp.arange(S) < length))

    x, flips = lax.scan(
        layer, x, (jnp.arange(f["num_hidden_layers"] - nd), rest))
    return (_rms_norm(x, params["norm_f"].astype(jnp.float32), eps),
            jnp.sum(flips))


def forward(fields: dict, params, ids):
    """Logits ``[B, S, V]`` in float32 of the token ids ``[B, S]`` under the
    configuration ``fields`` (a config file's keys): the whole model in one
    call, for sequences of test length."""
    def one(row):
        x, _ = _hidden(fields, params, row, row.shape[0])
        return x @ params["lm_head"].astype(jnp.float32).T

    return jnp.stack([one(row) for row in ids])


def _frozen(fields: dict):
    """The keys of ``fields`` the reference reads, hashable."""
    rs = fields.get("rope_scaling")
    held = fields.get("experts_held")
    return (tuple((k, fields[k]) for k in FIELDS),
            None if not rs else tuple(sorted(rs.items())),
            None if held is None else tuple(held))


def _thawed(frozen) -> dict:
    keys, rs, held = frozen
    return dict(keys, rope_scaling=None if rs is None else dict(rs),
                experts_held=held)


@functools.partial(jax.jit, static_argnums=0)
def _hidden_jit(frozen, params, ids, length):
    return _hidden(_thawed(frozen), params, ids, length)


@functools.partial(jax.jit, static_argnums=3)
def _head_jit(x, head, start, rows):
    block = lax.dynamic_slice_in_dim(head, start, rows, 0)
    return x @ block.astype(jnp.float32).T


def _run(fields: dict, params, row: list):
    """Reference logits ``[len, V]`` (numpy) of one token row and its
    ``route_flips``, the row padded on the right to a multiple of ``PAD_TO``:
    a causal model cannot see the padding from the left.  The head runs a
    block of the vocabulary at a time and the logits are assembled on the
    host."""
    ids = np.zeros((-(-len(row) // PAD_TO) * PAD_TO,), np.int32)
    ids[:len(row)] = row
    V = params["lm_head"].shape[0]
    with jax.default_matmul_precision("highest"):
        x, flips = _hidden_jit(_frozen(fields), params, jnp.asarray(ids),
                               jnp.int32(len(row)))
        out = np.concatenate([
            np.asarray(_head_jit(x, params["lm_head"], jnp.int32(v0),
                                 min(VOCAB_BLOCK, V - v0)))[:len(row)]
            for v0 in range(0, V, VOCAB_BLOCK)], axis=1)
    return out, int(flips)


def logits(fields: dict, params, rows: list) -> list:
    """Reference logits of each token row, one row at a time."""
    return [_run(fields, params, row)[0] for row in rows]


# -- what the engine served, against the reference --------------------------

def replay_logits(eng, prompt: list, generated: list):
    """Logits of one served request, replayed on the engine's own state
    (``eng.params``, its cache as serving left it, a block table from
    ``eng.kv``): the prompt in ``eng.chunk`` pieces in slot 0 of the
    ``max_running``-wide batch, then ``generated`` one token at a time, the
    way ``step()`` fed them.  The engine's executables return argmaxes only,
    so the logits come from the same ``forward_paged`` under a jit of the
    benchmark's, which hands back slot 0's fed rows and nothing else of the
    ``[R, Tc, V]``."""
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
    ``token_gap_sigma``: over every served token, how far the reference's
    logit of that token trails the reference's best, in standard deviations
    of that row of logits, teacher forced on the engine's own stream (the
    worst one).  ``logits_rel_err``: ``||served - ref|| / ||ref||`` over the
    logits of the request with the most tokens, replayed on the live engine:
    the absorbed attention on the paged latents, the sorted grouped experts
    and bfloat16 everywhere against materialised heads, a loop over experts
    and float32.  ``route_flip_share``: the share of (token, expert layer)
    pairs of the served rows whose chosen experts the program's router
    product changes on the reference's own activations (``route_flips``);
    logged, no limit."""
    ref, flips, routed = [], 0, 0
    layers = fields["num_hidden_layers"] - fields["first_k_dense_replace"]
    for prompt, out in served:
        rows, n = _run(fields, params, prompt + out[:-1])
        ref.append(rows)
        flips += n
        routed += len(rows) * layers
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
