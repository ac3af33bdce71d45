"""Jamba: a decoder whose layers are Mamba-1 mixers with an attention layer
every ``attn_layer_period`` (the public ``JambaForCausalLM``; dense SwiGLU in
every layer, no positional encoding, tied head).

Layer ``i`` is attention where ``i % attn_layer_period == attn_layer_offset``
and a Mamba mixer otherwise.  With D the hidden size, E = expand x D, N the
state size, K the convolution width and r the dt rank, a Mamba layer on
``u [T, D]`` is::

    [x, z] = RMSNorm(u) W_in                                (D -> 2E)
    x_t   <- silu(b_conv + sum_k w_conv[k] * x_{t-K+1+k})   causal, depthwise
    [dt, B, C] = x W_x                                      (E -> r + 2N)
    Delta = softplus(RMSNorm(dt) W_dt + b_dt)               [T, E]
    S_t   = exp(Delta_t (x) A) * S_{t-1} + (Delta_t * x_t) (x) RMSNorm(B_t)
    y_t   = S_t RMSNorm(C_t) + D_skip * x_t                 A = -exp(A_log)
    u    <- u + (y * silu(z)) W_out                         (E -> D)

with ``S`` in float32, then ``u <- u + SwiGLU(RMSNorm(u))`` as in every
layer.  An attention layer is causal softmax attention of
``num_attention_heads`` query heads over ``num_key_value_heads`` K/V heads
without any positional term.

Parameters: ``embed [V, D]``, ``norm_f [D]``, ``mamba`` (every leaf stacked
over the Mamba layers) and ``attn`` (stacked over the attention layers),
both holding their layers' ``ln2 / w_gate / w_up / w_down`` too.  What has a
state axis keeps it second to last (``A_log [M, N, E]``, ``conv_w [M, K,
E]``): E fills the 128 lanes of a TPU tile, N or K the sublanes.

Serving (``SERVING``, the protocol ``serving.LLMEngine`` asks a
configuration for): the cache is a dict of the two attention layers' page
pools ``k_pages / v_pages [A, nkv, P, page, d]``, written and read exactly
as Llama's, and the recurrent state of every engine slot, ``conv [M, K-1, R,
E]`` (the last K-1 inputs of the convolution) and ``ssm [M, N, R, E]``
float32.  The state belongs to a slot, not to pages: see ``forward_paged``.
"""
from __future__ import annotations

import dataclasses
import math
import types
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from .llama import _dense_mlp, _rms_norm
from .step_layout import StepLayout

__all__ = ["JambaConfig", "PRESETS", "preset", "config_from_fields",
           "init_params", "param_count", "forward_pure", "forward_paged",
           "init_cache", "cache_bytes", "step_counts", "SERVING"]


@dataclasses.dataclass
class JambaConfig:
    """Field names are those of the public ``config.json``; the defaults
    are AI21-Jamba2-3B."""
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def attn_layers(self) -> tuple:
        return tuple(i for i in range(self.num_hidden_layers)
                     if i % self.attn_layer_period == self.attn_layer_offset)

    @property
    def num_mamba_layers(self) -> int:
        return self.num_hidden_layers - len(self.attn_layers)

    def layer_runs(self) -> list:
        """The layers in order as ``("mamba", lo, hi)`` (Mamba layers
        ``lo..hi`` of the Mamba stack, consecutive in the model) and
        ``("attn", a)`` (attention layer ``a`` of the attention stack)."""
        runs, lo, hi = [], 0, 0
        for a, i in enumerate(self.attn_layers):
            hi = i - a
            runs += [("mamba", lo, hi)] * (hi > lo) + [("attn", a)]
            lo = hi
        last = self.num_mamba_layers
        return runs + [("mamba", lo, last)] * (last > lo)

    @property
    def serving(self):
        return SERVING


PRESETS: Dict[str, Dict[str, Any]] = {
    "jamba2-3b": {},
    # six layers, the third one attention; for the CPU tests
    "jamba-debug": dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                        num_hidden_layers=6, num_attention_heads=4,
                        num_key_value_heads=1, attn_layer_period=6,
                        attn_layer_offset=2, mamba_dt_rank=4,
                        max_position_embeddings=512),
}


def preset(name: str, **overrides) -> JambaConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown jamba preset {name!r}; available: "
                       f"{sorted(PRESETS)}")
    return JambaConfig(**dict(PRESETS[name], **overrides))


def config_from_fields(fields: dict) -> JambaConfig:
    """A ``JambaConfig`` from a ``config.json``-shaped dict: every key that
    is a field, ``dtype`` by name; other keys are not this model's."""
    names = {f.name for f in dataclasses.fields(JambaConfig)}
    kw = {k: v for k, v in fields.items() if k in names}
    kw["dtype"] = jnp.dtype(kw.get("dtype", "bfloat16")).type
    return JambaConfig(**kw)


def param_count(cfg: JambaConfig) -> int:
    D, I, E = cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner
    N, K, r = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    mlp_and_norms = 3 * D * I + 2 * D
    mixer = (2 * D * E + K * E + E + E * (r + 2 * N) + r * E + E
             + N * E + E + E * D + r + 2 * N)
    qkv = D * cfg.head_dim * (cfg.num_attention_heads
                              + 2 * cfg.num_key_value_heads)
    attn = qkv + cfg.num_attention_heads * cfg.head_dim * D
    return (cfg.num_mamba_layers * (mixer + mlp_and_norms)
            + len(cfg.attn_layers) * (attn + mlp_and_norms)
            + cfg.vocab_size * D + D)


def init_params(cfg: JambaConfig, key) -> Dict[str, Any]:
    """Seeded weights: normal(0, 0.02) matrices, and Mamba's standard
    start for the recurrence — ``A_log = log(1..N)``, ``D_skip = 1``, a dt
    bias that is softplus^-1 of step sizes log-uniform in [1e-3, 1e-1] and
    a dt projection uniform in +-r^-0.5 — so that the state neither dies
    nor saturates within a request."""
    D, I, E, V = (cfg.hidden_size, cfg.intermediate_size, cfg.mamba_inner,
                  cfg.vocab_size)
    N, K, r = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank
    M, A = cfg.num_mamba_layers, len(cfg.attn_layers)
    Q, KV = (cfg.num_attention_heads * cfg.head_dim,
             cfg.num_key_value_heads * cfg.head_dim)
    k = iter(jax.random.split(key, 20))

    def normal(shape):
        return (jax.random.normal(next(k), shape, jnp.float32)
                * 0.02).astype(cfg.dtype)

    def ones(*shape):
        return jnp.ones(shape, cfg.dtype)

    def mlp(n):
        return {"ln2": ones(n, D), "w_gate": normal((n, D, I)),
                "w_up": normal((n, D, I)), "w_down": normal((n, I, D))}

    dt = jnp.exp(jax.random.uniform(next(k), (M, E), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    mamba = {
        "ln1": ones(M, D), "w_in": normal((M, D, 2 * E)),
        "conv_w": (jax.random.uniform(next(k), (M, K, E), jnp.float32, -1, 1)
                   / math.sqrt(K)).astype(cfg.dtype),
        "conv_b": jnp.zeros((M, E), cfg.dtype),
        "w_x": normal((M, E, r + 2 * N)),
        "dt_norm": ones(M, r), "b_norm": ones(M, N), "c_norm": ones(M, N),
        "w_dt": (jax.random.uniform(next(k), (M, r, E), jnp.float32, -1, 1)
                 / math.sqrt(r)).astype(cfg.dtype),
        "b_dt": (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype),
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32))[None, :, None],
            (M, N, E)).astype(cfg.dtype),
        "D_skip": ones(M, E), "w_out": normal((M, E, D)), **mlp(M)}
    attn = {"ln1": ones(A, D), "wq": normal((A, D, Q)),
            "wk": normal((A, D, KV)), "wv": normal((A, D, KV)),
            "wo": normal((A, Q, D)), **mlp(A)}
    return {"embed": normal((V, D)), "mamba": mamba, "attn": attn,
            "norm_f": ones(D)}


# ---------------------------------------------------------------------------
# the Mamba mixer on a ragged batch of chunks, with carried state
# ---------------------------------------------------------------------------

def _ssm_conv(lp, x, conv, l, q_lens, fresh, lay):
    """The causal depthwise convolution of the flat tokens ``x [T, E]`` of
    the step ``lay``, each row's chunk continued from the K-1 inputs before
    it in layer ``l`` of the stack ``conv [M, K-1, R, E]`` (from zeros for a
    ``fresh`` row).  Returns the activations ``[T, E]`` float32 and the
    stack with the last K-1 REAL inputs of each row in layer ``l``, so
    padding never enters the state and a row with ``q_lens == 0`` keeps its
    own.

    A row's window is its carried inputs, then its chunk: tap j of the token
    at position t reads entry ``t + j``, which is the flat token ``K-1-j``
    before it where that is still in the chunk and the row's carried input
    otherwise, by select; its new carried inputs are entries ``q_lens ..
    q_lens + K-2``.  No array here has a padded row's positions.  The scope
    ``ssm_conv`` holds everything that touches the state (its slice out of
    the stack, the reset, the update, the write-back); the two moves between
    a per-row array and the flat batch (each token's row's carried inputs,
    each row's last tokens) are ``StepLayout``'s, under ``step_layout``."""
    K = conv.shape[1] + 1
    T = x.shape[0]
    f32 = jnp.float32
    with jax.named_scope("ssm_conv"):
        # the reset on the state itself, once: the gathers and the update
        # read the one array (folded into their selects XLA writes it
        # twice); as its K-1 entries, sliced under this scope
        c = list(jnp.where(fresh[None, :, None], 0, _layer_at(conv, l)))
    # one gather an entry, each of ``[R, E]``: gathered as one ``[K-1, R,
    # E]`` array by its middle axis, XLA re-lays the whole stack of states
    # out around the layer loop, 0.1 GB moved twice a step
    carried = [lay.of_rows(c[m]) for m in range(K - 1)]     # K-1 x [T, E]
    last = lay.last_tokens(x, K - 1)                         # [K-1, R, E]
    with jax.named_scope("ssm_conv"):
        w = lp["conv_w"].astype(f32)
        taps = []
        for j in range(K):
            back = K - 1 - j
            tap = jnp.pad(x, ((back, 0), (0, 0)))[:T]        # x[i - back]
            for m in range(j, K - 1):                        # t + j == m
                tap = jnp.where((lay.pos == m - j)[:, None], carried[m], tap)
            taps.append(w[j] * tap.astype(f32))
        out = jax.nn.silu(lp["conv_b"].astype(f32) + sum(taps))
        new = []
        for m in range(K - 1):
            kept = last[m]                  # a flat token from entry K-1 on
            for q in range(K - 1 - m):
                kept = jnp.where((q_lens == q)[:, None], c[q + m], kept)
            new.append(kept)
        conv = lax.dynamic_update_index_in_dim(conv, jnp.stack(new, 0), l, 0)
    return out, conv


def _layer_at(stack, l):
    """Layer ``l`` (traced) of a stack of per-layer leaves: the slice a
    ``lax.scan`` over the stack would take, from the whole stack, so that a
    run of layers scans the one stack by index and copies none of it."""
    return jax.tree_util.tree_map(
        lambda w: lax.dynamic_index_in_dim(w, l, 0, keepdims=False), stack)


def _mixer_core(lp, x, z, conv, ssm, l, q_lens, fresh, lay, dt_b_c):
    """What every Mamba-1 mixer of this repo does between ``w_in`` and
    ``w_out``, on the flat tokens of the step ``lay``: the convolution of
    ``x [T, E]``, ``dt_b_c(x)`` (the caller's ``w_x``, ``dt`` chain and
    norms: ``dt [T, E]`` after softplus and ``B, C [T, N]``, float32), the
    selective scan, ``D_skip`` and the gate ``z [T, E]``.  The state of each
    row is read from and written back into layer ``l`` of the stacks ``conv
    [M, K-1, R, E]`` and ``ssm [M, N, R, E]`` float32; a row whose chunk is
    ``fresh`` starts from zero state, a position ``t >= q_lens[r]``
    advances neither.  Returns the gated ``[T, E]`` in ``z``'s dtype, the
    scan's output with its skip ``m [T, E]`` float32, and both stacks.

    Everything stays flat: the convolution and the scan read a row's fed
    tokens where they lie in the batch (``_ssm_conv``;
    ``pallas_ops.selective_scan``, on the TPU a kernel that updates the
    stack in place and walks only the live positions).  The scopes
    ``ssm_conv`` and ``ssm_scan`` hold everything that touches their state
    and nothing else: its slice out of the stack, the reset, the update and
    the write-back, so a reader of the scope's time times every byte that
    ``benchmark/kernel_costs_ssm.py`` counts; the convolution's two moves
    between a per-row array and the flat batch are ``step_layout``'s, beside
    ``ssm_conv`` and not inside it.  The scan writes ``y`` at the
    fed tokens alone; the select that makes every other position zero
    (``StepLayout``'s contract) rides in the gate's pass."""
    from ..ops.pallas_ops import selective_scan
    f32 = jnp.float32
    x, conv = _ssm_conv(lp, x, conv, l, q_lens, fresh, lay)  # x float32
    with jax.named_scope("ssm_proj"):
        dt, Bm, Cm = dt_b_c(x)
    with jax.named_scope("ssm_scan"):
        A = -jnp.exp(lp["A_log"].astype(f32))
        y, ssm = selective_scan(ssm, dt, x, Bm, Cm, A, q_lens, lay.start,
                                fresh, Tc=lay.Tc, layer=l)
    with jax.named_scope("ssm_proj"):
        m = jnp.where(lay.valid[:, None],
                      y + lp["D_skip"].astype(f32) * x, 0.0)
        gated = (m * jax.nn.silu(z.astype(f32))).astype(z.dtype)
    return gated, m, conv, ssm


def _mamba_mixer(cfg, lp, h, conv, ssm, l, q_lens, fresh, lay):
    """Mixer ``l`` on the flat tokens ``h [T, D]`` of the step ``lay`` with
    the state of each row in layer ``l`` of the stacks ``conv`` and ``ssm``
    (``_mixer_core``, which ``phi4flash._mamba_layer`` shares).  Returns
    the mixer's output ``[T, D]`` and both stacks.  What is Jamba's own:
    the RMSNorm before ``w_in`` and those of ``dt``, ``B`` and ``C``.

    The mixer has four names in a profile: ``ssm_proj`` around its three
    per-token stretches (norm and ``w_in``; ``w_x``, the ``dt`` chain and
    the B and C norms; the gate, ``D_skip`` and ``w_out``), ``ssm_conv``,
    ``ssm_scan``, and ``step_layout`` (each token's row's carried inputs
    and each row's last tokens, the two places where the convolution goes
    between a per-row array and the flat batch); what is left under
    ``mamba`` and under none of the four is the caller's residual add."""
    N, r, eps = cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.rms_norm_eps
    f32 = jnp.float32

    def dt_b_c(x):
        dt, Bm, Cm = jnp.split(x.astype(h.dtype) @ lp["w_x"], [r, r + N],
                               axis=-1)
        dt = _rms_norm(dt, lp["dt_norm"], eps) @ lp["w_dt"]
        return (jax.nn.softplus(dt.astype(f32) + lp["b_dt"].astype(f32)),
                _rms_norm(Bm, lp["b_norm"], eps).astype(f32),
                _rms_norm(Cm, lp["c_norm"], eps).astype(f32))

    with jax.named_scope("ssm_proj"):
        x, z = jnp.split(_rms_norm(h, lp["ln1"], eps) @ lp["w_in"], 2,
                         axis=-1)
    gated, _, conv, ssm = _mixer_core(lp, x, z, conv, ssm, l, q_lens, fresh,
                                      lay, dt_b_c)
    with jax.named_scope("ssm_proj"):
        out = gated @ lp["w_out"]
    return out, conv, ssm


def _mamba_run(cfg, stack, lo, hi, h, conv, ssm, q_lens, fresh, lay):
    """Mamba layers ``lo..hi`` of the stack as one scan.  ``conv [M, K-1, R,
    E]`` and ``ssm [M, N, R, E]`` go round in the carry and are updated in
    place at the layer's index."""
    def body(carry, l):
        h, conv, ssm = carry
        lp = _layer_at(stack, l)
        with jax.named_scope("mamba"):
            out, conv, ssm = _mamba_mixer(cfg, lp, h, conv, ssm, l, q_lens,
                                          fresh, lay)
            h = h + out
        with jax.named_scope("mlp"):
            h = h + _dense_mlp(lp, _rms_norm(h, lp["ln2"], cfg.rms_norm_eps))
        return (h, conv, ssm), None

    (h, conv, ssm), _ = lax.scan(body, (h, conv, ssm),
                                 jnp.arange(lo, hi, dtype=jnp.int32))
    return h, conv, ssm


def _forward(cfg, params, tokens, conv, ssm, q_lens, fresh, attend, lay):
    """Embedding, the layers in order, final norm, tied head, on the flat
    tokens ``[T, ...]`` of the step ``lay`` (``step_layout.StepLayout``);
    returns the logits ``[T, V]``.  ``attend(a, q, k, v)`` is attention
    layer ``a``'s mixing of ``q [R, Tc, nh, d]`` with ``k, v [R, Tc, nkv,
    d]`` and whatever came before them."""
    R, Tc, T = lay.R, lay.Tc, lay.T
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    with jax.named_scope("embed"):
        h = jnp.take(params["embed"], lay.flat(tokens), axis=0)
    with jax.named_scope("layers"):
        for run in cfg.layer_runs():
            if run[0] == "mamba":
                h, conv, ssm = _mamba_run(cfg, params["mamba"], run[1],
                                          run[2], h, conv, ssm, q_lens, fresh,
                                          lay)
                continue
            lp = jax.tree_util.tree_map(lambda w: w[run[1]], params["attn"])
            with jax.named_scope("attn"):
                xn = _rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
                out = attend(
                    run[1], lay.rows((xn @ lp["wq"]).reshape(T, nh, d)),
                    lay.rows((xn @ lp["wk"]).reshape(T, nkv, d)),
                    lay.rows((xn @ lp["wv"]).reshape(T, nkv, d)))
                out = lay.flat(out.reshape(R, Tc, nh * d))
                h = h + out.astype(h.dtype) @ lp["wo"]
            with jax.named_scope("mlp"):
                h = h + _dense_mlp(
                    lp, _rms_norm(h, lp["ln2"], cfg.rms_norm_eps))
    with jax.named_scope("lm_head"):
        h = _rms_norm(h, params["norm_f"], cfg.rms_norm_eps)
        logits = jnp.einsum("td,vd->tv", h, params["embed"],
                            preferred_element_type=jnp.float32)
    return logits, conv, ssm


def _fresh_state(cfg, rows: int):
    E, f32 = cfg.mamba_inner, jnp.float32
    M = cfg.num_mamba_layers
    return (jnp.zeros((M, cfg.mamba_d_conv - 1, rows, E), cfg.dtype),
            jnp.zeros((M, cfg.mamba_d_state, rows, E), f32))


def forward_pure(cfg: JambaConfig, params, input_ids):
    """Logits ``[B, S, V]`` float32 of whole sequences ``[B, S]``: no
    cache, plain causal attention.  The recurrence is unrolled over S
    (``pallas_ops._ssm_scan_jnp``: a chunk of S positions is no serve
    step's), so this is for sequences of test length."""
    B, S = input_ids.shape
    rep = cfg.num_attention_heads // cfg.num_key_value_heads
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attend(a, q, k, v):
        k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(
            jnp.where(causal, s / math.sqrt(cfg.head_dim), -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)

    conv, ssm = _fresh_state(cfg, B)
    q_lens = jnp.full((B,), S, jnp.int32)
    lay = StepLayout(q_lens, S)
    return lay.rows(_forward(cfg, params, input_ids, conv, ssm, q_lens,
                             jnp.ones((B,), bool), attend, lay)[0])


# ---------------------------------------------------------------------------
# serving: the engine's protocol
# ---------------------------------------------------------------------------

def init_cache(cfg: JambaConfig, slots: int, num_pages: int, page_size: int,
               kv_dtype):
    """The cache of an engine with ``slots`` rows: zeroed page pools of the
    attention layers and zero recurrent state of every slot."""
    if jnp.dtype(kv_dtype).itemsize < 2:
        raise ValueError(
            f"kv_dtype {jnp.dtype(kv_dtype)} pages need the per-page scale "
            "pools that only models/llama.py's step writes")
    pool = (len(cfg.attn_layers), cfg.num_key_value_heads, num_pages,
            page_size, cfg.head_dim)
    conv, ssm = _fresh_state(cfg, slots)
    return {"k_pages": jnp.zeros(pool, kv_dtype),
            "v_pages": jnp.zeros(pool, kv_dtype), "conv": conv, "ssm": ssm}


def cache_bytes(cfg: JambaConfig, kv_dtype_bytes: int = 2,
                page_size: int = 128) -> dict:
    """What the cache costs: K/V bytes a token (attention layers only),
    scale bytes a page (none: no quantized pages) and recurrent-state bytes
    a slot, whatever the length of the request in it."""
    E = cfg.mamba_inner
    return {
        "per_token": (2 * len(cfg.attn_layers) * cfg.num_key_value_heads
                      * cfg.head_dim * kv_dtype_bytes),
        "scales_per_page": 0,
        "per_slot": cfg.num_mamba_layers * E * (
            cfg.mamba_d_state * 4
            + (cfg.mamba_d_conv - 1) * jnp.dtype(cfg.dtype).itemsize)}


def step_counts(cfg: JambaConfig, seq_lens, q_lens) -> dict:
    """What one step's Mamba layers walk, a layer, from the host's arrays:
    ``scan_positions``, the row-positions of the selective scan (8 x the
    longest chunk of every group of 8 rows; ``R x Tc`` padded).  The engine
    puts it on its ``serve/engine_step`` span and sums it."""
    from ..ops.pallas_ops import scan_positions
    del cfg, seq_lens
    return {"scan_positions": scan_positions(q_lens)}


def forward_paged(cfg: JambaConfig, params, tokens, cache, block_tables,
                  seq_lens, q_lens, step_tokens=None):
    """The engine's step: ragged mixed prefill and decode rows ``tokens [R,
    Tc]`` (row r feeds ``tokens[r, :q_lens[r]]`` and then holds ``seq_lens[r]``
    tokens) over ``cache`` (``init_cache``).  Returns ``(logits [R, Tc, V]
    float32, cache)``; logits of padding positions are garbage.  With
    ``step_tokens = T`` the program computes ``T`` positions in place of
    ``R x Tc`` and returns the logits flat, ``[T, V]`` (``StepLayout``); the
    caller guarantees ``sum(q_lens) <= T``.

    Attention layers write and read their page pools through the block
    table with the kernels Llama's step uses (``paged_kv_write``,
    ``ragged_paged_attention``).  The recurrent state is per ROW (engine
    slot).  Row r's state is zeroed inside this step where its chunk starts
    at position 0 (``seq_lens[r] == q_lens[r] > 0``): that is all admission
    into a used slot, preemption-replay and a rebuilt engine need, since
    each feeds a request from its first token.  Positions ``t >= q_lens[r]``
    advance neither state, and a row with ``q_lens[r] == 0`` comes back as it
    was.  A step that skipped tokens (a prefix-cache hit) or fed tokens to
    take back (a rejected draft) would leave the state wrong, which is why
    ``recurrent_state`` makes the engine refuse both."""
    from ..ops.pallas_ops import paged_kv_write, ragged_paged_attention
    R, Tc = tokens.shape
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    rep = nh // nkv
    pools = [cache["k_pages"], cache["v_pages"]]

    def attend(a, q, k, v):
        with jax.named_scope("kv_write"):
            pools[:] = paged_kv_write(*pools, k, v, block_tables, seq_lens,
                                      q_lens, layer=a)
        # the kernel's layout [R, nkv, Tc*rep, d], as in llama.forward_paged
        qk = q.reshape(R, Tc, nkv, rep, d).transpose(
            0, 2, 1, 3, 4).reshape(R, nkv, Tc * rep, d)
        out = ragged_paged_attention(qk, *pools, block_tables, seq_lens,
                                     q_lens, rep=rep, layer=a)
        return out.reshape(R, nkv, Tc, rep, d).transpose(0, 2, 1, 3, 4)

    fresh = (q_lens > 0) & (seq_lens == q_lens)
    lay = StepLayout(q_lens, Tc, step_tokens)
    logits, conv, ssm = _forward(cfg, params, tokens, cache["conv"],
                                 cache["ssm"], q_lens, fresh, attend, lay)
    return (logits if lay.compact else lay.rows(logits)), {
        "k_pages": pools[0], "v_pages": pools[1], "conv": conv, "ssm": ssm}


# what serving.LLMEngine asks a configuration for (``cfg.serving``)
SERVING = types.SimpleNamespace(
    forward_paged=forward_paged, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    prepare_params=lambda cfg, params: params,   # no weight is converted
    step_counts=step_counts, recurrent_state=True)
