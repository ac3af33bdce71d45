"""Llama model family — the flagship LLM config (BASELINE.json #4).

Two faces:

1. `LlamaForCausalLM` — an eager `nn.Layer` built from the TP layer library
   (fleet mp_layers), usable like the reference PaddleNLP model: forward,
   loss, generate-one-step. Capability parity surface.

2. The functional core (`init_params` / `forward_pure` /
   `build_train_step`) — pure jnp functions over a stacked-parameter
   pytree, which is what the 4-D+ parallel trainer, the pipeline schedule,
   `__graft_entry__.dryrun_multichip` and `benchmark/run.py` drive. This is the
   TPU-native replacement for fleet's PipelineLayer/LayerDesc partitioning
   (reference: fleet/meta_parallel/parallel_layers/pp_layers.py:209) —
   layers are stacked along a leading axis and sharded/scanned rather than
   partitioned into per-rank Python objects.

Parallelism mapping (SURVEY.md §7):
  dp      — batch axis sharding (+ ZeRO: optimizer state sharded on dp)
  mp (tp) — megatron column/row specs on attention + MLP weights; vocab-
            parallel embedding & lm_head; sequence-parallel activations
            ride the same axis between blocks
  pp      — layer-stack axis sharded over 'pp'; GPipe/1F1B microbatch
            schedule via shard_map + ppermute (distributed/pipeline.py)
  ep      — MoE expert axis sharded over 'dp' (GShard-style dense dispatch,
            reference analog: incubate/distributed/models/moe/moe_layer.py)
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import math
import types
from typing import Any, Dict, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P, NamedSharding

from .decoding import GenerationMixin
from .step_layout import StepLayout

__all__ = ["LlamaConfig", "LlamaForCausalLM", "init_params", "forward_pure",
           "forward_with_cache", "forward_paged", "build_train_step",
           "param_specs", "PRESETS", "preset", "quantize_params", "SERVING"]


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    # MoE (config #5 — DeepSeekMoE/Qwen-MoE shape)
    moe_num_experts: int = 0          # 0 => dense FFN
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # training: each layer rematerialised in the backward pass, keeping of
    # what that pass reads (SAVED_NAMES) as much as the device's memory
    # holds (saved_residuals: the train step decides, from the shapes)
    use_remat: bool = True
    # fused decoder-block Pallas kernels (ops.pallas_ops
    # fused_attention_block / fused_mlp_block) — same math as the unfused
    # composition on the flash kernels, at 1.7x its step time on a v5e
    # (PERF.md section 6, PR 36): "on" = wherever the kernels can run
    # (_fused_block_modes; incl. the interpreter — what parity tests
    # use), "off" = the unfused composition
    fused_blocks: str = "off"
    # int8 weight path for serving (quantize_params + the pallas_ops
    # int8_matmul kernels): a different model, so never chosen from the
    # platform — "on" quantizes at engine build everywhere (CPU runs the
    # jnp oracle, the same integer math), "off" serves dense weights
    quantized: str = "off"

    def __post_init__(self):
        assert self.fused_blocks in ("on", "off"), \
            f"fused_blocks must be 'on' or 'off', got " \
            f"{self.fused_blocks!r}"
        assert self.quantized in ("on", "off"), \
            f"quantized must be 'on' or 'off', got {self.quantized!r}"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def serving(self):
        """What ``serving.LLMEngine`` asks a configuration for."""
        return SERVING


# Named shapes for tools (tools/pod_report.py, chip_smoke.py, tests). The
# LlamaConfig defaults ARE the 7B shape, so llama7b overrides nothing.
PRESETS: Dict[str, Dict[str, Any]] = {
    "llama7b": {},
    "llama1b": dict(hidden_size=2048, intermediate_size=5504,
                    num_hidden_layers=16, num_attention_heads=16,
                    num_key_value_heads=16),
    "llama-debug": dict(vocab_size=256, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=4,
                        max_position_embeddings=256),
}


def preset(name: str, **overrides) -> LlamaConfig:
    """LlamaConfig from a named preset, with field overrides on top."""
    if name not in PRESETS:
        raise KeyError(f"unknown llama preset {name!r}; "
                       f"available: {sorted(PRESETS)}")
    kw = dict(PRESETS[name])
    kw.update(overrides)
    return LlamaConfig(**kw)


def _split_key(key, n):
    return list(jax.random.split(key, n))


def init_params(cfg: LlamaConfig, key) -> Dict[str, Any]:
    """Stacked parameter pytree. Layer axis L leads every per-layer array."""
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    V = cfg.vocab_size
    KV = cfg.num_key_value_heads * cfg.head_dim
    k = iter(_split_key(key, 16))
    std = 0.02

    def init(k_, shape):
        return (jax.random.normal(k_, shape, jnp.float32) * std).astype(
            cfg.dtype)

    params = {
        "embed": init(next(k), (V, H)),
        "layers": {
            "ln1": jnp.ones((L, H), cfg.dtype),
            "wq": init(next(k), (L, H, H)),
            "wk": init(next(k), (L, H, KV)),
            "wv": init(next(k), (L, H, KV)),
            "wo": init(next(k), (L, H, H)),
            "ln2": jnp.ones((L, H), cfg.dtype),
        },
        "norm_f": jnp.ones((H,), cfg.dtype),
        "lm_head": init(next(k), (H, V)),
    }
    if cfg.moe_num_experts > 0:
        E = cfg.moe_num_experts
        params["layers"]["router"] = init(next(k), (L, H, E)).astype(
            jnp.float32)
        params["layers"]["w_gate"] = init(next(k), (L, E, H, I))
        params["layers"]["w_up"] = init(next(k), (L, E, H, I))
        params["layers"]["w_down"] = init(next(k), (L, E, I, H))
    else:
        params["layers"]["w_gate"] = init(next(k), (L, H, I))
        params["layers"]["w_up"] = init(next(k), (L, H, I))
        params["layers"]["w_down"] = init(next(k), (L, I, H))
    return params


def param_specs(cfg: LlamaConfig) -> Dict[str, Any]:
    """GSPMD PartitionSpecs — the Column/RowParallel + vocab-parallel and
    expert-parallel placement contract (mp_layers.py analog). Leading layer
    axis is sharded over 'pp' (the pipeline placement)."""
    moe = cfg.moe_num_experts > 0
    layers = {
        "ln1": P("pp", None),
        "wq": P("pp", None, "mp"),     # column parallel
        "wk": P("pp", None, "mp"),
        "wv": P("pp", None, "mp"),
        "wo": P("pp", "mp", None),     # row parallel
        "ln2": P("pp", None),
    }
    if moe:
        layers.update({
            "router": P("pp", None, None),
            "w_gate": P("pp", "dp", None, "mp"),   # experts over dp (=ep)
            "w_up": P("pp", "dp", None, "mp"),
            "w_down": P("pp", "dp", "mp", None),
        })
    else:
        layers.update({
            "w_gate": P("pp", None, "mp"),
            "w_up": P("pp", None, "mp"),
            "w_down": P("pp", "mp", None),
        })
    return {
        "embed": P("mp", None),        # vocab parallel
        "layers": layers,
        "norm_f": P(None),
        "lm_head": P(None, "mp"),      # column parallel (vocab out)
    }


# ---------------------------------------------------------------------------
# pure forward pieces
# ---------------------------------------------------------------------------

def _rope_tables(cfg: LlamaConfig, seq_len: int):
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (cfg.rope_theta
                      ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)                      # [S, half]
    emb = jnp.concatenate([freqs, freqs], axis=-1)      # [S, D]
    return jnp.sin(emb), jnp.cos(emb)


def _apply_rope(x, sin, cos):
    # x: [B, S, H, D] (neox style)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    sin_ = sin[None, :, None, :].astype(x.dtype)
    cos_ = cos[None, :, None, :].astype(x.dtype)
    return x * cos_ + rot * sin_


def _rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(ms + eps)).astype(x.dtype) * w


def _batch_axes(axes):
    """The batch-dim entry of a PartitionSpec over mesh ``axes``
    (HybridTopology.batch_axes: dp, plus the ZeRO axis when carved)."""
    return tuple(a for a in ("dp", "sharding") if a in axes) or None


def _per_device(fn, args, replicated=(), heads_dim=None):
    """``fn(*args, *replicated)`` holding Pallas kernels: called directly
    where the program is unpartitioned, per device under one shard_map
    over every mesh axis where it is partitioned (pallas_ops.kernel_axes)
    — ``args`` split over the batch axes on dim 0 and, with ``heads_dim``,
    over 'mp' on that dim; ``replicated`` whole on every device."""
    from ..ops import pallas_ops
    axes = pallas_ops.kernel_axes()
    if not axes:
        return fn(*args, *replicated)

    def spec(a):
        dims = [None] * a.ndim
        dims[0] = _batch_axes(axes)
        if heads_dim is not None and "mp" in axes:
            dims[heads_dim] = "mp"
        return P(*dims)

    return jax.shard_map(
        fn, in_specs=tuple(map(spec, args)) + (P(),) * len(replicated),
        out_specs=spec(args[0]), check_vma=False)(*args, *replicated)


def _attention(cfg: LlamaConfig, lp, x, sin, cos, cp_mesh=None,
               cp_axis="sp", cp_axis_level=False):
    B, S, H = x.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    q = _qmm(x, lp["wq"]).reshape(B, S, nh, d)
    k = _qmm(x, lp["wk"]).reshape(B, S, nkv, d)
    v = _qmm(x, lp["wv"]).reshape(B, S, nkv, d)
    q = _apply_rope(q, sin, cos)
    k = _apply_rope(k, sin, cos)
    if cp_axis_level or cp_mesh is not None:
        # the ring has no backward rule of its own, so a rematerialised
        # layer runs it again whatever is kept (no attn_out here); q, k
        # and v kept spare that pass the projections and rotary products
        q, k, v = (checkpoint_name(t, n) for t, n in
                   ((q, "attn_q"), (k, "attn_k"), (v, "attn_v")))
    if cp_axis_level:
        # already inside a shard_map that maps cp_axis (the pipeline's
        # pp x sp region): call the axis-level ring directly — nesting
        # another shard_map here would be illegal
        from ..distributed.sequence_parallel import ring_attention
        out = ring_attention(q, k, v, axis_name=cp_axis)
    elif cp_mesh is not None:
        # context parallel: sequence sharded over cp_axis, K/V blocks
        # rotate the ring (distributed.sequence_parallel) — exact causal
        # attention at O(S/n) memory per device. GQA expansion happens
        # inside the ring's block compute, so only nkv heads rotate.
        from ..distributed.sequence_parallel import ring_attention_sharded
        out = ring_attention_sharded(q, k, v, cp_mesh, cp_axis)
    else:
        if nkv != nh:  # grouped-query attention: repeat kv heads
            rep = nh // nkv
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        # flash attention via Pallas on TPU for qualifying shapes, XLA
        # elsewhere; batch rides dp(+sharding), heads ride mp
        from ..ops import pallas_ops
        if pallas_ops.flash_attention_available(q.shape, q.dtype):
            out = _per_device(pallas_ops.causal_attention, (q, k, v),
                              heads_dim=2)
        else:
            out = pallas_ops.causal_attention(q, k, v)
    return _qmm(out.reshape(B, S, H), lp["wo"])


def _dense_mlp(lp, x):
    gate = jax.nn.silu(checkpoint_name(_qmm(x, lp["w_gate"]), "mlp_gate"))
    up = checkpoint_name(_qmm(x, lp["w_up"]), "mlp_up")
    return _qmm(gate * up, lp["w_down"])


# ---------------------------------------------------------------------------
# int8 weight path (serving): quantize_params + _qmm dispatch
# ---------------------------------------------------------------------------

def _qmm(x, w):
    """x @ w where ``w`` is either a dense array or a quantize_params
    leaf ``{"q": int8 [K, N], "scale": f32 [1, N]}`` — the int8 leaf
    routes through ops.pallas_ops.int8_matmul (Pallas kernel on TPU,
    jnp dequant oracle elsewhere)."""
    if isinstance(w, dict):
        from ..ops.pallas_ops import int8_matmul
        return int8_matmul(x, w["q"], w["scale"])
    return x @ w


# weight leaves quantize_params converts (per-layer stacked [L, K, N]);
# norms, embed and the MoE expert einsum weights stay dense
_QUANT_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def _note_quant_err(name, w, q, scale):
    """Numerics-watchdog gauges (satellite of the int8 path): rms +
    absmax of (dequant - reference) per weight, plus the worst layer
    index for stacked weights — so a bad scale is localized like a NaN.
    All behind FLAGS_tpu_check_nan_inf; zero cost when off."""
    from ..profiler import numerics
    if not numerics.enabled():
        return
    wf = np.asarray(jax.device_get(w)).astype(np.float32)
    deq = (np.asarray(jax.device_get(q)).astype(np.float32)
           * np.asarray(jax.device_get(scale)).astype(np.float32))
    err = deq - wf
    if err.size == 0:
        return
    numerics.note(f"quant_err_rms_{name}",
                  float(np.sqrt(np.mean(err * err))))
    numerics.note(f"quant_err_absmax_{name}", float(np.max(np.abs(err))))
    if err.ndim == 3:  # stacked [L, K, N]: localize the worst layer
        per_layer = np.max(np.abs(err), axis=(1, 2))
        numerics.note(f"quant_err_worst_layer_{name}",
                      float(np.argmax(per_layer)))


def quantize_params(cfg: LlamaConfig, params):
    """PTQ the serving weight path to int8: each matmul weight in
    _QUANT_WEIGHTS (stacked [L, K, N]) plus lm_head becomes a
    ``{"q": int8, "scale": f32}`` leaf via per-output-channel absmax
    (ops.pallas_ops.quantize_int8). lax.scan slices dict leaves along
    the leading L axis like any pytree, so forward bodies see per-layer
    ``{"q": [K, N], "scale": [1, N]}`` and dispatch through _qmm.
    Dense configs only — MoE expert weights ride einsums and stay
    dense. Idempotent (already-quantized leaves pass through)."""
    from ..ops.pallas_ops import quantize_int8
    out = dict(params)
    layers = dict(params["layers"])
    if cfg.moe_num_experts == 0:
        for nm in _QUANT_WEIGHTS:
            w = layers.get(nm)
            if w is None or isinstance(w, dict):
                continue
            q, scale = quantize_int8(w)
            layers[nm] = {"q": q, "scale": scale}
            _note_quant_err(nm, w, q, scale)
    out["layers"] = layers
    head = out.get("lm_head")
    if head is not None and not isinstance(head, dict):
        q, scale = quantize_int8(head)
        out["lm_head"] = {"q": q, "scale": scale}
        _note_quant_err("lm_head", head, q, scale)
    return out


def _moe_mlp(cfg: LlamaConfig, lp, x):
    """GShard top-k MoE with capacity, dense dispatch einsums.

    Reference analog: moe_layer.py:260 MoELayer + global_scatter/gather
    NCCL all-to-all. Here dispatch/combine are einsums against a one-hot
    capacity tensor; with the expert axis of w_* sharded over 'dp', GSPMD
    lowers the token<->expert resharding to the same all-to-all over ICI.
    """
    B, S, H = x.shape
    E, K = cfg.moe_num_experts, cfg.moe_top_k
    T = B * S
    C = max(1, int(cfg.moe_capacity_factor * T * K / E))
    xt = x.reshape(T, H)
    logits = (xt.astype(jnp.float32) @ lp["router"])        # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = lax.top_k(probs, K)               # [T, K]
    gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)
    # position of each (t, k) within its expert's capacity buffer
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.int32)   # [T, K, E]
    flat = onehot.reshape(T * K, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)          # [T, K]
    keep = pos < C
    # dispatch tensor [T, K, E, C]
    disp = (onehot.astype(jnp.bool_)
            & keep[..., None]).astype(x.dtype)[..., None] \
        * jax.nn.one_hot(jnp.where(keep, pos, 0), C, dtype=x.dtype)[
            :, :, None, :]
    combine = disp * gate_vals[..., None, None].astype(x.dtype)
    disp2 = disp.sum(1)                                     # [T, E, C]
    expert_in = jnp.einsum("tec,th->ech", disp2, xt)        # [E, C, H]
    gate = jax.nn.silu(checkpoint_name(
        jnp.einsum("ech,ehi->eci", expert_in, lp["w_gate"]), "mlp_gate"))
    up = checkpoint_name(
        jnp.einsum("ech,ehi->eci", expert_in, lp["w_up"]), "mlp_up")
    expert_out = jnp.einsum("eci,eih->ech", gate * up, lp["w_down"])
    out = jnp.einsum("tkec,ech->th", combine, expert_out)
    # aux load-balancing loss (GShard)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(gate_idx[:, 0], E, dtype=jnp.float32),
                  axis=0)
    aux = E * jnp.sum(me * ce)
    return out.reshape(B, S, H), aux


# ---------------------------------------------------------------------------
# what a layer keeps for its backward pass
# ---------------------------------------------------------------------------

# Checkpoint names of what a rematerialised layer's backward reads and the
# layer's input does not give for free: the flash forward rule's residuals
# (ops.pallas_ops.causal_attention: q, k and v after the rotary product in
# the kernels' layout, the output with its log-sum-exp under one name) and
# the feed-forward's gate and up products. Saving none of them is full
# rematerialisation; saving all of them leaves norms, rotary products and
# the wo matmul to recompute.
SAVED_NAMES = ("attn_out", "attn_q", "attn_k", "attn_v", "mlp_gate",
               "mlp_up")
# the share of the device's memory that the estimate leaves free: what the
# estimate cannot see (the compiler's own scratch, a caller's arrays beside
# the step's) and its own error
_MEMORY_MARGIN = 1 / 16
_LOG = logging.getLogger(__name__)


def _memory_limit(mesh):
    """Bytes a device of ``mesh`` reports room for, asked of one this
    process addresses (another's raises; every host has the same kind);
    None where it reports none (a CPU)."""
    return (mesh.local_devices[0].memory_stats() or {}).get("bytes_limit")


def saved_residuals(cfg: LlamaConfig, tokens: int, seq: int,
                    param_bytes: int, opt_bytes: int, limit, mp: int = 1,
                    names=SAVED_NAMES):
    """(names, their bytes, the step's estimated peak bytes): the longest
    prefix of ``names`` (those of SAVED_NAMES the traced layer has, in
    SAVED_NAMES' order: layer_names) that the layers of a train step can
    keep for the backward pass on a device that holds ``tokens``
    positions of sequences of ``seq``, ``param_bytes`` of parameters and
    ``opt_bytes`` of optimizer state and reports room for ``limit`` bytes
    (None: no limit, keep everything) less _MEMORY_MARGIN. SAVED_NAMES
    stands in the order of what a kept byte spares the backward pass: the
    flash forward (for sequences of half the hidden size or more), then
    a projection with its rotary product and relayout, then a projection.

    The estimate: parameters and optimizer state; every layer's input,
    which the layer scan keeps whatever is saved; what is saved (k and v
    at q's width: grouped heads are repeated before the flash kernels);
    and the larger transient of the two below. Fitted to libtpu's peak
    for the step at hidden 2048 on one v5e (within 0.2% at 16 and 24
    layers); compiled elsewhere it reads from 1.4% low (hidden 4096) to
    9-22% high, the safe side (internlm2-1.8b; four chips): PERF.md
    section 4."""
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    w = jnp.dtype(cfg.dtype).itemsize
    ffn_rows = tokens
    if cfg.moe_num_experts > 0:   # the experts' capacity buffers
        ffn_rows = math.ceil(cfg.moe_capacity_factor * tokens * cfg.moe_top_k)
    proj, ffn = tokens * H * w // mp, ffn_rows * I * w // mp
    layer_bytes = {
        "attn_out": tokens * (H * w + cfg.num_attention_heads * 4) // mp,
        "attn_q": proj, "attn_k": proj, "attn_v": proj,
        "mlp_gate": ffn, "mlp_up": ffn}
    # the head's float32 logits and their cotangent live before the
    # gradients do; one layer's backward (gate, up, their product and the
    # cotangents of the three) lives beside all the gradients, and where
    # the attention is XLA's (q named and no output: a ring, the body
    # that serves without a flash kernel) so do its float32 scores, its
    # probabilities and the cotangents of both
    scores = 0
    if "attn_q" in names and "attn_out" not in names:
        scores = tokens * cfg.num_attention_heads * seq * 4 // mp
    transient = max(2 * tokens * cfg.vocab_size * 4 // mp,
                    param_bytes + 5 * ffn + 4 * scores)
    estimate = param_bytes + opt_bytes + L * tokens * H * w + transient
    room = math.inf if limit is None else limit * (1 - _MEMORY_MARGIN)
    saved, kept = [], 0
    for name in names:
        if estimate + kept + L * layer_bytes[name] > room:
            break
        saved.append(name)
        kept += L * layer_bytes[name]
    return tuple(saved), kept, estimate + kept


def layer_names(cfg: LlamaConfig, x, cp_mesh=None, kernels=True):
    """Those of SAVED_NAMES that ``decoder_layer`` names when traced on
    ``x`` at this point of the trace: the fused blocks name nothing, and
    the attention's output is named by the flash forward rule only: not
    by the ring of a context-parallel plan (``cp_mesh``), nor by the XLA
    body that serves where no Pallas kernel can run (off the chip, or
    ``kernels`` False for a region that is manual over a part of the
    mesh)."""
    from ..ops import pallas_ops
    fused_attn, fused_mlp = (False, False) if not kernels else \
        _fused_block_modes(cfg, x, cp_mesh, False)
    flash = (kernels and cp_mesh is None
             and pallas_ops.flash_attention_available(
                 x.shape[:2] + (cfg.num_attention_heads, cfg.head_dim),
                 x.dtype))
    gone = set()
    if fused_attn:
        gone |= {"attn_out", "attn_q", "attn_k", "attn_v"}
    elif not flash:
        gone.add("attn_out")
    if fused_mlp:
        gone |= {"mlp_gate", "mlp_up"}
    return tuple(n for n in SAVED_NAMES if n not in gone)


def _fused_block_modes(cfg: LlamaConfig, x, cp_mesh, cp_axis_level):
    """(use_fused_attention, use_fused_mlp) — resolved at trace time from
    cfg.fused_blocks, the shape and the mesh. "on" engages wherever the
    kernels can run, including the Pallas interpreter — which is how
    parity tests exercise this. Mesh rule, on top of
    pallas_ops.kernel_axes: the kernels take whole [H, H] / [H, I]
    weights and add the residual inside, so under tensor parallelism
    (mp > 1: column/row weight shards, a psum before the residual) they
    are excluded; with mp == 1 they run per device over the batch axes."""
    from ..ops import pallas_ops
    if cfg.fused_blocks == "off":
        return False, False
    if pallas_ops.kernel_axes() and \
            jax.sharding.get_abstract_mesh().shape.get("mp", 1) > 1:
        return False, False
    attn_ok = (cp_mesh is None and not cp_axis_level
               and cfg.num_key_value_heads == cfg.num_attention_heads
               and pallas_ops.fused_attention_available(
                   x.shape, cfg.head_dim, x.dtype))
    mlp_ok = (cfg.moe_num_experts == 0
              and pallas_ops.fused_mlp_available(
                  x.shape, cfg.intermediate_size, x.dtype))
    return attn_ok, mlp_ok


def decoder_layer(cfg: LlamaConfig, lp, x, sin, cos, cp_mesh=None,
                  cp_axis="sp", cp_axis_level=False):
    """One decoder block on a per-layer param slice (no leading L axis)."""
    from ..ops import pallas_ops
    fused_attn, fused_mlp = _fused_block_modes(cfg, x, cp_mesh,
                                               cp_axis_level)
    if isinstance(lp.get("wq"), dict) or isinstance(lp.get("w_gate"), dict):
        # int8 quantize_params leaves: the fused-block kernels take dense
        # weight refs, so quantized layers always use the unfused
        # composition (whose matmuls dispatch through _qmm)
        fused_attn = fused_mlp = False
    with jax.named_scope("attn"):
        if fused_attn:
            # norm + qkv + rope + flash + wo + residual in two Pallas
            # kernels
            h = _per_device(
                functools.partial(pallas_ops.fused_attention_block,
                                  head_dim=cfg.head_dim,
                                  eps=cfg.rms_norm_eps),
                (x,), (lp["ln1"], lp["wq"], lp["wk"], lp["wv"], lp["wo"],
                       sin, cos))
        else:
            h = x + _attention(cfg, lp,
                               _rms_norm(x, lp["ln1"], cfg.rms_norm_eps),
                               sin, cos, cp_mesh=cp_mesh, cp_axis=cp_axis,
                               cp_axis_level=cp_axis_level)
    with jax.named_scope("mlp"):
        if cfg.moe_num_experts > 0:
            mlp_out, aux = _moe_mlp(
                cfg, lp, _rms_norm(h, lp["ln2"], cfg.rms_norm_eps))
            return h + mlp_out, aux
        if fused_mlp:
            # norm + gate/up + silu + down + residual in one Pallas kernel
            out = _per_device(
                functools.partial(pallas_ops.fused_mlp_block,
                                  eps=cfg.rms_norm_eps),
                (h,), (lp["ln2"], lp["w_gate"], lp["w_up"], lp["w_down"]))
            return out, jnp.zeros((), jnp.float32)
        normed = _rms_norm(h, lp["ln2"], cfg.rms_norm_eps)
        return h + _dense_mlp(lp, normed), jnp.zeros((), jnp.float32)


def run_layer_stack(cfg: LlamaConfig, stacked, x, sin, cos,
                    cp_mesh=None, cp_axis="sp", cp_axis_level=False,
                    grad_sync_axis=None, save=SAVED_NAMES):
    """lax.scan over the stacked layer axis (compiler-friendly sequential
    control flow; remat per layer = the recompute strategy: the backward
    pass keeps a layer's input and, of SAVED_NAMES, those in ``save``,
    and recomputes the rest of the layer).

    grad_sync_axis: when set (manual shard_map data parallelism), each
    layer's parameter slice is routed through ``reduce_in_backward`` so
    the transposed scan emits one gradient all-reduce per layer *inside*
    the backward loop — overlapped with the remaining backward compute —
    instead of a single fused tail collective."""
    layer_fn = functools.partial(decoder_layer, cp_axis_level=cp_axis_level,
                                 cp_mesh=cp_mesh,
                                 cp_axis=cp_axis)

    def body(carry, lp):
        h, aux = carry
        if grad_sync_axis is not None:
            from ..distributed.overlap import reduce_tree_in_backward
            lp = reduce_tree_in_backward(lp, grad_sync_axis)
        fn = layer_fn
        if cfg.use_remat:
            fn = jax.checkpoint(
                layer_fn, static_argnums=(0,),
                policy=jax.checkpoint_policies.save_only_these_names(*save))
        h, a = fn(cfg, lp, h, sin, cos)
        return (h, aux + a), None
    with jax.named_scope("layers"):
        (x, aux), _ = lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               stacked)
    return x, aux


def forward_pure(cfg: LlamaConfig, params, input_ids, sp_axis=None,
                 cp_mesh=None, cp_axis="sp", grad_sync_axis=None,
                 save=SAVED_NAMES):
    """Full forward: ids -> logits (fp32). sp_axis: mesh axis name to shard
    the sequence dimension of activations on (Megatron-style sequence
    parallelism for the elementwise/norm work). cp_mesh: enable ring-
    attention context parallelism over the mesh's 'sp' axis — sequence
    sharded end to end, exact causal attention at O(S/sp) memory. save:
    what the layers keep for a backward pass (run_layer_stack)."""
    B, S = input_ids.shape
    sin, cos = _rope_tables(cfg, S)
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], input_ids, axis=0)
    if cp_mesh is not None:
        # pin ONLY the sequence dim: UNCONSTRAINED (not None — None means
        # replicated) leaves batch/hidden placement to GSPMD, so dp batch
        # sharding survives and no 'dp' axis is required of cp meshes
        x = lax.with_sharding_constraint(
            x, P(P.UNCONSTRAINED, cp_axis, P.UNCONSTRAINED))
    elif sp_axis is not None:
        x = lax.with_sharding_constraint(x, P("dp", sp_axis, None))
    x, aux = run_layer_stack(cfg, params["layers"], x, sin, cos,
                             cp_mesh=cp_mesh, cp_axis=cp_axis,
                             grad_sync_axis=grad_sync_axis, save=save)
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        logits = _qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, aux


def loss_fn(cfg: LlamaConfig, params, batch, sp_axis=None,
            cp_mesh=None, cp_axis="sp", grad_sync_axis=None,
            save=SAVED_NAMES):
    ids, labels = batch["input_ids"], batch["labels"]
    logits, aux = forward_pure(cfg, params, ids, sp_axis, cp_mesh=cp_mesh,
                               cp_axis=cp_axis,
                               grad_sync_axis=grad_sync_axis, save=save)
    # logsumexp form: ce = lse - target_logit. Avoids materializing the
    # full [B, S, V] log-softmax (1 GB fp32 at bench shapes) — XLA fuses
    # the reduction into the lm_head matmul epilogue.
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    ce = jnp.mean(lse - tgt)
    return ce + 0.01 * aux, ce


# ---------------------------------------------------------------------------
# KV-cache inference (models/decoding.py core)
# ---------------------------------------------------------------------------

def forward_with_cache(cfg: LlamaConfig, params, tokens, cache, pos):
    """Chunked cached forward: process ``tokens`` [B, T] starting at
    sequence offset ``pos`` against per-layer KV caches. For dense
    configs this is the same math as forward_pure (rope at absolute
    positions, GQA-width cache) — cached greedy decode reproduces the
    uncached forward token-for-token (asserted in test_generation).
    MoE configs decode with per-chunk capacity (C computed from the
    chunk's tokens, so single-token steps are effectively dropless); this
    intentionally differs from the training forward, whose GShard
    capacity makes tokens compete across the whole sequence. Serves both
    prefill (T=prompt) and decode (T=1)."""
    from .decoding import KVCache, cached_attention_core

    B, T = tokens.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    H = cfg.hidden_size
    sin_full, cos_full = _rope_tables(cfg, cfg.max_position_embeddings)
    sin = lax.dynamic_slice_in_dim(sin_full, pos, T, axis=0)
    cos = lax.dynamic_slice_in_dim(cos_full, pos, T, axis=0)
    x = jnp.take(params["embed"], tokens, axis=0)

    def body(h, inp):
        lp, ck, cv = inp
        xn = _rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
        q = _apply_rope(_qmm(xn, lp["wq"]).reshape(B, T, nh, d), sin, cos)
        k = _apply_rope(_qmm(xn, lp["wk"]).reshape(B, T, nkv, d), sin, cos)
        v = _qmm(xn, lp["wv"]).reshape(B, T, nkv, d)
        out, ck, cv = cached_attention_core(q, k, v, ck, cv, pos)
        h = h + _qmm(out.reshape(B, T, H), lp["wo"])
        hn = _rms_norm(h, lp["ln2"], cfg.rms_norm_eps)
        if cfg.moe_num_experts > 0:
            mlp_out, _aux = _moe_mlp(cfg, lp, hn)
            h = h + mlp_out
        else:
            h = h + _dense_mlp(lp, hn)
        return h, (ck, cv)

    x, (new_k, new_v) = lax.scan(body, x,
                                 (params["layers"], cache.k, cache.v))
    x = _rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
    logits = _qmm(x, params["lm_head"]).astype(jnp.float32)
    return logits, KVCache(new_k, new_v)


def forward_paged(cfg: LlamaConfig, params, tokens, k_pages, v_pages,
                  block_tables, seq_lens, q_lens, *,
                  k_scales=None, v_scales=None, step_tokens=None):
    """Ragged mixed prefill+decode forward over a paged KV cache (the
    serving engine's step function).

    tokens        [R, Tc] int32   current-chunk token slots; request r
                                  uses tokens[r, :q_lens[r]]
    k/v_pages     [L, nkv, P, page, d] the stacked pools of all layers
    block_tables  [R, Bmax] i32   pool page of each logical kv block
                                  (page 0 = the allocator's reserved
                                  null page: never written, never read)
    seq_lens      [R] i32         total kv length incl. this chunk
    q_lens        [R] i32         chunk lengths (0 = inactive slot)
    k/v_scales    [L, nkv, P] f32 per-page dequant scales — presence
                                  selects the quantized-KV path: pools
                                  hold int8 pages, new k/v are
                                  quantize-on-write requantized per
                                  page, and attention dequants on read
    step_tokens   int or None     how many positions the program
                                  computes: None is all ``R x Tc``; a
                                  count ``T`` is the token-major step,
                                  whose caller guarantees
                                  ``sum(q_lens) <= T``

    Fixed shapes throughout — one compilation per (R, Tc, pool)
    signature.  Rope runs at each token's absolute position
    (seq_lens - q_lens + t), new k/v are written through the block
    table (``ops.pallas_ops.paged_kv_write``), and attention is
    ``ops.pallas_ops.ragged_paged_attention`` (jnp reference off-TPU).
    Returns (logits [R, Tc, V] fp32, (k_pages, v_pages)) — with
    scales, (k_pages, v_pages, k_scales, v_scales); logits in padding
    rows are garbage by contract — callers read row q_lens[r] - 1.

    What is per token (embedding, norms, projections and rope, the MLP,
    the head) runs on the flat batch ``[T, ...]`` of ``StepLayout``; the
    K/V write and the attention take the padded ``[R, Tc, ...]`` rows
    and hand their result back flat.  With ``step_tokens`` the logits
    come back flat too, ``[T, V]``: token ``start[r] + t`` is row r's
    position t (``StepLayout.last`` is each row's last fed token).

    The pools stay one buffer.  They go round the ONE ``lax.scan`` over
    the layers whole, in its carry beside the hidden state (the scanned
    inputs are the layer weights and the layer counter), and come back
    as the same arrays: a caller that donates them (the engine does)
    gets them updated in place, argument to result.  No operation
    slices a layer's pool out of the stack or writes one back — the
    write touches only the new tokens' rows at (layer, :, page, row),
    the attention kernel takes the stack and the layer index, and the
    int8 path gathers and scatters its window pages at
    (layer, :, page) with this layer's [nkv, P] scales sliced out for
    the kernel.  (Scanning the pools instead costs a slice, two
    relayouts and a write-back of every layer's pool on every layer
    and a second copy of both stacks: half the serve step, PERF.md
    section 6, PR 26.)

    Quantized-KV write path: a per-request window of W logical blocks
    starting at the chunk's first page is gathered, dequantized,
    updated with the chunk's new tokens, re-scaled per page (absmax /
    127) and requantized back.  Window positions at/beyond seq_len are
    zero-masked before the rescale, so a recycled page's previous
    content can never leak into the new owner's page scale — writes
    are a pure function of the request's own tokens, which keeps
    replay after preemption and prefix-cache reuse deterministic.
    Requantization is exact for untouched tokens while the page scale
    is unchanged (dequant of q*s is lossless and the absmax token
    requants to ±127), but a page written under a different chunking
    schedule can differ in the last int8 bit — quantized streams are
    parity-within-tolerance, not bit-identical (docs/serving.md).
    Window slots whose block-table entry is 0 (unallocated → the
    reserved null page) are dropped from the scatter, keeping the
    null page zero."""
    from ..ops.pallas_ops import paged_kv_write, ragged_paged_attention

    R, Tc = tokens.shape
    nh, nkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.head_dim
    H = cfg.hidden_size
    rep = nh // nkv
    page = k_pages.shape[3]
    num_pages = k_pages.shape[2]
    lay = StepLayout(q_lens, Tc, step_tokens)
    T = lay.T

    # absolute position of each token slot, clipped for the rope gather
    start = (seq_lens - q_lens).astype(jnp.int32)        # [R]
    t_off = jnp.arange(Tc, dtype=jnp.int32)
    qpos = start[:, None] + t_off[None, :]               # [R, Tc]
    valid = t_off[None, :] < q_lens[:, None]             # [R, Tc]
    qpos_c = jnp.clip(qpos, 0, cfg.max_position_embeddings - 1)
    sin_full, cos_full = _rope_tables(cfg, cfg.max_position_embeddings)
    pos = lay.flat(qpos_c)                               # [T]
    sin = jnp.take(sin_full, pos, axis=0)                # [T, D]
    cos = jnp.take(cos_full, pos, axis=0)

    def rope(x):
        # per-token tables (ragged positions), else same as _apply_rope
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return (x * cos[:, None, :].astype(x.dtype)
                + rot * sin[:, None, :].astype(x.dtype))

    quant_kv = k_scales is not None
    if quant_kv:
        # R-M-W window per request: W logical blocks from the chunk's
        # first page (covers Tc tokens straddling page boundaries)
        Bmax = block_tables.shape[1]
        W = Tc // page + 2
        first_blk = (jnp.maximum(start, 0) // page).astype(jnp.int32)
        wblk = first_blk[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        wvalid = (wblk < Bmax) & (q_lens > 0)[:, None]         # [R, W]
        phys_w = jnp.take_along_axis(
            block_tables, jnp.clip(wblk, 0, Bmax - 1), axis=1)
        wvalid = wvalid & (phys_w > 0)   # never write the null page
        flat_w = phys_w.reshape(-1)                            # [R*W]
        # OOB sentinel + mode="drop" discards invalid window slots
        scatter_pg = jnp.where(wvalid.reshape(-1), flat_w, num_pages)
        rel = qpos - (first_blk * page)[:, None]               # [R, Tc]
        rel_c = jnp.where(valid, rel, W * page)                # OOB drop
        rows = jnp.broadcast_to(
            jnp.arange(R, dtype=jnp.int32)[:, None], (R, Tc))
        # window positions at/beyond seq_len hold garbage (recycled
        # pages keep their previous owner's bytes); zero them so the
        # page absmax — and therefore every written byte — depends
        # only on this request's own tokens
        wpos = (first_blk * page)[:, None] \
            + jnp.arange(W * page, dtype=jnp.int32)[None, :]   # [R,W*page]
        live = wpos < seq_lens[:, None]

        def quant_write(pool, scales, l, new_t):
            # pool [L, nkv, P, page, d] int8 · scales [L, nkv, P] f32 ·
            # new_t [R, Tc, nkv, d] f32 — gather layer l's window, dequant,
            # insert new tokens, per-page absmax rescale, requantize.
            # (l, :, pages) indexes the stack itself: the layer and the
            # page indices are apart, so the gathered axis leads
            win = pool[l, :, flat_w].astype(jnp.float32)  # [R*W,nkv,page,d]
            sc = scales[l, :, flat_w]                      # [R*W, nkv]
            deq = (win * sc[:, :, None, None]).reshape(R, W, nkv, page, d)
            deq = deq.at[rows, rel_c // page, :, rel_c % page].set(
                new_t, mode="drop")
            deq = jnp.where(live.reshape(R, W, 1, page, 1), deq, 0.0)
            amax = jnp.max(jnp.abs(deq), axis=(3, 4))          # [R,W,nkv]
            new_sc = jnp.maximum(amax, 1e-8) / 127.0
            qp = jnp.clip(jnp.round(deq / new_sc[..., None, None]),
                          -127, 127).astype(pool.dtype)
            pool = pool.at[l, :, scatter_pg].set(
                qp.reshape(R * W, nkv, page, d), mode="drop")
            scales = scales.at[l, :, scatter_pg].set(
                new_sc.reshape(R * W, nkv), mode="drop")
            return pool, scales

    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], lay.flat(tokens), axis=0)   # [T, H]

    def kv_write(pools, l, k, v):
        # k, v [R, Tc, nkv, d]; only the new tokens are written, into
        # the stack: nothing here yields a layer's pool
        if quant_kv:
            kp, vp, ks, vs = pools
            kp, ks = quant_write(kp, ks, l, k.astype(jnp.float32))
            vp, vs = quant_write(vp, vs, l, v.astype(jnp.float32))
            return kp, vp, ks, vs
        return paged_kv_write(*pools, k, v, block_tables, seq_lens,
                              q_lens, layer=l)

    def attn(h, lp, pools, l):
        xn = _rms_norm(h, lp["ln1"], cfg.rms_norm_eps)
        q = lay.rows(rope(_qmm(xn, lp["wq"]).reshape(T, nh, d)))
        k = lay.rows(rope(_qmm(xn, lp["wk"]).reshape(T, nkv, d)))
        v = lay.rows(_qmm(xn, lp["wv"]).reshape(T, nkv, d))
        with jax.named_scope("kv_write"):
            pools = kv_write(pools, l, k, v)
        # kernel layout [R, nkv, Tc*rep, d]: row t*rep + j = q head
        # k*rep + j of token t (the h // rep GQA mapping)
        qk = q.reshape(R, Tc, nkv, rep, d).transpose(
            0, 2, 1, 3, 4).reshape(R, nkv, Tc * rep, d)
        out = ragged_paged_attention(
            qk, pools[0], pools[1], block_tables, seq_lens, q_lens,
            rep=rep, layer=l,
            **dict(zip(("k_scales", "v_scales"), pools[2:])))
        out = lay.flat(out.reshape(R, nkv, Tc, rep, d).transpose(
            0, 2, 1, 3, 4).reshape(R, Tc, H))
        return h + _qmm(out.astype(h.dtype), lp["wo"]), pools

    def body(carry, inp):
        h, pools = carry
        lp, l = inp
        with jax.named_scope("attn"):
            h, pools = attn(h, lp, pools, l)
        with jax.named_scope("mlp"):
            hn = _rms_norm(h, lp["ln2"], cfg.rms_norm_eps)
            if cfg.moe_num_experts > 0:
                mlp_out, _aux = _moe_mlp(cfg, lp, hn[None])
                h = h + mlp_out[0]
            else:
                h = h + _dense_mlp(lp, hn)
        return (h, pools), None

    # the pools go round the layer loop whole, in the carry: a scanned
    # input or a stacked output would be a slice and a write-back of a
    # layer's whole pool on every layer, and a second copy of the stacks
    pools = (k_pages, v_pages) + ((k_scales, v_scales) if quant_kv else ())
    n_layers = k_pages.shape[0]
    with jax.named_scope("layers"):
        (x, pools), _ = lax.scan(
            body, (x, pools),
            (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["norm_f"], cfg.rms_norm_eps)
        logits = _qmm(x, params["lm_head"]).astype(jnp.float32)
    return (logits if lay.compact else lay.rows(logits)), pools


# ---------------------------------------------------------------------------
# the engine's protocol (``cfg.serving``): the cache is the bare page pools
# ---------------------------------------------------------------------------

def init_cache(cfg: LlamaConfig, slots: int, num_pages: int, page_size: int,
               kv_dtype):
    """The cache ``forward_paged`` runs on, as one pytree: zeroed page pools
    ``(k_pages, v_pages)``; with int8 pages also the per-page scale pools at
    1.0, so untouched (all-zero) pages dequant to exact zeros.  Nothing is
    held per slot."""
    del slots
    L, nkv = cfg.num_hidden_layers, cfg.num_key_value_heads
    shape = (L, nkv, num_pages, page_size, cfg.head_dim)
    cache = (jnp.zeros(shape, kv_dtype), jnp.zeros(shape, kv_dtype))
    if jnp.dtype(kv_dtype) == jnp.dtype(jnp.int8):
        cache += (jnp.ones((L, nkv, num_pages), jnp.float32),
                  jnp.ones((L, nkv, num_pages), jnp.float32))
    return cache


def cache_bytes(cfg: LlamaConfig, kv_dtype_bytes: int = 2,
                page_size: int = 128) -> dict:
    """What the cache costs: K/V bytes a token over all layers, scale bytes
    a page (two float32 a layer and K/V head beside sub-2-byte pages) and
    bytes a slot (none: no state but the pages)."""
    heads = cfg.num_hidden_layers * cfg.num_key_value_heads
    return {"per_token": 2 * heads * cfg.head_dim * kv_dtype_bytes,
            "scales_per_page": 2 * heads * 4 if kv_dtype_bytes < 2 else 0,
            "per_slot": 0}


def param_count(cfg: LlamaConfig) -> int:
    """Dense parameter count from the config (embed + L blocks + final
    norm + lm_head), the number that dominates serving HBM."""
    H, I = cfg.hidden_size, cfg.intermediate_size
    nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)
    per_layer = (H * nh * d + 2 * H * nkv * d + nh * d * H  # attn
                 + 3 * H * I                                 # gated mlp
                 + 2 * H)                                    # norms
    return (cfg.vocab_size * H * 2                           # embed+head
            + cfg.num_hidden_layers * per_layer + H)


def _serve_step(cfg, params, tokens, cache, block_tables, seq_lens, q_lens,
                step_tokens=None):
    """``forward_paged`` on the cache as one pytree."""
    k_pages, v_pages, *scales = cache
    return forward_paged(cfg, params, tokens, k_pages, v_pages, block_tables,
                         seq_lens, q_lens, step_tokens=step_tokens,
                         **dict(zip(("k_scales", "v_scales"), scales)))


SERVING = types.SimpleNamespace(
    forward_paged=_serve_step, init_cache=init_cache,
    cache_bytes=cache_bytes, param_count=param_count,
    # int8 weight path: PTQ the serving weights once at engine build; asked
    # for by the config, never by the platform
    prepare_params=lambda cfg, params: (
        quantize_params(cfg, params) if cfg.quantized == "on" else params),
    recurrent_state=False)


def _cfg_key(cfg):
    return tuple(sorted((k, str(v))
                        for k, v in dataclasses.asdict(cfg).items()))


def generate(cfg: LlamaConfig, params, input_ids, max_new_tokens,
             temperature=0.0, top_k=0, rng=None, eos_token_id=None):
    """[B, P] prompt -> [B, max_new_tokens] continuations, whole decode
    loop on device (one compiled scan, memoized per signature)."""
    from .decoding import model_generate

    return model_generate(
        functools.partial(forward_with_cache, cfg),
        num_layers=cfg.num_hidden_layers,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        max_positions=cfg.max_position_embeddings, cache_dtype=cfg.dtype,
        cache_key=("llama", _cfg_key(cfg)), params=params,
        input_ids=input_ids, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, rng=rng,
        eos_token_id=eos_token_id)


# ---------------------------------------------------------------------------
# parallel train step
# ---------------------------------------------------------------------------

def build_train_step(cfg: LlamaConfig, topo, optimizer=None, use_pp=None,
                     n_microbatches=None, zero=True, schedule="gpipe",
                     virtual_pp=None, overlap=False):
    """Compiled full training step over the hybrid mesh.

    Returns (step_fn, init_fn):
      init_fn(rng) -> (params, opt_state) placed per param_specs (+ZeRO
      opt-state sharding over 'dp').
      step_fn(params, opt_state, batch) -> (params, opt_state, metrics).

    use_pp: pipeline over the 'pp' axis with shard_map; defaults to
    pp_degree > 1. schedule: "gpipe" (autodiff-transposed scan) or "1f1b"
    (hand-scheduled forward/backward interleave, O(pp) activation
    residency — reference pipeline_parallel.py:228).

    overlap: enable compute/communication overlap. With schedule='1f1b'
    the pipeline issues stage-boundary ppermutes one tick ahead of the
    consuming compute (double-buffered edge activations). On a pure-DP
    topology the gradient all-reduce is split into per-layer psums
    emitted inside the backward scan (``reduce_in_backward``) plus
    bucketed collectives for the tail params, instead of one fused tail
    all-reduce. Other topologies ignore the flag.
    """
    import optax
    if schedule not in ("gpipe", "1f1b", "interleaved"):
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}; expected 'gpipe', "
            "'1f1b' or 'interleaved'")
    if virtual_pp is not None and schedule != "interleaved":
        raise ValueError(
            "virtual_pp only applies to schedule='interleaved'")
    mesh = topo.mesh
    pp = topo.pp_degree
    use_pp = (pp > 1) if use_pp is None else use_pp
    cp_in_pp = use_pp and getattr(topo, "sp_degree", 1) > 1
    if cp_in_pp and schedule != "gpipe":
        raise ValueError(
            "context parallelism (sp > 1) composes with pipeline "
            "parallelism on the GPipe schedule only (ring attention "
            "inside the pp x sp shard_map); use schedule='gpipe' or "
            "drop one axis")
    opt = optimizer or optax.adamw(3e-4, b1=0.9, b2=0.95, weight_decay=0.1)
    specs = param_specs(cfg)

    grad_fn = None
    if use_pp and schedule == "1f1b":
        from ..distributed.pipeline import pipeline_1f1b_value_and_grad

        def grad_fn(params, batch):
            total, ce, grads = pipeline_1f1b_value_and_grad(
                cfg, mesh, n_microbatches or pp, params, batch,
                overlap=overlap)
            return (total, ce), grads
    elif use_pp and schedule == "interleaved":
        from ..distributed.pipeline import pipeline_interleaved_loss_fn
        # virtual stages per device: as many 2-chunk splits as the layer
        # count allows (the reference's virtual_pp_degree)
        v = virtual_pp or (2 if cfg.num_hidden_layers % (pp * 2) == 0
                           else 1)
        loss = functools.partial(pipeline_interleaved_loss_fn, cfg, mesh,
                                 n_microbatches or pp, v)
    elif use_pp:
        from ..distributed.pipeline import pipeline_loss_fn
        loss = functools.partial(pipeline_loss_fn, cfg, mesh,
                                 n_microbatches or pp,
                                 cp_axis="sp" if cp_in_pp else None)
    else:
        cp_mesh = mesh if getattr(topo, "sp_degree", 1) > 1 else None
        dp_deg = topo.dims.get("dp", 1)
        # pure-DP overlap: manual shard_map over 'dp' with per-layer
        # backward-scan gradient psums + bucketed tail collectives. Only
        # sound when no other axis carries model state (params fully
        # replicated across 'dp').
        overlap_dp = (overlap and cp_mesh is None and dp_deg > 1
                      and topo.dims.get("mp", 1) == 1
                      and topo.dims.get("sharding", 1) == 1
                      and cfg.moe_num_experts == 0)
        if overlap_dp:
            from ..distributed.overlap import bucketed_psum

            def _dp_body(save, params, batch):
                def local_loss(p):
                    # local mean loss scaled by 1/dp: psum of its grads
                    # over 'dp' is exactly the global-batch gradient
                    t, c = loss_fn(cfg, p, batch, grad_sync_axis="dp",
                                   save=save)
                    return t / dp_deg, (t, c)
                (_, (t, c)), grads = jax.value_and_grad(
                    local_loss, has_aux=True)(params)
                # layer grads were psum'd per layer inside the backward
                # scan; the non-stacked tail reduces in byte-bounded
                # buckets so early buckets overlap late backward compute
                tail = bucketed_psum(
                    {k: v for k, v in grads.items() if k != "layers"},
                    "dp")
                grads = dict(grads, **tail)
                return lax.pmean(t, "dp"), lax.pmean(c, "dp"), grads

            def grad_fn(params, batch):
                param_p = jax.tree_util.tree_map(lambda _: P(), params)
                save = kept(batch)
                total, ce, grads = jax.shard_map(
                    functools.partial(_dp_body, save), mesh=mesh,
                    in_specs=(param_p,
                              {"input_ids": P("dp", None),
                               "labels": P("dp", None)}),
                    out_specs=(P(), P(), param_p),
                    axis_names={"dp"}, check_vma=False)(params, batch)
                return (total, ce), grads
        else:
            def loss(params, batch):
                save = kept(batch)
                if cp_mesh is not None:  # ring-attention context parallel
                    return loss_fn(cfg, params, batch, cp_mesh=cp_mesh,
                                   save=save)
                return loss_fn(cfg, params, batch, sp_axis="mp", save=save)

    from ._sharding_utils import sharding_tree
    param_sh = sharding_tree(mesh, specs)

    # ZeRO axis: the dedicated 'sharding' axis when the topology carves
    # one out (fleet's 4-D ["data","pipe","sharding","model"]), else the
    # data axis itself (pure-DP ZeRO)
    zero_axis = "sharding" if topo.dims.get("sharding", 1) > 1 else "dp"
    zero_degree = topo.dims.get(zero_axis, 1)

    def zero_shard_spec(spec, shape):
        # ZeRO-1: shard the largest unsharded dim of each optimizer-state
        # array over the zero axis when divisible
        dims = list(spec) + [None] * (len(shape) - len(spec))
        if not zero or zero_axis in dims or not shape:
            return P(*dims) if dims else P()
        n = zero_degree
        for i, d in sorted(enumerate(shape), key=lambda t: -t[1]):
            if dims[i] is None and d % n == 0 and d >= n:
                dims[i] = zero_axis
                break
        return P(*dims)

    # map each opt-state leaf to the spec of its matching param by
    # pytree path: optax states (mu/nu/trace/...) mirror the param
    # tree under a state-field prefix, so the param's path is a
    # suffix of the state leaf's path. Shape-keyed matching would
    # collide for same-shape params (wq/wo both (L,H,H)) and hand
    # Adam moments the wrong placement.
    flat_specs, _ = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda s: isinstance(s, P))
    spec_by_path = [(jax.tree_util.keystr(path), s)
                    for path, s in flat_specs]

    def init_fn(rng):
        with jax.set_mesh(mesh):
            params = jax.jit(
                lambda k: init_params(cfg, k),
                out_shardings=param_sh)(rng)
            opt_state = jax.jit(
                opt.init,
                out_shardings=None)(params)
            # re-place opt state with ZeRO sharding
            def place(x, pspec):
                if not hasattr(x, "shape") or x.ndim == 0:
                    return x  # scalars: replicate_scalars below
                return jax.device_put(
                    x, NamedSharding(mesh, zero_shard_spec(
                        pspec, x.shape)))

            def place_leaf(path, x):
                key = jax.tree_util.keystr(path)
                pspec = next((s for pk, s in spec_by_path
                              if key.endswith(pk)), P())
                return place(x, pspec)

            opt_state = jax.tree_util.tree_map_with_path(
                place_leaf, opt_state)
            from ._sharding_utils import replicate_scalars
            opt_state = replicate_scalars(mesh, opt_state)
        return params, opt_state

    def train_step(params, opt_state, batch):
        with jax.named_scope("fwd_bwd"):
            if grad_fn is not None:
                (total, ce), grads = grad_fn(params, batch)
            else:
                (total, ce), grads = jax.value_and_grad(
                    lambda p: loss(p, batch), has_aux=True)(params)
        with jax.named_scope("optimizer"):
            updates, opt_state = opt.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, {"loss": total, "ce": ce}

    batch_axes = getattr(topo, "batch_axes", "dp")
    batch_sh = {"input_ids": NamedSharding(mesh, P(batch_axes, None)),
                "labels": NamedSharding(mesh, P(batch_axes, None))}
    step_jit = jax.jit(train_step, in_shardings=(param_sh, None, batch_sh),
                       out_shardings=(param_sh, None, None),
                       donate_argnums=(0, 1))

    def step_fn(params, opt_state, batch):
        # set_mesh (not the legacy ``with mesh``): the trace reads the
        # mesh from the context to place Pallas kernels
        # (pallas_ops.kernel_axes)
        with jax.set_mesh(mesh):
            return step_jit(params, opt_state, batch)

    def lower(params, opt_state, batch):
        """The step traced and lowered (not run) under the same mesh
        context ``step_fn`` runs it in; avals do for arguments."""
        with jax.set_mesh(mesh):
            return step_jit.lower(params, opt_state, batch)

    def abstract_state():
        """ShapeDtypeStructs (with shardings) for (params, opt_state) —
        lets tools (pod_report) lower/compile the step and read
        its memory_analysis() without ever materializing the weights."""
        p_abs = jax.eval_shape(functools.partial(init_params, cfg),
                               jax.ShapeDtypeStruct((2,), jnp.uint32))
        p_abs = jax.tree_util.tree_map(
            lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                               sharding=sh),
            p_abs, param_sh)
        o_abs = jax.eval_shape(opt.init, p_abs)

        def leaf_abs(path, x):
            shape = tuple(getattr(x, "shape", ()) or ())
            if not shape:
                sh = NamedSharding(mesh, P())
            else:
                key = jax.tree_util.keystr(path)
                pspec = next((s for pk, s in spec_by_path
                              if key.endswith(pk)), P())
                sh = NamedSharding(mesh, zero_shard_spec(pspec, shape))
            return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sh)

        o_abs = jax.tree_util.tree_map_with_path(leaf_abs, o_abs)
        return p_abs, o_abs

    def device_bytes(tree):
        return sum(math.prod(l.sharding.shard_shape(l.shape))
                   * l.dtype.itemsize
                   for l in jax.tree_util.tree_leaves(tree))

    @functools.lru_cache(maxsize=None)
    def residuals(batch_shape):
        """``saved_residuals`` for a global batch of this shape on this
        topology: what the layers of the step keep for the backward pass
        of what they name (``layer_names``), decided (and logged) once a
        shape, as the step is traced."""
        B, S = batch_shape
        split = math.prod(topo.dims.get(a, 1)
                          for a in ("dp", "sharding", "sp"))
        p_abs, o_abs = abstract_state()
        limit = _memory_limit(mesh)
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            named = layer_names(
                cfg, jax.ShapeDtypeStruct((B, S, cfg.hidden_size),
                                          cfg.dtype),
                cp_mesh=cp_mesh, kernels=not overlap_dp)
        names, nbytes, estimate = saved_residuals(
            cfg, B * S // split, S, device_bytes(p_abs),
            device_bytes(o_abs), limit, mp=topo.dims.get("mp", 1),
            names=named)
        _LOG.info("train step of %s x %s tokens: layers keep %s for the "
                  "backward pass (%d bytes a device); estimated peak %d "
                  "bytes a device of %s", B, S, ",".join(names) or "nothing",
                  nbytes, estimate, limit)
        return names, nbytes, estimate

    def kept(batch):
        if not cfg.use_remat:   # nothing is rematerialised: no rule
            return ()
        return residuals(batch["input_ids"].shape)[0]

    step_fn.jitted = step_jit
    step_fn.lower = lower
    step_fn.abstract_state = abstract_state
    # the pipeline schedules keep all of SAVED_NAMES, and a step whose
    # layers are not rematerialised keeps everything: no rule to report
    if cfg.use_remat and not use_pp:
        step_fn.residuals = residuals
    step_fn.batch_shardings = batch_sh
    return step_fn, init_fn


# ---------------------------------------------------------------------------
# eager Layer face
# ---------------------------------------------------------------------------

from ..nn.layer.layers import Layer, Parameter  # noqa: E402
from ..core.tensor import Tensor, apply_op  # noqa: E402


class LlamaForCausalLM(GenerationMixin, Layer):
    """Eager/dygraph face over the functional core: parameters are the same
    stacked pytree exposed as Layer parameters, so state_dict naming is
    stable and the eager forward matches forward_pure bit-for-bit."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        key = jax.random.PRNGKey(0)
        raw = init_params(config, key)
        self._flat = {}
        for name, arr in _flatten_params(raw):
            p = Parameter(arr)
            p.name = name
            self.add_parameter(name.replace(".", "_"), p)
            self._flat[name] = p

    def _tree(self):
        raw = {}
        for name, p in self._flat.items():
            raw[name] = p._array
        return _unflatten_params(raw)

    def forward(self, input_ids, labels=None):
        cfg = self.config
        flat_names = list(self._flat)
        tensors = [self._flat[n] for n in flat_names]

        def _f(ids, *arrs):
            raw = dict(zip(flat_names, arrs))
            params = _unflatten_params(raw)
            logits, aux = forward_pure(cfg, params, ids)
            return logits
        ids_t = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(np.asarray(input_ids)))
        logits = apply_op(_f, ids_t, *tensors, op_name="llama_forward")
        return self._maybe_loss(logits, labels)

    def _maybe_loss(self, logits, labels):
        if labels is not None:
            from ..nn import functional as F
            from ..tensor.manipulation import reshape
            V = logits.shape[-1]
            loss = F.cross_entropy(reshape(logits, [-1, V]),
                                   reshape(labels, [-1]))
            return loss, logits
        return logits


def _flatten_params(tree, prefix=""):
    out = []
    for k, v in tree.items():
        name = f"{prefix}.{k}" if prefix else k
        if isinstance(v, dict):
            out.extend(_flatten_params(v, name))
        else:
            out.append((name, v))
    return out


def _unflatten_params(flat):
    tree = {}
    for name, v in flat.items():
        parts = name.split(".")
        node = tree
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return tree


LlamaForCausalLM._generate_fn = staticmethod(generate)
