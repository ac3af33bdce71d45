"""GPT-2-style decoder family: pre-LN blocks, learned positional
embeddings, fused-QKV projection, GELU MLP, tied LM head.

Reference analog: the GPT nets PaddleNLP trains on the fleet stack (the
reference repo itself ships the fused kernels they ride:
paddle/fluid/operators/fused/fused_multi_transformer_op.cu,
fused_feedforward); architecture follows Radford et al. 2019. Same
functional design as models/llama.py: stacked [L, ...] parameter pytree,
lax.scan over layers, Pallas flash attention when shapes qualify, and
KV-cache generation through models/decoding.py.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.tensor import Tensor, apply_op
from ..nn.layer.layers import Layer, Parameter
from .decoding import GenerationMixin

__all__ = ["GPTConfig", "init_params", "forward_pure", "loss_fn",
           "forward_with_cache", "generate", "GPTForCausalLM"]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0          # 0 -> 4 * hidden
    max_position_embeddings: int = 1024
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16
    use_remat: bool = False
    remat_policy: str = "dots"

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        assert self.hidden_size % self.num_attention_heads == 0

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads


def init_params(cfg: GPTConfig, key) -> Dict[str, Any]:
    H, I, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_hidden_layers
    ks = iter(jax.random.split(key, 8))
    std = 0.02

    def init(k_, shape, scale=1.0):
        return (jax.random.normal(k_, shape, jnp.float32)
                * std * scale).astype(cfg.dtype)

    # residual-path projections scaled by 1/sqrt(2L) (GPT-2 init)
    res = 1.0 / (2 * L) ** 0.5
    return {
        "wte": init(next(ks), (cfg.vocab_size, H)),
        "wpe": init(next(ks), (cfg.max_position_embeddings, H)),
        "layers": {
            "ln1_g": jnp.ones((L, H), cfg.dtype),
            "ln1_b": jnp.zeros((L, H), cfg.dtype),
            "attn_w": init(next(ks), (L, H, 3 * H)),
            "attn_b": jnp.zeros((L, 3 * H), cfg.dtype),
            "proj_w": init(next(ks), (L, H, H), res),
            "proj_b": jnp.zeros((L, H), cfg.dtype),
            "ln2_g": jnp.ones((L, H), cfg.dtype),
            "ln2_b": jnp.zeros((L, H), cfg.dtype),
            "fc_w": init(next(ks), (L, H, I)),
            "fc_b": jnp.zeros((L, I), cfg.dtype),
            "fcp_w": init(next(ks), (L, I, H), res),
            "fcp_b": jnp.zeros((L, H), cfg.dtype),
        },
        "lnf_g": jnp.ones((H,), cfg.dtype),
        "lnf_b": jnp.zeros((H,), cfg.dtype),
    }


def _ln(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps)).astype(x.dtype) * g + b


def _qkv(cfg: GPTConfig, lp, xn):
    B, T, H = xn.shape
    nh, d = cfg.num_attention_heads, cfg.head_dim
    qkv = xn @ lp["attn_w"] + lp["attn_b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    return (q.reshape(B, T, nh, d), k.reshape(B, T, nh, d),
            v.reshape(B, T, nh, d))


def _block(cfg: GPTConfig, lp, x):
    eps = cfg.layer_norm_epsilon
    B, T, H = x.shape
    q, k, v = _qkv(cfg, lp, _ln(x, lp["ln1_g"], lp["ln1_b"], eps))
    from ..ops import pallas_ops
    att = pallas_ops.causal_attention(q, k, v).reshape(B, T, H)
    x = x + att @ lp["proj_w"] + lp["proj_b"]
    hn = _ln(x, lp["ln2_g"], lp["ln2_b"], eps)
    mlp = jax.nn.gelu(hn @ lp["fc_w"] + lp["fc_b"]) @ lp["fcp_w"] \
        + lp["fcp_b"]
    return x + mlp


def forward_pure(cfg: GPTConfig, params, input_ids):
    """ids [B, S] -> logits [B, S, V] fp32 (LM head tied to wte)."""
    B, S = input_ids.shape
    pos = jnp.arange(S)
    x = jnp.take(params["wte"], input_ids, axis=0) \
        + jnp.take(params["wpe"], pos, axis=0)[None]

    def body(h, lp):
        fn = _block
        if cfg.use_remat:
            policy = jax.checkpoint_policies.dots_saveable \
                if cfg.remat_policy == "dots" else None
            fn = jax.checkpoint(_block, static_argnums=(0,), policy=policy)
        return fn(cfg, lp, h), None

    x, _ = lax.scan(body, x, params["layers"])
    x = _ln(x, params["lnf_g"], params["lnf_b"], cfg.layer_norm_epsilon)
    return (x @ params["wte"].T).astype(jnp.float32)


def loss_fn(cfg: GPTConfig, params, batch):
    ids, labels = batch["input_ids"], batch["labels"]
    logits = forward_pure(cfg, params, ids)
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# -- KV-cache inference ------------------------------------------------------

def forward_with_cache(cfg: GPTConfig, params, tokens, cache, pos):
    from .decoding import KVCache, cached_attention_core

    B, T = tokens.shape
    H = cfg.hidden_size
    eps = cfg.layer_norm_epsilon
    positions = pos + jnp.arange(T)
    x = jnp.take(params["wte"], tokens, axis=0) \
        + jnp.take(params["wpe"], positions, axis=0)[None]

    def body(h, inp):
        lp, ck, cv = inp
        q, k, v = _qkv(cfg, lp, _ln(h, lp["ln1_g"], lp["ln1_b"], eps))
        out, ck, cv = cached_attention_core(q, k, v, ck, cv, pos)
        h = h + out.reshape(B, T, H) @ lp["proj_w"] + lp["proj_b"]
        hn = _ln(h, lp["ln2_g"], lp["ln2_b"], eps)
        h = h + jax.nn.gelu(hn @ lp["fc_w"] + lp["fc_b"]) @ lp["fcp_w"] \
            + lp["fcp_b"]
        return h, (ck, cv)

    x, (nk, nv) = lax.scan(body, x, (params["layers"], cache.k, cache.v))
    x = _ln(x, params["lnf_g"], params["lnf_b"], eps)
    return (x @ params["wte"].T).astype(jnp.float32), KVCache(nk, nv)


def generate(cfg: GPTConfig, params, input_ids, max_new_tokens,
             temperature=0.0, top_k=0, rng=None, eos_token_id=None):
    from .decoding import model_generate
    from .llama import cfg_key

    return model_generate(
        functools.partial(forward_with_cache, cfg),
        num_layers=cfg.num_hidden_layers,
        kv_heads=cfg.num_attention_heads, head_dim=cfg.head_dim,
        max_positions=cfg.max_position_embeddings, cache_dtype=cfg.dtype,
        cache_key=("gpt", cfg_key(cfg)), params=params,
        input_ids=input_ids, max_new_tokens=max_new_tokens,
        temperature=temperature, top_k=top_k, rng=rng,
        eos_token_id=eos_token_id)


# -- Layer facade ------------------------------------------------------------

class GPTForCausalLM(GenerationMixin, Layer):
    """Eager face over the functional core (same pattern as
    LlamaForCausalLM: parameters are the stacked pytree)."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        from .llama import flatten_params, unflatten_params
        self._unflatten = unflatten_params
        raw = init_params(config, jax.random.PRNGKey(0))
        self._flat = {}
        for name, arr in flatten_params(raw):
            p = Parameter(arr)
            p.name = name
            self.add_parameter(name.replace(".", "_"), p)
            self._flat[name] = p

    def _tree(self):
        return self._unflatten({n: p._array
                                for n, p in self._flat.items()})

    def forward(self, input_ids, labels=None):
        cfg = self.config
        names = list(self._flat)
        tensors = [self._flat[n] for n in names]

        def _f(ids, *arrs):
            params = self._unflatten(dict(zip(names, arrs)))
            return forward_pure(cfg, params, ids)

        ids_t = input_ids if isinstance(input_ids, Tensor) \
            else Tensor(jnp.asarray(np.asarray(input_ids)))
        logits = apply_op(_f, ids_t, *tensors, op_name="gpt_forward")
        if labels is not None:
            from ..nn import functional as F
            from ..tensor.manipulation import reshape
            V = logits.shape[-1]
            loss = F.cross_entropy(reshape(logits, [-1, V]),
                                   reshape(labels, [-1]))
            return loss, logits
        return logits


GPTForCausalLM._generate_fn = staticmethod(generate)
