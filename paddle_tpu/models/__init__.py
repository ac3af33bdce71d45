"""Flagship model families (functional cores + Layer facades).

- llama: RoPE/GQA/SwiGLU decoder with 4-D parallel train step (the
  Llama-2 pretrain north star), optional MoE layers, ring-attention CP.
- Served by ``serving.LLMEngine``, with no train step: jamba (Mamba-1
  layers, an attention layer every few: state per slot), phi4flash (Mamba-1
  and window attention, then gated memory units and cross layers on one full
  layer's K/V: rings), deepseek_v2 (latent attention on paged latents,
  experts without drops beside shared ones), longcat_flash (two latent
  sublayers a layer, a shortcut-connected expert layer, zero-compute
  experts), cohere2_moe (parallel blocks of window and global attention
  and sigmoid-routed experts beside averaged shared ones) and mellum
  (sequential blocks of window and YaRN full attention beside
  softmax-routed experts, renormalised).
- What those share: protocol (the engine's ``ServingModel``, the config
  reader), step_layout (a step's frame), paged (dense attention on pages),
  mla, experts, rings, rotary.
- gpt: GPT-2-style decoder (learned positions, fused QKV, GELU, tied head).
- ernie: encoder pretraining family (MLM+NSP).
- decoding: shared KV-cache autoregressive generation.
"""
from . import llama  # noqa: F401
from . import jamba  # noqa: F401
from . import phi4flash  # noqa: F401
from . import experts  # noqa: F401
from . import deepseek_v2  # noqa: F401
from . import gpt  # noqa: F401
from . import ernie  # noqa: F401
from . import decoding  # noqa: F401
from . import convert  # noqa: F401

__all__ = ["llama", "jamba", "phi4flash", "experts", "deepseek_v2", "gpt", "ernie", "decoding", "convert"]
