"""Flagship model families (functional cores + Layer facades).

- llama: RoPE/GQA/SwiGLU decoder with 4-D parallel train step (the
  Llama-2 pretrain north star), optional MoE layers, ring-attention CP.
- jamba: Mamba-1 layers with an attention layer every few (forward and the
  serving engine's ragged step with per-slot recurrent state; no train step).
- phi4flash: Mamba-1 and window attention interleaved, then gated memory
  units and cross layers on one full layer's K/V (forward and the serving
  engine's step with window rings per slot; no train step).
- deepseek_v2: latent (MLA) attention on a paged cache of latent vectors,
  then expert layers that drop no token beside shared experts (``experts``:
  the routed layer itself); forward and the serving engine's step, no train
  step.
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
