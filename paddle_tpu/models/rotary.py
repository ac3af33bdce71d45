"""Rotary frequencies, plain or under YaRN, for every model whose attention
turns its queries and keys by position: ``deepseek_v2`` (its 64 rope lanes)
and ``mellum`` (its full layers' 128 lanes).

Pair j of a rotated width ``dr`` turns at ``f_j = theta^(-2j / dr)``.
**YaRN** (a ``rope_parameters``-shaped dict of ``rope_type`` / ``type``
"yarn"): ``low, high`` are the correction range of ``beta_fast`` and
``beta_slow`` rotations over ``original_max_position_embeddings``, floored
and ceiled into ``[0, dr - 1]``; ``ramp_j = clip((j - low) / (high - low),
0, 1)``; the pair turns at ``f_j (1 - ramp_j) + (f_j / factor) ramp_j``.  cos
and sin are scaled by ``attention_factor`` where the dict gives it, else by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` where it gives
both (DeepSeek's: 1 where the two are equal), else by ``mscale(factor, 1)``;
the scores are then scaled by its square.  This is HF's
``_compute_yarn_parameters`` with ``truncate`` as it defaults.
"""
from __future__ import annotations

import math
from typing import Optional

import numpy as np

__all__ = ["frequencies", "yarn_mscale"]


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude correction of a context stretched ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def frequencies(theta: float, width: int, scaling: Optional[dict] = None):
    """``(inv_freq [width / 2] float32 numpy, what cos and sin are scaled
    by)`` for ``width`` rotated lanes of base ``theta``: the plain
    frequencies where ``scaling`` is None or of the type "default", YaRN's
    blend under it (module docstring)."""
    j = np.arange(width // 2, dtype=np.float64)
    freq = theta ** (-2.0 * j / width)
    scaling = scaling or {"rope_type": "default"}
    kind = scaling.get("rope_type", scaling.get("type", "yarn"))
    if kind == "default":
        return freq.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope scaling of type {kind!r}")

    def correction_dim(rotations):
        return (width * math.log(scaling["original_max_position_embeddings"]
                                 / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    factor = scaling["factor"]
    low = max(math.floor(correction_dim(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(scaling.get("beta_slow", 1))),
               width - 1)
    ramp = np.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    inv_freq = freq * (1.0 - ramp) + freq / factor * ramp
    if scaling.get("attention_factor") is not None:
        scale = scaling["attention_factor"]
    elif scaling.get("mscale") and scaling.get("mscale_all_dim"):
        scale = (yarn_mscale(factor, scaling["mscale"])
                 / yarn_mscale(factor, scaling["mscale_all_dim"]))
    else:
        scale = yarn_mscale(factor)
    return inv_freq.astype(np.float32), float(scale)
